"""RWKV-6 "Finch" 1.6B [arXiv:2404.05892] — attention-free, data-dependent decay.

The released checkpoint is RWKV-x060-World-1B6
(https://huggingface.co/BlinkDL/rwkv-6-world), trained at context 4096:
24 layers, d_model 2048, head size 64 (32 heads), channel-mix width 7168,
vocabulary 65536, untied head, LayerNorms (``ln0`` on the embeddings, two per
block, ``ln_out``) with epsilon 1e-5.

Its decode state is O(1) in sequence length (a 64 x 64 matrix per head and
the two token-shift rows per layer).  Measured on a TPU v5e: the train step
at 1 x 4096 with 4 of the 24 layers (PERF.md); decode at long contexts has
not been run on the chip.
"""
from ..models import ModelConfig

CONFIG = ModelConfig(
    arch_id="rwkv6-1.6b",
    family="ssm",
    n_layers=24,
    d_model=2048,
    n_heads=32,           # derived: d_model / rwkv_head_dim
    d_ff=7168,
    vocab_size=65536,
    norm="layernorm",
    norm_eps=1e-5,
    rwkv_head_dim=64,
    source="arXiv:2404.05892 (Eagle and Finch / RWKV-6); "
           "huggingface.co/BlinkDL/rwkv-6-world (RWKV-x060-World-1B6, ctx 4096)",
)
