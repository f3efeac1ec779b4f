"""Model FLOPs per trained token of the SmolLM-135M configuration.

Forward and backward, no recomputation: 6 FLOPs per matmul parameter per
token (2 forward, 4 backward), plus attention's score and value products.
Causal attention counts half: of the S x S scores a token needs only those
at or before its position, so a kernel that skips the masked half does the
same required work in less time.  Norms, RoPE, softmax and the loss are left
out: they are elementwise and well under 1% of the total.
"""


def matmul_params(m: dict) -> int:
    d, hd = m["d_model"], m["d_model"] // m["n_heads"]
    attn = d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2  # q, o, k, v
    mlp = 3 * d * m["d_ff"]                                          # gate, up, down
    head = d * m["vocab_size"]                  # tied or not, the head is a matmul
    return m["n_layers"] * (attn + mlp) + head


def flops_per_token(cfg: dict) -> float:
    m = cfg["model"]
    seq = cfg["seq_len"]
    d_attn = m["n_heads"] * (m["d_model"] // m["n_heads"])
    # q.k and p.v: 2 * 2 * S * d_attn forward, x3 with backward, halved for causal
    attention = m["n_layers"] * 12 * seq * d_attn / 2
    return 6.0 * matmul_params(m) + attention
