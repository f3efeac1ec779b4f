"""Model FLOPs per trained token of the RWKV-6 configuration.

Forward and backward, no recomputation: 6 FLOPs per matmul parameter per
token (2 forward, 4 backward), the LoRAs and the untied head included; plus
the WKV recurrence's required work.  Per token, layer and head the
recurrence decays the N x N state, adds k v^T and reads it out with r:
about 5 N^2 FLOPs forward (the bonus term is O(N)), so 5 * d_model * N a
layer, times 3 with the backward.  That is the recurrence's work, not the
chunked form's, so a cheaper formulation reads as higher utilization and
not as less work.  Token shift, DDLerp's mixing, the norms, the gates and
the loss are elementwise and left out (well under 1 % of the total).
"""

N_MIX, LORA_MIX, LORA_DECAY = 5, 32, 64


def matmul_params(m: dict) -> int:
    d, f = m["d_model"], m["d_ff"]
    time_mix = 5 * d * d                                  # r, k, v, g, o
    loras = d * N_MIX * LORA_MIX + N_MIX * LORA_MIX * d + 2 * d * LORA_DECAY
    channel_mix = 2 * d * f + d * d                       # k, v, r
    head = d * m["vocab_size"]
    return m["n_layers"] * (time_mix + loras + channel_mix) + head


def flops_per_token(cfg: dict) -> float:
    m = cfg["model"]
    wkv = m["n_layers"] * 3 * 5 * m["d_model"] * m["rwkv_head_dim"]
    return 6.0 * matmul_params(m) + wkv
