"""Operations and bytes the algorithms need, computed from shapes.

Kept here so that the yardstick does not move with the model file:
``train_flops_per_token`` is a copy of ``GPT.flops_per_token``
(6N + 12*L*H*T), and the kernel counts are what the mathematics needs,
not what an implementation happens to do: recomputed operations and
padded or re-laid-out bytes do not count.
"""


def gpt_param_count(cfg):
    """Parameters of the pre-LN GPT with a tied head. `cfg` has
    vocab_size, max_seq_len, hidden, layers (ffn is 4x hidden)."""
    V, P, C, L = (cfg["vocab_size"], cfg["max_seq_len"], cfg["hidden"],
                  cfg["layers"])
    F = 4 * C
    per_block = (C * 3 * C + 3 * C) + (C * C + C) + (C * F + F) \
        + (F * C + C) + 4 * C
    return V * C + P * C + L * per_block + 2 * C


def train_flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs per trained token: 6 per parameter for
    the weight matmuls, plus the attention score and value matmuls at
    12 * layers * hidden * seq_len (2*T*hidden each, forward; x3 with
    the backward pass)."""
    return 6 * gpt_param_count(cfg) + 12 * cfg["layers"] * cfg["hidden"] \
        * seq_len


def flash_attention_cost(batch, heads, seq_len, head_dim, causal=True,
                         backward=False, dtype_bytes=2):
    """(FLOPs, HBM bytes) one call of the flash-attention kernel needs.

    Forward: S = QK^T and O = PV, 2*T*T*D multiply-adds each per head,
    halved under a causal mask; reads Q, K, V and writes O once.
    Backward: dV, dP, dQ, dK (four such matmuls; the recomputed S is not
    counted); reads Q, K, V, O, dO and writes dQ, dK, dV once. The
    row statistics (log-sum-exp, delta) are float32 [B, H, T]."""
    matmul = 2 * batch * heads * seq_len * seq_len * head_dim
    if causal:
        matmul //= 2
    panel = batch * heads * seq_len * head_dim * dtype_bytes
    rowstat = batch * heads * seq_len * 4
    if backward:
        return 4 * matmul, 8 * panel + 2 * rowstat
    return 2 * matmul, 4 * panel + rowstat


def decode_weight_bytes(cfg, dtype_bytes=4):
    """Bytes of weights one decode step must read: every block and the
    tied head (the whole embedding matrix); of the position table only
    one row per sequence, which is not counted."""
    n = gpt_param_count(cfg) - cfg["max_seq_len"] * cfg["hidden"]
    return n * dtype_bytes


def kv_bytes_per_token(cfg, dtype_bytes=4):
    """K and V of one cached position, all layers."""
    return cfg["layers"] * 2 * cfg["hidden"] * dtype_bytes


def decode_step_bytes(cfg, live_tokens, dtype_bytes=4):
    """Least HBM traffic of one decode step over rows whose caches hold
    `live_tokens` positions together: the weights once, plus the live
    K/V rows once."""
    return decode_weight_bytes(cfg, dtype_bytes) \
        + live_tokens * kv_bytes_per_token(cfg, dtype_bytes)


def roofline_share(flops, nbytes, seconds, peak):
    """Share (%) of the least time the chip could take (the larger of
    operations over peak FLOP/s and bytes over peak bytes/s) in the time
    it took, and which of the two bounds it."""
    t_flops = flops / peak["flops"]
    t_bytes = nbytes / peak["bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
