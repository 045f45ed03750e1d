"""Operations and bytes the algorithms need, computed from shapes: the
pieces that know no architecture. What a kernel's mathematics needs,
not what an implementation happens to do: recomputed operations and
padded or re-laid-out bytes do not count. The counts of a whole model
(parameters, FLOPs per trained token, bytes of a decode step) belong to
its family, ``chipbench/families/<family>.py``.
"""


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


def roofline_share(flops, nbytes, seconds, peak):
    """Share (%) of the least time the chip could take (the larger of
    operations over peak FLOP/s and bytes over peak bytes/s) in the time
    it took, and which of the two bounds it."""
    t_flops = flops / peak["flops"]
    t_bytes = nbytes / peak["bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
