"""Attention functionals.

The reference only has fused inference attention kernels
(operators/fused/multihead_matmul_op.cu); training attention is composed
from matmul/softmax ops. Here scaled_dot_product_attention is first-class:
it dispatches to the Pallas flash-attention kernel on TPU when shapes
qualify (paddle_tpu/ops/pallas/flash_attention.py), else an XLA composition.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.flags import get_flags
from ...core.tensor import Tensor, apply

__all__ = ["scaled_dot_product_attention", "seq_parallel_scope",
           "flash_mesh_scope"]

# sequence-parallel routing context: when set (by the fleet strategy
# compiler or user code), qualifying sdpa calls run ring/Ulysses attention
# over the 'sp' mesh axis instead of single-device attention
_seq_parallel_ctx = [None]   # (mesh, axis, impl, batch_axis, head_axis)


class _RoutingScope:
    """Context manager that publishes ``self._val`` in the one-element
    list ``self._slot`` for the duration of the block (scopes nest)."""

    _slot: list

    def __enter__(self):
        self._prev = self._slot[0]
        self._slot[0] = self._val
        return self

    def __exit__(self, *exc):
        self._slot[0] = self._prev
        return False


class seq_parallel_scope(_RoutingScope):
    """with seq_parallel_scope(mesh, "sp", impl="ring", batch_axis="dp"):
    attention inside routes through distributed.sequence_parallel."""

    _slot = _seq_parallel_ctx

    def __init__(self, mesh, axis="sp", impl="ring", batch_axis=None,
                 head_axis=None):
        """head_axis: mesh axis the HEAD dim is already sharded over
        (tensor parallel) — attention is per-head, so it composes with
        the sequence ring/all-to-all."""
        if impl not in ("ring", "ulysses"):
            raise ValueError(f"sequence_parallel impl must be 'ring' or "
                             f"'ulysses', got {impl!r}")
        self._val = (mesh, axis, impl, batch_axis, head_axis)


# mesh routing context for the flash kernel: GSPMD cannot partition a
# Mosaic custom call ("Mosaic kernels cannot be automatically
# partitioned"), so a step traced under a dp/tp mesh runs the kernel
# shard_map-inner on its local [B/dp, T, H/tp, D] shard
_flash_mesh_ctx = [None]     # (mesh, batch_axis, head_axis)


class flash_mesh_scope(_RoutingScope):
    """with flash_mesh_scope(mesh, batch_axis="dp", head_axis="tp"):
    qualifying sdpa calls inside run the flash kernel per shard (set by
    the fleet strategy compiler around its traced step)."""

    _slot = _flash_mesh_ctx

    def __init__(self, mesh, batch_axis=None, head_axis=None):
        self._val = (mesh, batch_axis, head_axis)


def _sdpa_xla(q, k, v, mask, dropout_p, causal, scale, key=None):
    # q,k,v: [B, S, H, D] (paddle convention)
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhsd,bhtd->bhst", qt, kt) * s
    logits = logits.astype(jnp.float32)
    if causal:
        S, T = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((S, T), bool))
        logits = jnp.where(causal_mask, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, scale=None,
                                 training=True, name=None, rng_key=None):
    """query/key/value: [batch, seq, heads, head_dim]."""
    if not training:
        dropout_p = 0.0
    if dropout_p > 0.0 and rng_key is None:
        from ...core import random as random_mod
        rng_key = random_mod.next_key()

    sp = _seq_parallel_ctx[0]
    if sp is not None:
        mesh, axis, impl, batch_axis, head_axis = sp
        n_sp = int(mesh.shape[axis])
        T, H = query.shape[1], query.shape[2]
        if attn_mask is not None or dropout_p > 0.0:
            import warnings
            warnings.warn(
                "sequence_parallel is active but this attention call uses "
                "attn_mask/dropout, which the SP paths do not support — "
                "falling back to single-device attention (GSPMD will "
                "gather the sequence dim; no SP memory savings here)")
        else:
            if T % n_sp:
                raise ValueError(
                    f"sequence_parallel: seq len {T} not divisible by "
                    f"sp={n_sp} (hybrid_configs.sep_degree)")
            n_head_shards = int(mesh.shape[head_axis]) if head_axis else 1
            if head_axis and H % n_head_shards:
                # uneven head sharding: keep the pre-head_axis behavior
                # (GSPMD handles tp collectives outside the SP region)
                import warnings
                warnings.warn(
                    f"sequence_parallel: {H} heads not divisible by "
                    f"{head_axis!r} size {n_head_shards}; running the SP "
                    f"region with replicated heads")
                head_axis, n_head_shards = None, 1
            local_h = H // n_head_shards
            if impl == "ulysses" and local_h % n_sp:
                raise ValueError(
                    f"sequence_parallel impl='ulysses': sp={n_sp} must "
                    f"divide the local head count {local_h} "
                    f"(= {H} heads / {n_head_shards} head shards); use "
                    f"impl='ring' or adjust sep_degree")
            from ...distributed.sequence_parallel import (
                make_ring_attention, make_ulysses_attention)
            maker = make_ring_attention if impl == "ring" \
                else make_ulysses_attention
            f = maker(mesh, axis=axis, causal=is_causal, scale=scale,
                      batch_axis=batch_axis, head_axis=head_axis)
            return apply(f, query, key, value, op_name="sp_attention")

    # the kernel tiles the sequence in blocks of >= 128 rows: shapes it
    # cannot tile take the XLA composition by rule; a call that IS routed
    # here and then fails raises — it never quietly computes with XLA
    seq_len = query.shape[1]
    use_pallas = (get_flags("use_pallas_attention") and attn_mask is None
                  and dropout_p == 0.0
                  and seq_len >= get_flags("pallas_attention_min_seq")
                  and seq_len % 128 == 0)
    if use_pallas:
        from ...ops.pallas.flash_attention import flash_attention
        fn = functools.partial(flash_attention, causal=is_causal,
                               scale=scale)
        ctx = _flash_mesh_ctx[0]
        if ctx is not None:
            mesh, batch_axis, head_axis = ctx
            if head_axis and query.shape[2] % int(mesh.shape[head_axis]):
                head_axis = None       # uneven heads: replicate them
            spec = P(batch_axis, None, head_axis, None)
            fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                               out_specs=spec, check_vma=False)
        return apply(fn, query, key, value, op_name="flash_attention")

    args = [query, key, value]
    if attn_mask is not None:
        return apply(lambda q, k, v, m: _sdpa_xla(q, k, v, m, dropout_p,
                                                  is_causal, scale, rng_key),
                     *args, attn_mask, op_name="sdpa")
    return apply(lambda q, k, v: _sdpa_xla(q, k, v, None, dropout_p,
                                           is_causal, scale, rng_key),
                 *args, op_name="sdpa")
