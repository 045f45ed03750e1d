"""K/V page pools for the paged decode engine, float32 and int8.

A pool (K or V) is a tuple of `layers` layer pools, one array a layer,
page axis 0. A float32 layer pool is a bare ``[pages, page_tokens,
heads * head_dim]`` array: a token's row is its heads side by side, so
the minor dimension fills whole 128-lane tiles at any head size and
the compiled step writes into, and gathers from, the array it was
given (with ``[.., heads, head_dim]`` minor dimensions the compiler
copied every pool into a layout it could gather from, and back, every
step). The int8 layer pool is the pair ``(data int8 [pages,
page_tokens, heads * head_dim], scale f32 [pages, page_tokens,
heads])`` — one symmetric scale per (page, token row, head). Per-row
scales mean a freshly written token never forces requantization of its
page, and a COW page copy is a plain copy of every leaf. Every pool
consumer (`memory.page_allocator` pool ops, the decode fns in
`models.gpt`, the engine's AOT signatures) branches on a layer pool's
structure at trace time.

Byte math per element: 1 (int8 payload) + 4 / head_dim (amortized
scale) versus 4 fp32 — a 3.76x reduction at head_dim 64.
"""
from __future__ import annotations

from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp

KV_DTYPES = ("float32", "int8")


def validate_kv_dtype(kv_dtype) -> str:
    """Normalize/validate a pool-dtype knob value ('' -> float32)."""
    s = str(kv_dtype or "float32").strip().lower()
    if s in ("float32", "fp32", "f32"):
        return "float32"
    if s == "int8":
        return "int8"
    raise ValueError(
        f"kv_dtype {kv_dtype!r}: expected one of {KV_DTYPES}"
    )


def quantize_kv(rows: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(row, head) symmetric int8: ``[..., D] f32 -> (int8 [..., D],
    f32 scale [...])`` with ``scale = max(|row|) / 127`` (floored so an
    all-zero row quantizes to zeros, not NaNs)."""
    scale = jnp.maximum(jnp.max(jnp.abs(rows), axis=-1), 1e-8) / 127.0
    scale = scale.astype(jnp.float32)
    q = jnp.clip(jnp.round(rows / scale[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def dequantize_kv(data: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_kv`: ``q * scale`` broadcast over D
    (rows as ``[..., H, D]``; a pool's ``[..., H * D]`` rows go through
    `ops.pallas.decode_attention.dequantize_rows`)."""
    return data.astype(jnp.float32) * scale[..., None]


LayerPool = Union[jax.Array, Tuple[jax.Array, jax.Array]]
PoolLike = Tuple[LayerPool, ...]


def kv_pool_sds(shape: Sequence[int], kv_dtype: str = "float32") -> PoolLike:
    """ShapeDtypeStruct pytree (warmup/AOT) of the pool that holds
    ``shape`` = (layers, pages, page_tokens, heads, head_dim): `layers`
    layer pools, each ``[pages, page_tokens, heads * head_dim]``."""
    layers, pages, pt, heads, dim = (int(s) for s in shape)
    if validate_kv_dtype(kv_dtype) == "int8":
        layer = (jax.ShapeDtypeStruct((pages, pt, heads * dim), jnp.int8),
                 jax.ShapeDtypeStruct((pages, pt, heads), jnp.float32))
    else:
        layer = jax.ShapeDtypeStruct((pages, pt, heads * dim), jnp.float32)
    return tuple(layer for _ in range(layers))


def kv_pool_zeros(shape: Sequence[int], kv_dtype: str = "float32") -> PoolLike:
    """Zero-initialized pool matching :func:`kv_pool_sds`, every leaf a
    buffer of its own (the step donates them one by one)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        kv_pool_sds(shape, kv_dtype))
