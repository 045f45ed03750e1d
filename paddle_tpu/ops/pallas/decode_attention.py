"""Single-token (q_len == 1) decode attention over a PAGED KV cache.

During autoregressive decode every step attends one fresh query row per
sequence against that sequence's cached K/V — a GEMV per head, not the
GEMM the flash kernel is tiled for. The cache is one layer's page pool
`[P, page_tokens, H * D]` (a token's row is every head side by side, so
it fills whole 128-lane tiles at any head size), float32 or the int8
pair `(data, scale)` of `quant/kv.py`, plus per-sequence int32 block
tables (inference/decode.py's paged engine).

There is one reader of a page, `paged_decode_attention`: it gathers the
table's pages with `jnp.take` (`gathered_panel`, which dequantizes an
int8 pool's gathered panel in the same expression) and keeps the panel
in that row layout through the scores and the weighted sum
(`head_scores`, `head_mix`). `models.gpt.gpt_paged_fns` builds the step
and each step of a rollout on it, and verify on its parts. A Pallas
kernel that walked the block table through scalar-prefetch index maps
(one grid cell a (batch, page), online softmax in scratch) was timed
against it on a v5e at the chat cell's shapes and lost on every one —
2.865 ms against 0.259 at 84 rows x 32 pages, 1.451 / 0.227 at 84 x 16,
0.653 / 0.212 at 19 x 32, 0.334 / 0.208 at 19 x 16 (PERF.md section 6,
PR 29) — so it and the option that selected it are gone (PR 31).

    q        [B, H, D]          fresh query row per sequence
    k_pool   [P, pt, H * D]     one layer's page pool (pt = page tokens);
    v_pool   [P, pt, H * D]     a token's row is its H heads side by side
    tables   [B, W] int32       block table: tables[b, w] = page holding
                                rows [w*pt, (w+1)*pt) of sequence b;
                                unused entries point at the null page
    lengths  [B] int32          valid prefix per sequence (masks the rest)
    out      [B, H, D]
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ._common import NEG_INF


def _head_blocks(heads, width):
    """[H, C] bool: lane c of a row belongs to head c // (C // H)."""
    lane = jnp.arange(width, dtype=jnp.int32)[None, :] // (width // heads)
    return lane == jnp.arange(heads, dtype=jnp.int32)[:, None]


def head_scores(q, keys, heads):
    """Per-head scaled scores over rows that hold every head side by
    side: q [B, Q, C], keys [B, K, C] -> float32 [B, Q, H, K] with C =
    H * D. Each query row is spread over H rows that are zero outside
    their head's D lanes, so one batched product against the panel AS
    IT LIES gives every head's score: the panel is never reshaped to
    [.., H, D] (D < 128 lanes would make the compiler copy it into
    another layout). The zeros add nothing, so the operands and the sum
    are those of `einsum("bqhd,bkhd->bqhk")`."""
    B, Q, C = q.shape
    qb = jnp.where(_head_blocks(heads, C), q[:, :, None, :], 0)
    s = jnp.einsum("bmc,bkc->bmk", qb.reshape(B, Q * heads, C), keys)
    s = s * (1.0 / math.sqrt(C // heads))
    return s.astype(jnp.float32).reshape(B, Q, heads, keys.shape[1])


def head_mix(p, vals):
    """The weighted sum that goes with `head_scores`: p [B, Q, H, K],
    vals [B, K, C] -> [B, Q, C], head h's D lanes mixed by p[:, :, h].
    One batched product gives every head's weights over the whole row;
    each head keeps its own lanes of it."""
    B, Q, H, K = p.shape
    C = vals.shape[-1]
    r = jnp.einsum("bmk,bkc->bmc", p.reshape(B, Q * H, K), vals)
    return jnp.sum(jnp.where(_head_blocks(H, C), r.reshape(B, Q, H, C), 0),
                   axis=2)


def dequantize_rows(data, scale):
    """An int8 pool's rows as float32: data [..., H * D] times its
    per-head scale [..., H] spread over the head's D lanes."""
    D = data.shape[-1] // scale.shape[-1]
    return data.astype(jnp.float32) * jnp.repeat(scale, D, axis=-1)


def take_pages(pool, tables):
    """The pages a block table names, [B, W, pt, ..]. A table holds
    page ids of this pool and nothing else, so the gather clips
    instead of filling: `jnp.take`'s default marks out-of-range reads
    with NaN, which costs one more pass over the gathered panel."""
    return jnp.take(pool, tables, axis=0, mode="clip")


def _panel_attention(q, k, v, lengths):
    """q [B, H, D] over gathered panels k, v [B, K, H * D]: masked
    softmax(q.k/sqrt(D)).v over the live rows, rows kept whole."""
    B, H, D = q.shape
    s = head_scores(q.reshape(B, 1, H * D), k, H)             # [B,1,H,K]
    live = jnp.arange(k.shape[1], dtype=jnp.int32)[None, None, None, :] \
        < lengths.astype(jnp.int32)[:, None, None, None]
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return head_mix(p, v).reshape(B, H, D).astype(q.dtype)


def gathered_panel(pool, tables):
    """The pages a block table names out of one layer's pool, as a
    float32 panel [B, W * pt, H * D] with every row whole; `pool` is
    the float32 array or the int8 pair `(data, scale)`, whose gathered
    panel is dequantized in the same expression."""
    if isinstance(pool, tuple):
        panel = dequantize_rows(take_pages(pool[0], tables),
                                take_pages(pool[1], tables))
    else:
        panel = take_pages(pool, tables)
    B, W, pt, C = panel.shape
    return panel.reshape(B, W * pt, C)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths):
    """Gather the table's pages (`jnp.take`) into a contiguous
    [B, W*pt, H*D] panel, masked softmax per head. A pool is float32 or
    the int8 pair `(data, scale)`."""
    with jax.named_scope("page_gather"):
        k = gathered_panel(k_pool, tables)
        v = gathered_panel(v_pool, tables)
    return _panel_attention(q, k, v, lengths)
