"""Grouped matrix products for a dropless expert layer: rows sorted by
group (expert), group g's rows multiplied by rhs[g].

    grouped_swiglu(x [M, K], wg, wu [G, K, N], sizes [G])   -> [M, N] x.dtype
                                       silu(x wg[g]) * (x wu[g]), in float32
    grouped_matmul_add(x [M, K], w [G, K, N], sizes [G], token [M],
                       scale [M], y [T, N] float32)         -> y' [T, N]
                       y'[token[r]] = y[token[r]] + scale[r] * (x[r] w[g])

Rows `sizes[:g].sum() .. sizes[:g + 1].sum()` belong to group g; rows
past `sizes.sum()` belong to none: `grouped_swiglu` LEAVES THEM UNWRITTEN
(whatever the buffer held), `grouped_matmul_add` never adds them.

The Pallas kernels (`name=KERNEL_NAME` and `KERNEL_NAME + "_add"`, so
that they reach the trace's `XLA Ops`) walk *visits*: one (row tile,
group) pair for every tile a group's rows touch, in group order. The
grid's second dimension is the NUMBER of visits, computed from `sizes`
on the device, so a call's work follows the rows that belong to a
group, not the rows handed in: tiles past the last group are never
read, multiplied or written. A group's weights stay in VMEM over its
consecutive visits (the whole contraction is one block), so each is
read once per column tile: once a call. A tile that straddles groups is
visited once a group and stored under a row mask. (The scheme is
megablox's `gmm`, which does not compile under this package's x64
setting; these keep K whole, fuse gate, up and SwiGLU, and fuse the
combine: the second kernel holds a column block of y in VMEM and adds
each row of its product, times the row's weight, onto the row's token,
where XLA's scatter took longer than both products.)

`grouped_*_reference` is the XLA form (`jax.lax.ragged_dot`, a
scatter-add): what runs off the chip, and the oracle of the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import _common
from ._common import I0 as _I0, pltpu

F32 = jnp.float32
KERNEL_NAME = "moe_grouped_matmul"
RHS_TILE_BYTES = 8 << 20    # one [K, tn] block of one expert's weights
VMEM_LIMIT_BYTES = 96 << 20


def grouped_swiglu_reference(x, wg, wu, sizes):
    g = jax.lax.ragged_dot(x, wg, sizes, preferred_element_type=F32)
    u = jax.lax.ragged_dot(x, wu, sizes, preferred_element_type=F32)
    return (jax.nn.silu(g) * u).astype(x.dtype)


def row_tile(rows, groups):
    """Rows a tile of the kernel, from the static shapes: a visit costs
    a whole tile's product whatever part of it the group fills, and a
    group is visited once more than its rows fill tiles, so the tile is
    about the rows a group has when every row handed in belongs to one
    (the worst case a call must compute), between the MXU's height and
    twice it."""
    return 256 if rows >= 256 * max(groups, 1) else 128


def _col_tile(K, N, itemsize, acc_rows=0):
    """The widest multiple of the lane width that divides N and keeps a
    [K, tn] weight block under `RHS_TILE_BYTES` (and, with `acc_rows`,
    a float32 accumulator of that many rows under half the VMEM
    allowance); N itself where no such divisor exists (small shapes)."""
    best = None
    for tn in range(_common.LANE, N + 1, _common.LANE):
        if N % tn == 0 and K * tn * itemsize <= RHS_TILE_BYTES \
                and acc_rows * tn * 4 <= VMEM_LIMIT_BYTES // 2:
            best = tn
    return best or N


def _visits(sizes, tiles_m, tm):
    """(offsets [G + 1], group of visit v, row tile of visit v, number
    of visits): group g is visited once for every row tile its rows
    touch, groups in order, empty groups never."""
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 0)
    most = tiles_m + G - 1
    group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                       total_repeat_length=most)
    first_visit = jnp.cumsum(tiles) - tiles
    tile = (starts // tm)[group] + jnp.arange(most, dtype=jnp.int32) \
        - first_visit[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), group,
            jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32),
            jnp.sum(tiles, dtype=jnp.int32))


def _dot(a, b):
    """a @ b accumulated in float32. bfloat16 operands take the MXU's
    one pass whatever `jax_default_matmul_precision` says (their
    products are exact in it, and Mosaic refuses more); float32
    operands follow the setting."""
    exact = a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
    return jnp.dot(a, b, preferred_element_type=F32,
                   precision=jax.lax.Precision.DEFAULT if exact else None)


def _swiglu_kernel(off_ref, group_ref, tile_ref, x_ref, wg_ref, wu_ref,
                   o_ref, *, tm):
    v = pl.program_id(1)
    g = group_ref[v]
    rows = tile_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)
    mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
    x = x_ref[...]
    val = jax.nn.silu(_dot(x, wg_ref[...])) * _dot(x, wu_ref[...])
    o_ref[...] = jnp.where(mine, val.astype(o_ref.dtype), o_ref[...])


def _pallas_swiglu(x, wg, wu, sizes, tm):
    M, K = x.shape
    G, _, N = wg.shape
    if M % tm:
        raise ValueError(f"grouped product: {M} rows in tiles of {tm}")
    tn = _col_tile(K, N, wg.dtype.itemsize)
    offsets, group, tile, visits = _visits(
        sizes.astype(jnp.int32), M // tm, tm)
    rhs = pl.BlockSpec((None, K, tn),
                       lambda n, v, off, grp, til: (grp[v], _I0, n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(N // tn, visits),
        in_specs=[pl.BlockSpec((tm, K),
                               lambda n, v, off, grp, til: (til[v], _I0))]
        + [rhs, rhs],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda n, v, off, grp, til: (til[v], n)))
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, tm=tm),
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=_common.interpret(),
        **_common.compiler_params("parallel", "arbitrary",
                                  vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(offsets, group, tile, x, wg, wu)


def grouped_matmul_add_reference(x, w, sizes, token, scale, y):
    ys = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=F32)
    valid = jnp.arange(x.shape[0], dtype=jnp.int32) < jnp.sum(
        sizes, dtype=jnp.int32)
    return y.at[token].add(
        jnp.where(valid[:, None], ys * scale[:, None], 0.0))


def _add_kernel(off_ref, group_ref, tile_ref, token_ref, x_ref, w_ref,
                scale_ref, y_hbm, o_hbm, acc, val, sem, *, tm, tn):
    n, v = pl.program_id(0), pl.program_id(1)
    cols = pl.ds(pl.multiple_of(n * tn, tn), tn)

    @pl.when(v == 0)
    def _load():
        copy = pltpu.make_async_copy(y_hbm.at[:, cols], acc, sem)
        copy.start()
        copy.wait()

    g = group_ref[v]
    base = tile_ref[v] * tm
    val[...] = _dot(x_ref[...], w_ref[...]) * scale_ref[:, :1]

    def row(r, carry):
        t = token_ref[base + r]
        acc[pl.ds(t, 1), :] = acc[pl.ds(t, 1), :] + val[pl.ds(r, 1), :]
        return carry

    # the rows of this tile that belong to this group, and no other
    jax.lax.fori_loop(jnp.maximum(off_ref[g] - base, 0),
                      jnp.minimum(off_ref[g + 1] - base, tm), row, 0)

    @pl.when(v == pl.num_programs(1) - 1)
    def _store():
        copy = pltpu.make_async_copy(acc, o_hbm.at[:, cols], sem)
        copy.start()
        copy.wait()


def _pallas_add(x, w, sizes, token, scale, y, tm):
    M, K = x.shape
    G, _, N = w.shape
    T = y.shape[0]
    if M % tm or T % 8:
        raise ValueError(f"grouped product: {M} rows in tiles of {tm} "
                         f"onto {T} tokens (whole tiles of 8)")
    tn = _col_tile(K, N, w.dtype.itemsize, acc_rows=T)
    offsets, group, tile, visits = _visits(
        sizes.astype(jnp.int32), M // tm, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(N // tn, visits),
        in_specs=[
            pl.BlockSpec((tm, K), lambda n, v, off, grp, til, tok:
                         (til[v], _I0)),
            pl.BlockSpec((None, K, tn), lambda n, v, off, grp, til, tok:
                         (grp[v], _I0, n)),
            pl.BlockSpec((tm, _common.LANE),
                         lambda n, v, off, grp, til, tok: (til[v], _I0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((T, tn), F32), pltpu.VMEM((tm, tn), F32),
                        pltpu.SemaphoreType.DMA(())])
    return pl.pallas_call(
        functools.partial(_add_kernel, tm=tm, tn=tn),
        name=KERNEL_NAME + "_add",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(y.shape, F32),
        # y is updated where it lies (operand 7, counting the four
        # prefetched arrays, is result 0): with no visit it is unchanged
        input_output_aliases={7: 0},
        interpret=_common.interpret(),
        **_common.compiler_params("arbitrary", "arbitrary",
                                  vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(offsets, group, tile, token.astype(jnp.int32), x, w,
      jnp.broadcast_to(scale.astype(F32)[:, None], (M, _common.LANE)), y)


def _choose(kernel):
    choice = kernel or ("pallas" if _common.on_tpu() else "xla")
    if choice not in ("pallas", "xla"):
        raise ValueError(f"kernel={choice!r}: expected 'pallas' or 'xla'")
    return choice


def grouped_matmul_add(x, w, sizes, token, scale, y, tm=None, kernel=None):
    """y [T, N] float32 with scale[r] * (x[r] @ w[group of r]) added onto
    row token[r], for every row r that belongs to a group: the Pallas
    kernel on a TPU and the reference off it (the interpreter is for
    tests), unless `kernel` ("pallas" | "xla") says."""
    if _choose(kernel) == "xla":
        return grouped_matmul_add_reference(x, w, sizes, token, scale, y)
    return _pallas_add(x, w, sizes, token, scale, y,
                       tm or row_tile(x.shape[0], w.shape[0]))


def grouped_swiglu(x, wg, wu, sizes, tm=None, kernel=None):
    if _choose(kernel) == "xla":
        return grouped_swiglu_reference(x, wg, wu, sizes)
    return _pallas_swiglu(x, wg, wu, sizes,
                          tm or row_tile(x.shape[0], wg.shape[0]))
