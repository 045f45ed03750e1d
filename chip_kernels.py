"""Per-kernel verdicts for ops/pallas/: compiles? matches the XLA composition?

    python chip_kernels.py          # on the chip: compile + run + compare
    python chip_kernels.py --aot    # in the sandbox: chipless v5e compile only
    python chip_kernels.py --grouped  # on the chip: the expert layer's grouped
                                      # product timed alone (see grouped_main)
    python chip_kernels.py --gqa    # on the chip: the grouped-query decode
                                    # reader and the banded flash forward
                                    # timed alone (see gqa_main)

The training path's kernels of ops/pallas/ (flash attention forward and
both backward schemes, fused linear + cross-entropy) at the widths the
main paths use (GPT-2 124M: H=12 D=64, T=1024/4096; GPT-3 1.3B geometry:
H=16 D=128, hidden 2048), against their XLA compositions computed at
`highest` matmul precision. One
JSON line per case ({kernel, compiles, matches, rel_err, msg} — for a
kernel that does not compile, `msg` is the compiler's message), a SUMMARY
line, and chiprun_out/kernels.json when that directory exists. Exits
non-zero if any case fails to compile or to match. On-chip run: about
40 s after start-up (PR 21).
"""
import functools
import json
import os
import sys
import time
import traceback

AOT = "--aot" in sys.argv
if AOT:
    os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu  # noqa: F401
from paddle_tpu.nn.functional.attention import _sdpa_xla
from paddle_tpu.ops.pallas import _common, flash_attention as fa, fused_ce

if AOT:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    _common.on_tpu = lambda: True           # answer as a TPU would
    _common.interpret = lambda: False
    SH = SingleDeviceSharding(
        topologies.get_topology_desc("v5e:2x2", "tpu").devices[0])
else:
    assert jax.devices()[0].platform == "tpu", jax.devices()

RESULTS = []
rng = np.random.default_rng(0)


def arr(shape, dtype, scale=1.0, ints=None):
    if ints is not None:
        return jnp.asarray(rng.integers(ints[0], ints[1], shape), dtype)
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


def case(name, kernel_fn, ref_fn, args, tol):
    """Compile kernel_fn(*args); on the chip also run it and ref_fn and
    compare every output leaf by max abs error relative to the
    reference's max abs value."""
    rec = {"kernel": name, "compiles": False, "matches": None, "msg": ""}
    t0 = time.time()
    try:
        jk = jax.jit(kernel_fn)
        if AOT:
            sds = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=SH)
                   for a in args]
            jk.lower(*sds).compile()
            rec["compiles"] = True
        else:
            exe = jk.lower(*args).compile()
            rec["compiles"] = True
            got = jax.tree_util.tree_leaves(exe(*args))
            with jax.default_matmul_precision("highest"):
                want = jax.tree_util.tree_leaves(jax.jit(ref_fn)(*args))
            errs = []
            for g, w in zip(got, want):
                g = np.asarray(g, np.float32)
                w = np.asarray(w, np.float32)
                errs.append(float(np.max(np.abs(g - w))
                                  / max(float(np.max(np.abs(w))), 1e-6)))
            rec["rel_err"] = [round(e, 5) for e in errs]
            rec["finite"] = all(
                bool(np.isfinite(np.asarray(g, np.float32)).all())
                for g in got)
            rec["matches"] = bool(rec["finite"] and max(errs) <= tol)
    except Exception as e:  # the compiler's message is the verdict
        msg = str(e)
        keep = [ln for ln in msg.splitlines() if ln.strip()][:6]
        rec["msg"] = (type(e).__name__ + ": " + " | ".join(keep))[:900]
        if os.environ.get("KERNELS_TRACE"):
            traceback.print_exc()
    rec["s"] = round(time.time() - t0, 1)
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)


# ---- the expert layer's grouped product, timed alone -----------------------
def _parent_held_sorted(x, local, held, w, wg, wu, wd):
    """The many-token path as it was before PR 34 (one 1,024-token chunk:
    every assignment sorted, gathered and handed to `ragged_dot`)."""
    N, K = local.shape
    count = wg.shape[0]
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=key.dtype),
                    axis=0, dtype=jnp.int32)
    xs = x[(order // K)]
    f32 = jnp.float32
    g = jax.lax.ragged_dot(xs, wg, sizes, preferred_element_type=f32)
    u = jax.lax.ragged_dot(xs, wu, sizes, preferred_element_type=f32)
    ys = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(x.dtype), wd, sizes,
                            preferred_element_type=f32)
    where = jnp.zeros(N * K, jnp.int32).at[order].set(
        jnp.arange(N * K, dtype=jnp.int32)).reshape(N, K)
    yk = jnp.where(held[..., None], ys[where] * w[..., None], 0.0)
    return yk.sum(axis=1)


def _parent_layer(x, local, held, w, wg, wu, wd, chunk=1024):
    N = x.shape[0]
    if N <= chunk:
        return _parent_held_sorted(x, local, held, w, wg, wu, wd)
    parts = jax.lax.map(
        lambda a: _parent_held_sorted(*a, wg, wu, wd),
        tuple(a.reshape((N // chunk, chunk) + a.shape[1:])
              for a in (x, local, held, w)))
    return parts.reshape(N, -1)


def _timed(fn, args, n=8, top=0):
    """(median seconds of a compiled call after two warm calls, its
    result); with `top`, also the device operations of three more calls
    that took most time (seconds a call), from a profiler trace. Weights
    go in as arguments: a closed-over array is compiled in as a
    constant."""
    exe = jax.jit(fn).lower(*args).compile()
    for _ in range(2):
        jax.block_until_ready(exe(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(exe(*args))
        ts.append(time.perf_counter() - t0)
    seconds, out = sorted(ts)[len(ts) // 2], exe(*args)
    if not top:
        return seconds, out
    import shutil
    import tempfile
    from chipbench import trace_reduce
    where = tempfile.mkdtemp(prefix="grouped-trace-")
    with jax.profiler.trace(where):
        for _ in range(3):
            jax.block_until_ready(exe(*args))
    ops = trace_reduce.top_ops(trace_reduce.load_xplane(where), top)
    shutil.rmtree(where, ignore_errors=True)
    return seconds, out, [[k, round(v / 3, 6)] for k, v in ops]


def grouped_main():
    """The two serving cells' expert layers at their real widths,
    bfloat16 operands: the parent's form, `ragged_dot` on the held prefix
    alone, the two Pallas kernels at four tile heights, and the whole
    layer (sort, gather, products, combine) a rung at a time. One JSON line a
    timing, with its floor: held weights once over 819 GB/s or held-row
    FLOPs over 197 TFLOP/s, whichever is larger. Non-zero exit where a
    kernel's values leave the `ragged_dot` form's by more than 2e-2 of
    their largest. `--only=axk1|kimi`
    keeps one shape, `--rungs` skips the chunk's table."""
    from paddle_tpu.nn.layer import moe
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    bf = jnp.bfloat16
    out = []

    def say(**rec):
        rec = {k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in rec.items()}
        out.append(rec)
        print(json.dumps(rec), flush=True)

    shapes = {"axk1": dict(H=7168, F=2048, count=12, routed=192, K=8,
                           skew=0.0, rungs=(2048, 4096, 8192)),
              "kimi": dict(H=2304, F=1024, count=128, routed=256, K=8,
                           skew=0.5, rungs=(512, 1024, 2048))}
    only = [a.split("=")[1] for a in sys.argv if a.startswith("--only=")]
    for name, c in shapes.items():
        if only and name not in only:
            continue
        H, F, count, K = c["H"], c["F"], c["count"], c["K"]
        ks = jax.random.split(jax.random.key(7), 8)
        ws = tuple(jax.random.normal(k, shape, bf) * 0.02 for k, shape in (
            (ks[0], (count, H, F)), (ks[1], (count, H, F)),
            (ks[2], (count, F, H))))
        # picks: K distinct experts a token, expert e drawn with weight
        # (e + 1) ** -skew (Gumbel top-k), in a random order of experts
        logp = -c["skew"] * np.log(np.arange(1, c["routed"] + 1))
        logp = np.random.default_rng(1).permutation(logp)

        def case(N, seed):
            k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
            x = jax.random.normal(k1, (N, H), bf)
            gum = jax.random.gumbel(k2, (N, c["routed"])) + jnp.asarray(logp)
            local = jax.lax.top_k(gum, K)[1].astype(jnp.int32)
            held = local < count
            w = jax.random.uniform(k3, (N, K), jnp.float32)
            return x, local, held, w

        def floor(rows):
            return max(3 * count * H * F * 2 / 819e9,
                       3 * 2 * rows * H * F / 197e12)

        def three_ragged(xs, sz, wg, wu, wd):
            a = gm.grouped_swiglu_reference(xs, wg, wu, sz)
            return jax.lax.ragged_dot(a, wd, sz,
                                      preferred_element_type=jnp.float32)

        def two_kernels(xs, sz, wg, wu, wd, kernel, tm=None):
            # every eighth row onto a token, as the layer's sorted rows
            token = jnp.arange(xs.shape[0], dtype=jnp.int32) // K
            a = gm.grouped_swiglu(xs, wg, wu, sz, tm=tm, kernel=kernel)
            return gm.grouped_matmul_add(
                a, wd, sz, token, jnp.ones(xs.shape[0], jnp.float32),
                jnp.zeros((xs.shape[0] // K, H), jnp.float32), tm=tm,
                kernel=kernel)

        # ---- one 1,024-token chunk ------------------------------------
        if "--rungs" not in sys.argv:
            x, local, held, w = case(1024, 11)
            sizes = np.bincount(np.asarray(local)[np.asarray(held)],
                                minlength=count)
            rows = int(sizes.sum())
            say(shape=name, what="chunk", tokens=1024, held_rows=rows,
                group_min=int(sizes.min()), group_max=int(sizes.max()),
                floor_s=floor(rows))
            t, _ = _timed(_parent_held_sorted, (x, local, held, w, *ws))
            say(shape=name, what="(a) parent layer, chunk", s=t)
            prefix = -(-rows // 512) * 512
            xs = jax.random.normal(ks[3], (8192, H), bf)
            sz = jnp.asarray(sizes, jnp.int32)
            t, _ = _timed(three_ragged, (xs, sz, *ws))
            say(shape=name, what="(a) three ragged_dot, 8192 rows in", s=t)
            t, _ = _timed(three_ragged, (xs[:prefix], sz, *ws))
            say(shape=name, what="(b) three ragged_dot, held prefix in",
                prefix=prefix, s=t)
            want = np.asarray(jax.jit(functools.partial(
                two_kernels, kernel="xla"))(xs, sz, *ws))
            for tm in (64, 128, 256, 512):
                t, got = _timed(functools.partial(
                    two_kernels, kernel="pallas", tm=tm), (xs, sz, *ws))
                say(shape=name, what="(c) pallas swiglu + down-and-add",
                    tm=tm, s=t, rel_err=float(
                        np.abs(np.asarray(got) - want).max()
                        / max(np.abs(want).max(), 1e-9)))

        # ---- (d) the whole layer, a rung at a time ---------------------
        for N in c["rungs"]:
            x, local, held, w = case(N, 100 + N)
            rows = int(np.asarray(held).sum())
            say(shape=name, what="rung", tokens=N, held_rows=rows,
                floor_s=floor(rows))
            args = (x, local, held, w, *ws)
            t, want = _timed(_parent_layer, args, 5)
            say(shape=name, what="(d) parent layer", tokens=N, s=t)
            want = np.asarray(want)
            rows_b = moe._block_rows(N * K, count, c["routed"])
            tile = gm.row_tile(rows_b, count)
            for tm in (tile, 384 - tile):
                keep = gm.row_tile
                gm.row_tile = lambda rows, groups: tm
                fn = lambda *a: moe._held_grouped(*a, c["routed"])
                t, got, ops = _timed(fn, args, 5, top=8)
                gm.row_tile = keep
                say(shape=name, what="(d) grouped layer", tokens=N,
                    block=rows_b, tm=tm, s=t, rel_err=float(
                        np.abs(np.asarray(got) - want).max()
                        / max(np.abs(want).max(), 1e-9)), ops=ops)
    if os.path.isdir("chiprun_out"):
        with open("chiprun_out/grouped-%s.json" % "-".join(only or ["all"]),
                  "w") as f:
            json.dump(out, f, indent=1)
    bad = [r for r in out if r.get("rel_err", 0.0) > 2e-2]
    print("SUMMARY", len(out), "lines; off by more than 2e-2:", bad)
    return 1 if bad else 0


if "--grouped" in sys.argv:
    sys.exit(grouped_main())


def gqa_main():
    """The `afmoe` kind's two kernels at the long-document cell's shapes
    (48 query heads over 8 K/V heads of 128, pages of 128 tokens,
    bfloat16), each timed alone with its floor, one JSON line a timing:

    * the paged grouped-query decode reader over 26 rows, Pallas against
      the `jnp.take` form: a window layer's ring (32 pages a slot, four
      rows in five full and wrapping, the fifth at 1.6k rows) and the
      full layer's table (128 pages, 9.3k to 15.6k rows a row), at 4
      and 8 pages a grid cell; the floor is each live K and V row once
      over 819 GB/s;
    * the flash forward over one sequence at the 16,384 and 2,048
      rungs: causal, and under a window of 4,096 (the band's blocks
      alone); the floor is the band's FLOPs over 197 TFLOP/s.

    Non-zero exit where a kernel leaves its plain form by more than
    2e-2 of the largest value."""
    from paddle_tpu.ops.pallas import gqa_attention as gq
    os.makedirs("chiprun_out", exist_ok=True)
    out = []

    def say(**rec):
        out.append(rec)
        print(json.dumps(rec), flush=True)

    bf = jnp.bfloat16
    B, Hq, Hkv, D, pt = 26, 48, 8, 128, 128
    scale = D ** -0.5
    q = arr((B, Hq, D), bf)
    pages_per_step = gq.PAGES_PER_STEP
    for name, W, lens in (
            ("ring", 32, [4096 if b % 5 else 1600 for b in range(B)]),
            ("full", 128, [9344 + (b * 6272) // (B - 1) for b in range(B)])):
        P = (B + 1) * W if name == "ring" else B * W + 1
        kp, vp = arr((P, pt, Hkv * D), bf), arr((P, pt, Hkv * D), bf)
        first = 0 if name == "ring" else 1
        tables = jnp.asarray(first + np.arange(B * W).reshape(B, W),
                             jnp.int32)
        lengths = jnp.asarray(lens, jnp.int32)
        floor = sum(lens) * Hkv * D * 2 * 2 / 819e9
        t_x, ref = _timed(functools.partial(
            gq.paged_gqa_decode_attention, scale=scale, kernel="xla"),
            (q, kp, vp, tables, lengths))
        say(kernel="gqa_decode", what=name, form="xla_take", seconds=t_x,
            floor_s=floor, share=floor / t_x, rows=sum(lens))
        for G in (4, 8):
            gq.PAGES_PER_STEP = G
            t_p, got = _timed(functools.partial(
                gq.paged_gqa_decode_attention, scale=scale,
                kernel="pallas"), (q, kp, vp, tables, lengths))
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - ref.astype(jnp.float32)))
                        / jnp.max(jnp.abs(ref.astype(jnp.float32))))
            say(kernel="gqa_decode", what=name, form=f"pallas_G{G}",
                seconds=t_p, floor_s=floor, share=floor / t_p, rel_err=err)
        gq.PAGES_PER_STEP = pages_per_step
    for T, window in ((16384, None), (16384, 4096), (2048, 4096),
                      (2048, 512)):
        qs, ks, vs = (arr((1, T, h, D), bf) for h in (Hq, Hkv, Hkv))
        fn = functools.partial(fa.flash_attention_forward, causal=True,
                               scale=scale, window=window)
        t, got = _timed(fn, (qs, ks, vs))
        bq, bk = fa._block_sizes(T, D)
        w = window if window is not None and window < T else None
        cells = sum(
            (i * bq + bq - 1) // bk
            - (max(i * bq - (w - 1), 0) // bk if w else 0) + 1
            for i in range(T // bq))
        flops = 4 * Hq * cells * bq * bk * D
        rec = dict(kernel="flash_fwd", T=T, window=window, seconds=t,
                   block_flops=flops, floor_s=flops / 197e12,
                   share=flops / 197e12 / t)
        if T == 2048:       # small enough for the masked plain form
            kr, vr = (jnp.repeat(a, Hq // Hkv, axis=2) for a in (ks, vs))
            s = jnp.einsum("bqhd,bkhd->bhqk", qs, kr,
                           preferred_element_type=jnp.float32) * scale
            t_ = jnp.arange(T)
            seen = t_[:, None] >= t_[None, :]
            if w:
                seen = seen & (t_[:, None] - t_[None, :] < w)
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            ref = jnp.einsum("bhqk,bkhd->bqhd", p.astype(bf), vr,
                             preferred_element_type=jnp.float32)
            rec["rel_err"] = float(
                jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
                / jnp.max(jnp.abs(ref)))
        say(**rec)
    with open("chiprun_out/gqa-kernels.json", "w") as f:
        json.dump(out, f, indent=1)
    bad = [r for r in out if r.get("rel_err", 0.0) > 2e-2]
    print("SUMMARY", len(out), "lines; off by more than 2e-2:", bad)
    return 1 if bad else 0


if "--gqa" in sys.argv:
    sys.exit(gqa_main())


# ---- flash attention: fwd + both backward schemes -------------------------
def flash_case(name, B, T, H, D, fused_bwd=True):
    q, k, v = (arr((B, T, H, D), jnp.bfloat16, 0.5) for _ in range(3))

    def run(fn):
        def f(q, k, v):
            out, vjp = jax.vjp(lambda a, b, c: fn(a, b, c), q, k, v)
            return (out,) + vjp(jnp.ones_like(out))
        return f

    os.environ["PT_FLASH_FUSED_BWD"] = "1" if fused_bwd else "0"
    case(name, run(lambda a, b, c: fa.flash_attention(a, b, c, causal=True)),
         run(lambda a, b, c: _sdpa_xla(a, b, c, None, 0.0, True, None)),
         (q, k, v), tol=3e-2)
    os.environ.pop("PT_FLASH_FUSED_BWD", None)


flash_case("flash fwd+fused bwd T=1024 D=64", 2, 1024, 12, 64)
flash_case("flash fwd+two-pass bwd T=1024 D=64", 2, 1024, 12, 64,
           fused_bwd=False)
flash_case("flash fwd+two-pass bwd T=4096 D=64", 1, 4096, 12, 64)
flash_case("flash fwd+fused bwd T=1024 D=128", 2, 1024, 16, 128)
flash_case("flash fwd+two-pass bwd T=2048 D=128", 1, 2048, 16, 128)


# ---- fused linear + cross-entropy -----------------------------------------
def ce_case(name, N, H, V):
    x = arr((N, H), jnp.bfloat16, 0.5)
    w = arr((V, H), jnp.bfloat16, 0.05)
    lbl = arr((N,), jnp.int32, ints=(0, V))

    def run(fn):
        def f(x, w, lbl):
            loss, vjp = jax.vjp(lambda a, b: fn(a, b, lbl), x, w)
            return (loss,) + vjp(jnp.ones_like(loss))
        return f

    lce = fused_ce.linear_cross_entropy
    case(name, run(lambda a, b, l: lce(a, b, l, fused=True)),
         run(lambda a, b, l: lce(a, b, l, fused=False)),
         (x, w, lbl), tol=3e-2)


ce_case("fused-CE fwd+bwd N=8192 H=768 V=50304", 8192, 768, 50304)
ce_case("fused-CE fwd+bwd N=4096 H=2048 V=50304", 4096, 2048, 50304)


if os.path.isdir("chiprun_out") and not AOT:
    with open("chiprun_out/kernels.json", "w") as f:
        json.dump(RESULTS, f, indent=1)
bad = [r["kernel"] for r in RESULTS
       if not r["compiles"] or r["matches"] is False]
print("SUMMARY", len(RESULTS), "cases; failing:", bad)
sys.exit(1 if bad else 0)
