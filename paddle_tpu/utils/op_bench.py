"""Per-op microbenchmark harness (op_tester analog —
/root/reference/paddle/fluid/operators/benchmark/op_tester.cc:1).

Timing: `bench_fn` chains n dependent calls inside each timed window,
ends the window with one device->host fetch, and reports the MARGINAL
time ((t_long - t_short) / (n_long - n_short)), which cancels the
fetch's constant cost; outputs are reduced to scalars on-device.

CLI:  python -m paddle_tpu.utils.op_bench [op ...]   (default: hot set)
"""
from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["bench_fn", "bench_suite", "HOT_OPS"]


def bench_fn(fn: Callable, *args, n_short=4, n_long=16, repeats=2,
             flops=0) -> Dict[str, float]:
    """fn(*args) -> scalar-reducible pytree. Returns marginal ms/call."""
    def scal(t):
        return sum(jnp.sum(l).astype(jnp.float32)
                   for l in jax.tree_util.tree_leaves(t)) * jnp.float32(1e-12)

    jfn = jax.jit(lambda *a: scal(fn(*a)))
    out = jfn(*args)
    _ = float(out)          # compile + first fetch

    def run(n):
        t0 = time.perf_counter()
        o = None
        for _ in range(n):
            o = jfn(*args)
        _ = float(o)
        return time.perf_counter() - t0

    best = float("inf")
    for _ in range(repeats):
        d1, d2 = run(n_short), run(n_long)
        delta = (d2 - d1) / (n_long - n_short)
        if delta > 0:
            best = min(best, delta)
    if best == float("inf"):
        best = run(n_long) / n_long
    res = {"ms": best * 1e3}
    if flops:
        res["tflops"] = flops / best / 1e12
    return res


def _mk(shape, dtype=jnp.bfloat16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape) * 0.1, dtype)


def _adam_update(p, g, m, v):
    m2 = 0.9 * m + 0.1 * g
    v2 = 0.999 * v + 0.001 * g * g
    return p - 1e-3 * m2 / (jnp.sqrt(v2) + 1e-8), m2, v2


def HOT_OPS():
    """BASELINE.json north-star op set: matmul, conv, layer_norm, softmax,
    fused attention, adam."""
    from ..ops.pallas.flash_attention import flash_attention
    B, T, H, D = 8, 1024, 12, 64
    x = _mk((8192, 768))
    w = _mk((768, 3072))
    img = _mk((32, 224, 224, 3), jnp.bfloat16)
    kern = _mk((7, 7, 3, 64))
    h = _mk((8192, 768), jnp.float32)
    q = _mk((B, T, H, D))
    p32 = _mk((8192, 768), jnp.float32)
    return {
        "matmul_8192x768x3072": (lambda: (
            lambda a, b: a @ b, (x, w),
            {"flops": 2 * 8192 * 768 * 3072})),
        "conv2d_7x7_s2": (lambda: (
            lambda i, k: jax.lax.conv_general_dilated(
                i, k, (2, 2), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")), (img, kern),
            {"flops": 2 * 32 * 112 * 112 * 64 * 7 * 7 * 3})),
        "layer_norm_8192x768": (lambda: (
            lambda a: (a - a.mean(-1, keepdims=True))
            / jnp.sqrt(a.var(-1, keepdims=True) + 1e-5), (h,), {})),
        "softmax_8192x768": (lambda: (
            lambda a: jax.nn.softmax(a, axis=-1), (h,), {})),
        "flash_attention_8x1024x12x64": (lambda: (
            lambda a: flash_attention(a, a, a, causal=True), (q,),
            {"flops": 4 * B * H * T * T * D})),
        "adam_update_8192x768": (lambda: (
            _adam_update, (p32, p32, p32, p32), {})),
    }


def eager_overhead(n_short=60, n_long=240, repeats=3):
    """µs/op of the EAGER dispatch path — Tensor.apply + tape recording
    (VERDICT r4 Next #10; the reference tracked the same quantity with
    operators/benchmark/op_tester.cc). Chains n dependent ops on [8, 8]
    tensors (device compute is negligible at that size) with ONE host
    sync per window; the marginal time is the per-op python-side cost.
    Returns {op: µs/op}."""
    from ..core.tensor import to_tensor
    from ..nn import functional as F

    eye = to_tensor(np.eye(8, dtype=np.float32))
    one = to_tensor(np.ones((8, 8), np.float32))

    def chain_add(x, n):
        for _ in range(n):
            x = x + one
        return x

    def chain_matmul(x, n):
        for _ in range(n):
            x = x.matmul(eye)          # identity keeps values bounded
        return x

    def chain_layer_norm(x, n):
        for _ in range(n):
            x = F.layer_norm(x, [8])
        return x

    out = {}
    for name, chain in (("add", chain_add), ("matmul", chain_matmul),
                        ("layer_norm", chain_layer_norm)):
        def run(n):
            x = to_tensor(np.ones((8, 8), np.float32))
            t0 = time.perf_counter()
            y = chain(x, n)
            float(np.asarray(y.numpy()).sum())
            return time.perf_counter() - t0

        run(4)                          # warm the per-op jit caches
        best = float("inf")
        for _ in range(repeats):
            d1, d2 = run(n_short), run(n_long)
            delta = (d2 - d1) / (n_long - n_short)
            if delta > 0:
                best = min(best, delta)
        if best == float("inf"):
            best = run(n_long) / n_long
        out[name] = best * 1e6
    return out


def bench_suite(names=None):
    ops = HOT_OPS()
    names = names or list(ops)
    rows = []
    for name in names:
        fn, args, extra = ops[name]()
        r = bench_fn(fn, *args, **extra)
        rows.append((name, r))
        tfl = f"  {r['tflops']:7.1f} TF/s" if "tflops" in r else ""
        print(f"{name:36s} {r['ms']:9.3f} ms{tfl}")
    return rows


if __name__ == "__main__":
    import sys
    if "--eager" in sys.argv:
        for op, us in eager_overhead().items():
            print(f"eager {op:12s} {us:8.1f} us/op")
    else:
        bench_suite(sys.argv[1:] or None)
