"""Per-kernel verdicts for ops/pallas/: compiles? matches the XLA composition?

    python chip_kernels.py          # on the chip: compile + run + compare
    python chip_kernels.py --aot    # in the sandbox: chipless v5e compile only

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
