"""Flagship benchmark: GPT-2 124M trained through the PRODUCT path —
hapi Model.prepare(strategy) + Model.fit — on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline = measured MFU / 0.45 (BASELINE.json north star: >=45% MFU for
Model.fit on GPT-2-class models; the reference repo publishes no absolute
numbers — BASELINE.md).

Methodology: fit() is timed end-to-end (DataLoader -> device prefetch ->
compiled strategy step -> callbacks). The loss stays on device between
log points (hapi _AsyncScalar), so the only host sync is the end-of-epoch
fetch — a constant the marginal-step estimator cancels: step_time =
(t(n_long) - t(n_short)) / (n_long - n_short), best of 2 rounds,
jitter-negative rounds discarded.

The accelerator is not optional: a backend that fails to initialise, a
mesh that cannot be built or a compile that raises is a traceback and a
non-zero exit. An explicit ``JAX_PLATFORMS=cpu`` runs toy shapes so the
script stays debuggable; that run reports no MFU.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# bf16 peak FLOP/s per chip, keyed by the ``device_kind`` string the
# runtime reports (Google Cloud TPU documentation, per-generation system
# architecture pages). "TPU v5 lite" is what jax 0.9 / libtpu 0.0.34
# report for a v5e (seen on the chip, PR 21); the other kinds follow
# jax's own naming and have not been seen on this installation.
PEAK_FLOPS = {"TPU v4": 275e12, "TPU v5 lite": 197e12, "TPU v5": 459e12,
              "TPU v6 lite": 918e12}


def peak_flops(devs=None):
    """bf16 peak FLOP/s of one chip of ``devs`` (default: jax.devices());
    a device kind that is not in the table is an error, not a default."""
    if devs is None:
        import jax
        devs = jax.devices()
    kind = devs[0].device_kind
    if kind not in PEAK_FLOPS:
        raise ValueError(
            f"peak_flops: no peak recorded for device_kind {kind!r}; add "
            f"it to bench.PEAK_FLOPS with its source (known: "
            f"{sorted(PEAK_FLOPS)})")
    return PEAK_FLOPS[kind]


def main():
    import jax

    devs = jax.devices()

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.hapi import Model, callbacks as hapi_cbks
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.static import InputSpec

    on_cpu = devs[0].platform == "cpu"
    if on_cpu:  # toy shapes so the bench is debuggable off-TPU
        cfg = GPTConfig(vocab_size=512, max_seq_len=128, hidden=128,
                        layers=2, heads=4)
        B, T, n_short, n_long = 2, 128, 1, 3
        # multi-device CPU run (xla_force_host_platform_device_count): the
        # global batch must stay divisible by the dp degree
        B = max(B, len(devs))
    else:
        cfg = GPTConfig()                      # GPT-2 124M
        B, T, n_short, n_long = 16, 1024, 4, 16

    paddle.seed(0)
    gpt = GPT(cfg)

    class _LMLoss(nn.Layer):
        """forward(ids, labels) -> scalar LM loss, keeping the fused
        linear+CE head (no [tokens, vocab] logits residuals)."""

        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, ids, labels):
            return self.m.loss(ids, labels)

    net = _LMLoss(gpt)
    net.train()
    model = Model(net, inputs=[InputSpec([None, T], "int32"),
                               InputSpec([None, T], "int32")])
    s = DistributedStrategy()
    # AMP O2: matmul-class ops run bf16 on the MXU (full rate),
    # softmax/LN/CE stay f32; master params and Adam state are f32.
    s.amp = True
    s.amp_configs.use_pure_bf16 = True
    if len(devs) > 1:
        # fleet.init only warns and leaves the mesh unset when the mesh
        # cannot be built; raise here instead
        s.resolve_degrees(len(devs))
    adam = opt.Adam(learning_rate=1e-4, parameters=model.parameters())
    model.prepare(adam, strategy=s)

    rng = np.random.default_rng(0)

    def dataset(n_batches):
        ids = rng.integers(0, cfg.vocab_size, (n_batches * B, T),
                           dtype=np.int32)
        labels = np.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        return TensorDataset([ids, labels])

    class _Last(hapi_cbks.Callback):
        def on_train_batch_end(self, step, logs=None):
            self.logs = logs

    last = _Last()

    def fit_time(ds):
        """One epoch through Model.fit; the closing float() forces the
        final on-device loss — the single host sync of the epoch."""
        t0 = time.perf_counter()
        model.fit(ds, batch_size=B, epochs=1, verbose=0, shuffle=False,
                  log_freq=10 ** 9, callbacks=[last])
        loss = float(last.logs["loss"])
        return time.perf_counter() - t0, loss

    ds_short, ds_long = dataset(n_short), dataset(n_long)
    fit_time(ds_short)                          # compile + warmup
    from paddle_tpu import profiler
    profiler.reset_step_timeline()  # report overlap for timed runs only
    estimates, loss = [], float("nan")
    for _ in range(2):
        dt_short, _ = fit_time(ds_short)
        dt_long, loss = fit_time(ds_long)
        delta = (dt_long - dt_short) / (n_long - n_short)
        if delta > 0:
            estimates.append(delta)
    # all-jitter fallback: amortised long-run time bounds the step above
    step_time = min(estimates) if estimates else dt_long / n_long
    assert np.isfinite(loss)

    tokens_per_sec = B * T / step_time
    # MFU is a device metric: a CPU run has none
    mfu = None if on_cpu else \
        tokens_per_sec * gpt.flops_per_token(T) / peak_flops(devs)

    if "--breakdown" in sys.argv:
        # step-time decomposition (stderr; stdout stays one JSON line);
        # timing methodology lives in utils/op_bench.bench_fn
        import jax.numpy as jnp

        from paddle_tpu.framework import MethodAdapter, functional_call
        from paddle_tpu.utils.op_bench import bench_fn

        wrapped = MethodAdapter(gpt, "loss")
        params = {k: v._data for k, v in gpt.named_parameters()}
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                          jnp.int32)
        labels = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)

        def loss_of(pp):
            with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
                out, _ = functional_call(wrapped, pp, {}, ids, labels)
            return out

        opt_state = adam.functional_init(params)
        t_fwd = bench_fn(loss_of, params)["ms"]
        t_fb = bench_fn(lambda p: jax.value_and_grad(loss_of)(p),
                        params)["ms"]
        t_opt = bench_fn(lambda p, st: adam.functional_update(
            p, p, st, lr=1e-4), params, opt_state)["ms"]
        step_ms = step_time * 1e3
        print(f"breakdown: step={step_ms:.2f}ms fwd={t_fwd:.2f}ms "
              f"bwd={t_fb - t_fwd:.2f}ms optimizer={t_opt:.2f}ms "
              f"overlap/other={step_ms - t_fb - t_opt:.2f}ms",
              file=sys.stderr)

    # compile observability: total explicit-AOT compile seconds and the
    # persistent-cache verdict ("hit" only when every compile hit)
    compiles = profiler.compile_events()
    compile_s = round(sum(e["compile_s"] for e in compiles), 3)
    verdicts = {e["cache"] for e in compiles}
    compile_cache = ("off" if not verdicts or verdicts == {"off"}
                     else "miss" if "miss" in verdicts else "hit")

    # async-pipeline observability (jit/async_pipeline feeding the
    # profiler step timeline over the timed runs): total host wall-clock
    # actually blocked on device results, max steps in flight, and the
    # mean host dispatch gap vs device step time (overlap is proven when
    # gap < device step time)
    async_stats = profiler.step_timeline_summary()

    # full registry dump (observability layer): every counter the run
    # touched, keyed by Prometheus sample name — diffable across runs
    from paddle_tpu.observability import REGISTRY, install_default_collectors
    install_default_collectors()

    print(json.dumps({
        "metric": "gpt2_124m_fit_tokens_per_sec" if not on_cpu
                  else "gpt_toy_cpu_debug_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": None if on_cpu else round(mfu / 0.45, 4),
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "compile_s": compile_s,
        "compile_cache": compile_cache,
        "steps_in_flight": async_stats["steps_in_flight"],
        "host_blocked_s": async_stats["host_blocked_s"],
        "dispatch_gap_s": async_stats["dispatch_gap_s"],
        "device_step_s": async_stats["device_step_s"],
        "metrics": REGISTRY.flat(),
    }))


if __name__ == "__main__":
    main()
