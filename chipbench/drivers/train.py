"""Training driver: the trainer's whole product path on one chip.

``Model.prepare(Adam, AMP O2 strategy)`` + ``Model.fit`` through the
DataLoader and the device prefetch, a fresh seeded batch per step from
an iterable dataset that ends at the deadline. The opening mark is
taken in the batch-end callback of the last pre-roll step, after that
step's loss has been read (so the device is drained and every later
step's work lies inside the window); the closing mark after the last
step is ready (``fit`` drains its pipeline before the epoch-end
callback). tokens = steps between the marks x batch x sequence.
"""
import json
import math
import time

import numpy as np

from chipbench import harness, stats, traffic


class Marks:
    """What the fit loop's callbacks leave for the driver."""

    def __init__(self):
        self.deadline = None        # the dataset ends here
        self.t_open = None
        self.t_close = None
        self.setup_s = None
        self.first_loss = None
        self.losses = []            # device scalars of the window's steps
        self.compiles_at_open = None
        self.tracer = None


def run(bench, cell, mix, seed, seconds, trace, t_process_start,
        require_tpu=True):
    clock = harness.SetupClock(t_process_start)
    devs = harness.require_devices(cell["chips"], require_tpu)
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import profiler
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.hapi import Model, callbacks as hapi_cbks
    from paddle_tpu.io import DataLoader, IterableDataset
    from paddle_tpu.jit.compile_cache import setup_compilation_cache
    from paddle_tpu.static import InputSpec

    setup_compilation_cache()
    clock.mark("import")
    _, sizes, family = harness.load_config(bench, cell["config"])
    B, T = int(mix["batch_size"]), int(mix["seq_len"])
    if mix["optimizer"] != "adam" or mix["amp"] != "O2":
        raise ValueError("train driver: only adam under AMP O2 is wired")
    marks = Marks()
    preroll = int(mix["preroll_steps"])

    class Batches(IterableDataset):
        """Samples of seeded batches, B at a time, until the deadline
        (looked at between batches only: a short batch would be a new
        shape)."""

        def __iter__(self):
            step = 0
            while marks.deadline is None \
                    or time.perf_counter() < marks.deadline:
                ids, labels = traffic.train_batch(
                    B, T, sizes["vocab_size"], seed, step)
                for i in range(B):
                    yield ids[i], labels[i]
                step += 1

    class Window(hapi_cbks.Callback):
        def on_train_batch_end(self, step, logs=None):
            loss = logs["loss"]
            if step == 0:
                marks.first_loss = float(loss)
            if step == preroll - 1:
                float(loss)                  # drains the device
                marks.compiles_at_open = len(profiler.compile_events())
                profiler.reset_step_timeline()
                marks.setup_s = clock.total()
                marks.t_open = time.perf_counter()
                marks.deadline = marks.t_open + seconds
                if trace:
                    marks.tracer = harness.MidWindowTrace(
                        marks.t_open, seconds, mix.get("trace_seconds", 2.0))
            elif step >= preroll:
                marks.losses.append(loss)

        def on_epoch_end(self, epoch, logs=None):
            marks.t_close = time.perf_counter()

    # weights: the program's own seeded constructor (the product path)
    paddle.seed(seed % (2 ** 31 - 1))
    net, initial = family.training_net(sizes)
    jax.block_until_ready(initial)
    clock.mark("weights")

    ids0, labels0 = traffic.train_batch(B, T, sizes["vocab_size"], seed, 0)
    ref_params = family.to_reference(initial)
    ref_loss = float(np.mean([family.reference_loss(
        ref_params, ids0[i], labels0[i], sizes) for i in range(B)]))
    del ref_params, initial
    clock.mark("reference_check")

    model = Model(net, inputs=[InputSpec([None, T], "int32"),
                               InputSpec([None, T], "int32")])
    s = DistributedStrategy()
    s.amp = True
    s.amp_configs.use_pure_bf16 = True
    s.build_mesh(devices=devs)
    adam = opt.Adam(learning_rate=float(mix["learning_rate"]),
                    parameters=model.parameters())
    model.prepare(adam, strategy=s)
    clock.mark("prepare")
    loader = DataLoader(Batches(), batch_size=B, shuffle=False,
                        drop_last=True)
    model.fit(loader, epochs=1, verbose=0, log_freq=10 ** 9,
              callbacks=[Window()])
    if marks.t_open is None or marks.t_close is None:
        raise RuntimeError("fit ended before the window opened")
    traced = marks.tracer.result() if marks.tracer else None
    prog = getattr(model, "_dist_prog", None)
    temp_bytes = harness.program_temp_bytes([getattr(prog, "_aot", None)])
    compiles_in_window = len(profiler.compile_events()) \
        - marks.compiles_at_open
    losses = [float(v) for v in marks.losses]
    steps = len(losses)
    tokens = steps * B * T
    rate = stats.rate(tokens, marks.t_open, marks.t_close)
    loss_err = abs(marks.first_loss - ref_loss)
    finite = bool(losses) and all(math.isfinite(v) for v in losses)
    checks = {"loss_abs_err": (loss_err, family.LOSS_TOL),
              "compiles_in_window": (compiles_in_window, 0),
              "losses_not_finite": (0 if finite else 1, 0)}
    correct = (loss_err <= family.LOSS_TOL and compiles_in_window == 0
               and finite)
    values = {"train_tokens_per_s": rate, "setup_s": marks.setup_s}
    print("SETUP " + json.dumps(
        {"parts": clock.parts + [["compile_and_pre_roll", round(
            marks.setup_s - sum(p[1] for p in clock.parts), 3)]],
         "setup_s": round(marks.setup_s, 3),
         "compiles": [(e["label"], e["compile_s"], e["cache"])
                      for e in profiler.compile_events()],
         "first_loss": marks.first_loss, "reference_loss": ref_loss,
         "loss_abs_err": loss_err}), flush=True)
    print("WINDOW " + json.dumps(
        {"window_s": marks.t_close - marks.t_open, "steps": steps,
         "tokens": tokens, "compiles_in_window": compiles_in_window,
         "loss_first_in_window": losses[0] if losses else None,
         "loss_last": losses[-1] if losses else None,
         "largest_temp_bytes": temp_bytes,
         "end_to_end": values}), flush=True)
    return harness.result_line(
        bench, cell, mix, sizes, family, (marks.t_open, marks.t_close),
        trace, values, correct, steps, 0 if finite else 1, devs, traced,
        temp_bytes, checks, ring=None, step_timeline=profiler.step_timeline())
