"""hapi Model — fit/evaluate/predict on a jit-compiled functional step.

Reference: python/paddle/hapi/model.py:810 (Model), :1299 (fit); the
reference dispatches each batch through the dygraph tracer or a static
Program (adapters model.py:224,:609). TPU-native redesign: ONE jitted
train step — functional_call(layer) + jax.value_and_grad + the optimizer's
pure functional_update — so the whole step (fwd, bwd, update) is a single
XLA executable; buffers (BN stats) and the dropout PRNG key are threaded
functionally through the step instead of mutated.
"""
from __future__ import annotations

import os
import pickle
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as random_mod
from ..core.tensor import Tensor
from ..framework import functional_call
from ..io import DataLoader
from ..jit import compile_cache
from ..metric import Metric
from . import callbacks as cbks_mod


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _to_jax(batch):
    out = []
    for b in _as_list(batch):
        out.append(b._data if isinstance(b, Tensor) else jnp.asarray(b))
    return out


class _AsyncScalar:
    """A loss that stays on device until someone looks at it.

    fit() keeps the dispatch pipeline full by NOT fetching the loss every
    batch (each fetch is a host sync that drains the in-flight steps);
    callbacks/logs materialise it lazily at log_freq.
    Reference analog: the monitor fetches fetch_list values only at
    Profiler/log steps, not per batch."""

    __slots__ = ("_arr", "_val")

    def __init__(self, arr):
        self._arr = arr
        self._val = None

    def __float__(self):
        if self._val is None:
            self._val = float(jax.device_get(self._arr))
            self._arr = None
        return self._val

    def __format__(self, spec):
        return format(float(self), spec)

    def __repr__(self):
        return repr(float(self))

    def __int__(self):
        return int(float(self))

    def __round__(self, ndigits=None):
        return round(float(self), ndigits)

    def __bool__(self):
        return bool(float(self))

    def __neg__(self):
        return -float(self)

    def __abs__(self):
        return abs(float(self))

    def __hash__(self):
        return hash(float(self))

    @staticmethod
    def _coerce(o):
        try:
            return float(o)
        except (TypeError, ValueError):
            return None

    def _cmp(self, o, op):
        v = self._coerce(o)
        if v is None:
            return NotImplemented
        return op(float(self), v)

    def __lt__(self, o):
        return self._cmp(o, lambda a, b: a < b)

    def __le__(self, o):
        return self._cmp(o, lambda a, b: a <= b)

    def __gt__(self, o):
        return self._cmp(o, lambda a, b: a > b)

    def __ge__(self, o):
        return self._cmp(o, lambda a, b: a >= b)

    def __eq__(self, o):
        v = self._coerce(o)
        # mirror float: incomparable operands are unequal, never an error
        return False if v is None else float(self) == v

    def __ne__(self, o):
        return not self.__eq__(o)

    def __add__(self, o):
        return self._cmp(o, lambda a, b: a + b)

    __radd__ = __add__

    def __sub__(self, o):
        return self._cmp(o, lambda a, b: a - b)

    def __rsub__(self, o):
        return self._cmp(o, lambda a, b: b - a)

    def __mul__(self, o):
        return self._cmp(o, lambda a, b: a * b)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._cmp(o, lambda a, b: a / b)

    def __rtruediv__(self, o):
        return self._cmp(o, lambda a, b: b / a)


import numbers as _numbers

_numbers.Real.register(_AsyncScalar)


class Model:
    """Wraps a Layer with train/eval/predict loops (hapi/model.py:810)."""

    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._amp_level = "O0"
        self._jit_step = None
        self._jit_eval = None
        self._jit_pred = None
        self._grad_accum_n = 1
        self.stop_training = False

    # ------------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, strategy=None):
        """strategy: a DistributedStrategy routes training through the
        fleet strategy compiler (dp/ZeRO/tp/sp/ep per its toggles).
        Metric-less evaluation runs under the SAME shardings (no host
        gather); metric evaluation and predict sync params and run
        single-device."""
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _as_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metrics must be Metric, got {type(m)}")
        if isinstance(amp_configs, str):
            self._amp_level = amp_configs
        elif isinstance(amp_configs, dict):
            self._amp_level = amp_configs.get("level", "O1")
        self._strategy = strategy
        if strategy is not None and self._metrics:
            import warnings
            if getattr(strategy, "pipeline", False):
                warnings.warn(
                    "metrics under a PIPELINE strategy evaluate on the "
                    "synced host path (the pp eval program computes only "
                    "the loss); non-pp strategies compute metrics under "
                    "the training shardings via evaluate()")
            else:
                warnings.warn(
                    "metrics are computed by evaluate() (under the "
                    "training shardings), not during fit() — the strategy "
                    "train step returns only the loss, so per-batch train "
                    "logs omit metric values")
        if strategy is not None and self._amp_level != "O0" \
                and not strategy.amp:
            import warnings
            warnings.warn(
                "amp_configs is ignored on the strategy training path; "
                "set strategy.amp=True (+ amp_configs.use_pure_bf16 for "
                "O2) instead")
        # wire the persistent XLA compile cache before the first compile
        compile_cache.setup_compilation_cache()
        self._invalidate()

    def _invalidate(self):
        self._dist_prog = None
        self._jit_step = self._jit_eval = self._jit_pred = None
        self._jit_grad = self._jit_apply = None
        self._aot_step = None
        self._retrace_guard = None
        self._compile_stats = None
        self._accum_grads = None
        self._accum_count = 0

    # -- functional plumbing -------------------------------------------
    def _split_tree(self, copy=False):
        from ..framework import param_arrays, state_arrays, unaliased_put
        params = param_arrays(self.network)
        state = state_arrays(self.network)
        if copy:
            # the jitted train step donates params: a no-copy split would
            # leave the network's own Tensors holding deleted buffers
            params = {k: unaliased_put(v) for k, v in params.items()}
        return params, state

    def _write_back(self, params, state):
        lookup = dict(self.network.named_parameters())
        lookup.update(dict(self.network.named_buffers()))
        for k, v in {**params, **state}.items():
            if k in lookup:
                lookup[k]._data = v

    def _compute_loss(self, outputs, labels):
        outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
        if self._loss is None:
            return outs[0]
        wrapped_outs = [Tensor(o) if not isinstance(o, Tensor) else o
                        for o in outs]
        wrapped_lbls = [Tensor(l) if not isinstance(l, Tensor) else l
                        for l in labels]
        loss = self._loss(*wrapped_outs, *wrapped_lbls)
        return loss._data if isinstance(loss, Tensor) else loss

    def _build_train_step(self):
        optimizer = self._optimizer
        optimizer.collect_param_regularizers(self.network)
        amp_on = self._amp_level in ("O1", "O2")

        def train_step(params, state, opt_state, key, lr, inputs, labels):
            def loss_of(p):
                from .. import amp as amp_mod
                with random_mod.key_scope(key):
                    ctx = amp_mod.auto_cast(enable=amp_on,
                                            level=self._amp_level,
                                            dtype="bfloat16")
                    with ctx:
                        outs, new_state = functional_call(
                            self.network, p, state, *inputs)
                loss = self._compute_loss(outs, labels)
                return loss, (outs, new_state)

            (loss, (outs, new_state)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            new_params, new_opt = optimizer.functional_update(
                params, grads, opt_state, lr=lr)
            return loss, outs, new_params, new_state, new_opt

        return jax.jit(train_step,
                       donate_argnums=self._donate_argnums((0, 2), 2))

    def _build_grad_step(self):
        amp_on = self._amp_level in ("O1", "O2")

        def grad_step(params, state, key, inputs, labels):
            def loss_of(p):
                from .. import amp as amp_mod
                with random_mod.key_scope(key):
                    with amp_mod.auto_cast(enable=amp_on,
                                           level=self._amp_level,
                                           dtype="bfloat16"):
                        outs, new_state = functional_call(
                            self.network, p, state, *inputs)
                loss = self._compute_loss(outs, labels)
                return loss, (outs, new_state)

            (loss, (outs, new_state)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            return loss, outs, new_state, grads

        return jax.jit(grad_step)

    def _build_apply_step(self):
        optimizer = self._optimizer
        n_acc = self._grad_accum_n

        def apply_step(params, opt_state, grads, lr):
            grads = jax.tree_util.tree_map(lambda g: g / n_acc, grads)
            return optimizer.functional_update(params, grads, opt_state,
                                               lr=lr)

        # donate params + opt slots only: donated grads have no matching
        # output to alias for slot-less optimizers (SGD), which made XLA
        # warn "Some donated buffers were not usable" on every fit
        return jax.jit(apply_step,
                       donate_argnums=self._donate_argnums((0, 1), 1))

    def _donate_argnums(self, argnums, opt_argnum):
        """Drop the opt_state argnum when the optimizer keeps no slots
        (e.g. plain SGD): donating a leaf-less pytree arg makes XLA warn
        "Some donated buffers were not usable" on every compile."""
        opt_state = getattr(self, "_opt_state", None)
        if not jax.tree_util.tree_leaves(opt_state):
            return tuple(a for a in argnums if a != opt_argnum)
        return tuple(argnums)

    def _build_eval_step(self):
        def eval_step(params, state, inputs, labels):
            outs, _ = functional_call(self.network, params, state, *inputs)
            loss = (self._compute_loss(outs, labels)
                    if (self._loss is not None and labels) else None)
            return loss, outs

        return jax.jit(eval_step)

    # ------------------------------------------------------------------
    def _dist_train_batch(self, inputs, labels, sync=True):
        """Strategy-compiled step (reference: fleet.distributed_optimizer
        -> meta-optimizer rewrites; here compile_train_step)."""
        from ..distributed.fleet.compiler import compile_train_step

        if self._dist_prog is None:
            net, model = self.network, self

            class _LossAdapter:
                """Presents network+loss as the layer-with-a-loss-method
                protocol compile_train_step drives. param_shardings is
                delegated via __getattr__ only when the network has one —
                the compiler provides the replicated fallback."""

                def named_parameters(self, *a, **k):
                    return net.named_parameters(*a, **k)

                def named_buffers(self, *a, **k):
                    return net.named_buffers(*a, **k)

                def named_sublayers(self, *a, **k):
                    # the compiler walks these for scan-stacked params
                    return net.named_sublayers(*a, **k)

                # train/eval must reach the real network: the pipelined
                # eval builder flips the layer to eval mode around its
                # trace (dropout blocks refuse keyless TRAIN traces)
                def eval(self):
                    net.eval()

                def train(self):
                    net.train()

                @property
                def training(self):
                    return getattr(net, "training", False)

                _FORWARDED = ("param_shardings",
                              "pipeline_split_params", "pipeline_fns",
                              # manual-tp pipeline protocol (pp x tp)
                              "split_block_params_tp", "block_tp_specs",
                              "pipeline_block_fn_tp",
                              "merge_block_params_tp",
                              "pipeline_block_fn_sp",
                              # expert-parallel pipeline protocol
                              "pipeline_block_fn_ep", "block_ep_specs",
                              "pipeline_block_emits_aux", "cfg",
                              # scan-over-layers unroll escape hatch
                              "set_scan_unroll")

                def __getattr__(self, name):
                    # expose the network's sharding/pipeline protocols to
                    # the compiler only when the network implements them
                    if name in self._FORWARDED and \
                            getattr(net, name, None) is not None:
                        return getattr(net, name)
                    raise AttributeError(name)

                def loss(self, *batch):
                    k = model._dist_n_inputs
                    outs = net(*batch[:k])
                    return Tensor(model._compute_loss(outs,
                                                      list(batch[k:])))

                def loss_and_outs(self, *batch):
                    """Sharded-eval protocol: loss + forward outputs so
                    metric states accumulate without gathering params."""
                    k = model._dist_n_inputs
                    outs = net(*batch[:k])
                    first = outs[0] if isinstance(outs, (list, tuple)) \
                        else outs
                    return (Tensor(model._compute_loss(outs,
                                                       list(batch[k:]))),
                            first)

            self._dist_n_inputs = len(inputs)
            from ..distributed import mesh as mesh_mod
            mesh = mesh_mod.get_mesh()
            if mesh is not None:
                # a stale global mesh from another strategy must not
                # silently override this strategy's degrees; a mesh whose
                # device count can't even satisfy the strategy (ValueError
                # from resolve_degrees) is just as stale as one with the
                # wrong axis sizes
                try:
                    want = self._strategy.resolve_degrees(
                        len(mesh.devices.ravel()))
                except ValueError:
                    want = None
                have = {k: int(v) for k, v in mesh.shape.items()}
                if want is None or {k: v for k, v in want.items()
                                    if k in have} != have:
                    mesh = None     # compiler rebuilds from the strategy
            self._dist_prog = compile_train_step(
                _LossAdapter(), self._optimizer, self._strategy,
                mesh=mesh)
            restored = getattr(self, "_restored_opt_state", None)
            if restored is not None and \
                    set(restored) == set(self._dist_prog.opt_state) and \
                    all(set(restored[n]) ==
                        set(self._dist_prog.opt_state[n])
                        for n in restored):
                sh = self._dist_prog.shardings["opt"]
                self._dist_prog.opt_state = {
                    n: {sl: jax.device_put(jnp.asarray(v), sh[n][sl])
                        for sl, v in st.items()}
                    for n, st in restored.items()}
                self._restored_opt_state = None
        loss = self._dist_prog.step(*inputs, *labels,
                                    lr=self._optimizer.get_lr())
        self._dist_dirty = True
        return [float(jax.device_get(loss))] if sync \
            else [_AsyncScalar(loss)]

    def train_batch(self, inputs, labels=None, sync=True):
        """One optimizer step on a batch; returns [loss] (+metric updates).
        sync=False keeps the loss on device (fit's log_freq-deferred
        fetch; the returned value is float-convertible on demand)."""
        if self._optimizer is None:
            raise RuntimeError("call prepare(optimizer, loss) first")
        self.network.train()
        if getattr(self, "_strategy", None) is not None:
            if getattr(self, "_grad_accum_n", 1) > 1:
                raise ValueError(
                    "accumulate_grad_batches is not supported with a "
                    "DistributedStrategy; set strategy.gradient_merge "
                    "and gradient_merge_configs.k_steps instead")
            return self._dist_train_batch(_as_list(inputs),
                                          _as_list(labels), sync=sync)
        if self._jit_step is None:
            self._params, self._state = self._split_tree(copy=True)
            restored = getattr(self, "_restored_opt_state", None)
            if restored is not None and set(restored) == set(self._params):
                self._opt_state = jax.tree_util.tree_map(jnp.asarray, restored)
            else:
                self._opt_state = self._optimizer.functional_init(self._params)
            self._restored_opt_state = None
            # opt_state must exist first: _build_train_step derives
            # donate_argnums from whether the optimizer keeps slots
            self._jit_step = self._build_train_step()
            self._aot_step = None
            self._retrace_guard = compile_cache.RetraceGuard(
                "hapi.train_step")
        inputs = _to_jax(inputs)
        labels = _to_jax(labels)
        key = random_mod.next_key()
        lr = jnp.asarray(self._optimizer.get_lr(), jnp.float32)
        n_acc = getattr(self, "_grad_accum_n", 1)
        if n_acc > 1:
            # gradient merge (reference GradientMergeOptimizer
            # optimizer.py:5671): accumulate microbatch grads, apply every
            # n_acc batches with the mean
            if getattr(self, "_jit_grad", None) is None:
                self._jit_grad = self._build_grad_step()
                self._jit_apply = self._build_apply_step()
                self._accum_grads = None
                self._accum_count = 0
            loss, outs, self._state, grads = self._jit_grad(
                self._params, self._state, key, inputs, labels)
            self._accum_grads = grads if self._accum_grads is None else \
                jax.tree_util.tree_map(jnp.add, self._accum_grads, grads)
            self._accum_count += 1
            if self._accum_count >= n_acc:
                self._params, self._opt_state = self._jit_apply(
                    self._params, self._opt_state, self._accum_grads, lr)
                self._accum_grads = None
                self._accum_count = 0
        else:
            args = (self._params, self._state, self._opt_state,
                    key, lr, inputs, labels)
            verdict = self._retrace_guard.check(inputs=inputs,
                                                labels=labels)
            if self._aot_step is None or verdict == "retrace":
                # explicit AOT compile (timed, persistent-cache aware)
                # instead of the first-step implicit trace; the compiled
                # executable is called directly below — lowering does not
                # seed the jit wrapper's own cache
                try:
                    self._aot_step, self._compile_stats = \
                        compile_cache.aot_compile(self._jit_step, *args,
                                                  label="hapi.train_step")
                except compile_cache.RetraceError:
                    raise
                except Exception:  # exotic input: keep the implicit path
                    self._aot_step = self._jit_step
            loss, outs, self._params, self._state, self._opt_state = \
                self._aot_step(*args)
        self._update_metrics(outs, labels)
        return [float(jax.device_get(loss))] if sync \
            else [_AsyncScalar(loss)]

    def _sync_dist_if_dirty(self):
        """One host gather per train->eval transition, not per batch."""
        if getattr(self, "_dist_prog", None) is not None and \
                getattr(self, "_dist_dirty", False):
            self._dist_prog.write_back()
            self._dist_dirty = False

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        prog = getattr(self, "_dist_prog", None)
        batch0 = _as_list(inputs)[0] if _as_list(inputs) else None
        div = getattr(prog, "_eval_batch_divisor", 0) if prog else 0
        # read shape without materializing (np.asarray on a device array
        # would force a device->host copy per eval step)
        b0 = getattr(batch0, "shape", None)
        b0 = (b0[0] if b0 else
              (len(batch0) if hasattr(batch0, "__len__") else None))
        metrics_ok = (not self._metrics or
                      getattr(prog, "_eval_returns_outs", False))
        if getattr(self, "_strategy", None) is not None and \
                prog is not None and \
                getattr(prog, "_eval_builder", None) is not None and \
                metrics_ok and batch0 is not None and div and \
                b0 is not None and b0 % div == 0 and b0 >= div:
            # evaluate under the TRAINING shardings — no host gather of
            # params, no single-device replication of a model that only
            # fits sharded (pp/tp/ZeRO-3 scale). Metric states come from
            # the step's returned outputs (batch-sized transfer only);
            # pipeline programs (no outs) and partial final batches fall
            # through to the synced path.
            labels_j = _to_jax(labels)
            res = prog.eval_step(*_to_jax(inputs), *labels_j)
            if getattr(prog, "_eval_returns_outs", False):
                loss, outs = res
                if self._metrics:
                    self._update_metrics(jax.device_get(outs), labels_j)
            else:
                loss = res
            return [float(jax.device_get(loss))]
        self._sync_dist_if_dirty()     # eval on the TRAINED params
        if self._jit_eval is None:
            self._jit_eval = self._build_eval_step()
        if self._jit_step is not None:
            params, state = self._params, self._state
        else:
            params, state = self._split_tree()
        inputs, labels = _to_jax(inputs), _to_jax(labels)
        loss, outs = self._jit_eval(params, state, inputs, labels)
        self._update_metrics(outs, labels)
        return [float(jax.device_get(loss))] if loss is not None else []

    def predict_batch(self, inputs):
        self.network.eval()
        self._sync_dist_if_dirty()
        if self._jit_eval is None:
            self._jit_eval = self._build_eval_step()
        if self._jit_step is not None:
            params, state = self._params, self._state
        else:
            params, state = self._split_tree()
        _, outs = self._jit_eval({**params}, state, _to_jax(inputs), [])
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        return [np.asarray(jax.device_get(o)) for o in outs]

    def _update_metrics(self, outs, labels):
        if not self._metrics:
            return
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        pred = Tensor(outs[0])
        lbls = [Tensor(l) for l in labels]
        for m in self._metrics:
            res = m.compute(pred, *lbls)
            res = res if isinstance(res, (list, tuple)) else [res]
            m.update(*[np.asarray(r._data if isinstance(r, Tensor) else r)
                       for r in res])

    # ------------------------------------------------------------------
    def _make_loader(self, data, batch_size, shuffle, drop_last=False,
                     num_workers=0):
        if data is None or isinstance(data, DataLoader):
            return data
        return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                          drop_last=drop_last, num_workers=num_workers)

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, prefetch_device=True):
        """Train loop with callbacks (reference fit hapi/model.py:1299).

        TPU-grade loop discipline: batches are device_put ahead of compute
        by a background thread (prefetch_device; reference
        operators/reader/buffered_reader.cc) and the per-batch loss stays
        on device until a callback/log actually reads it, so the host
        never blocks the dispatch pipeline between steps."""
        train_loader = self._make_loader(train_data, batch_size, shuffle,
                                         drop_last, num_workers)
        eval_loader = self._make_loader(eval_data, batch_size, False)
        n_acc = max(int(accumulate_grad_batches), 1)
        if n_acc != self._grad_accum_n:
            self._grad_accum_n = n_acc
            self._jit_grad = self._jit_apply = None  # apply step captures n
            self._accum_grads, self._accum_count = None, 0

        metric_names = ["loss"]
        for m in self._metrics:
            n = m.name()
            metric_names += list(n) if isinstance(n, (list, tuple)) else [n]
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, epochs=epochs,
            steps=self._len_or_none(train_loader), verbose=verbose,
            log_freq=log_freq, save_freq=save_freq, save_dir=save_dir,
            metrics=metric_names)

        cbks.on_begin("train")
        self.stop_training = False
        logs = {}
        try:
            self._fit_epochs(epochs, train_loader, eval_loader, eval_freq,
                             batch_size, num_iters, prefetch_device, cbks,
                             logs)
        finally:
            # hand the user back a live Layer even on Ctrl-C / callback
            # raise: the plain-path jitted step donated the layer's OWN
            # buffers on step 1, so without this the network's Tensors
            # reference deleted arrays. The strategy path device_put-
            # COPIES at compile (tensors stay valid, just stale) and
            # keeps the deferred write_back on eval/save — a full host
            # gather per fit() costs seconds on big models.
            if self._jit_step is not None:
                self._write_back(self._params, self._state)
        return self

    def _fit_epochs(self, epochs, train_loader, eval_loader, eval_freq,
                    batch_size, num_iters, prefetch_device, cbks, logs):
        from ..jit import async_pipeline as _apipe
        window = _apipe.async_steps()
        # window 0: synchronous stepping (fetch the loss every step) —
        # the bit-identical reference for the async path. window >= 1:
        # keep up to that many steps in flight, block_until_ready on the
        # oldest ticket for backpressure, fetch metrics lazily.
        pipeline = (_apipe.AsyncStepPipeline(window, label="hapi.fit")
                    if window >= 1 else None)
        self._async_pipeline = pipeline
        global_step = 0
        try:
            self._fit_epoch_loop(epochs, train_loader, eval_loader,
                                 eval_freq, batch_size, num_iters,
                                 prefetch_device, cbks, logs, pipeline,
                                 global_step)
        finally:
            # the stall watchdog must not outlive the fit that owns it
            if pipeline is not None:
                pipeline.close()

    def _fit_epoch_loop(self, epochs, train_loader, eval_loader, eval_freq,
                        batch_size, num_iters, prefetch_device, cbks, logs,
                        pipeline, global_step):
        for epoch in range(epochs):
            if self.stop_training:
                break
            cbks.on_epoch_begin(epoch)
            self._reset_metrics()
            it = train_loader
            if prefetch_device:
                from ..io.dataloader import device_prefetch
                # strategy path: place batches directly onto the step's
                # data sharding (known once the first batch has compiled;
                # epoch 0 falls back to default placement). put_batch
                # additionally applies the step's host-side preproc
                # (pipeline microbatching) off the critical path.
                prog = getattr(self, "_dist_prog", None)
                sh = getattr(prog, "data_sharding", None)
                place = getattr(prog, "put_batch", None)
                it = device_prefetch(iter(train_loader), sharding=sh,
                                     place=place)
            it = iter(it)
            step = 0
            try:
                while True:
                    t0 = time.perf_counter()
                    try:
                        batch = next(it)
                    except StopIteration:
                        break
                    collate_s = time.perf_counter() - t0
                    cbks.on_batch_begin("train", step, logs)
                    ins, lbls = self._split_batch(batch)
                    t1 = time.perf_counter()
                    losses = self.train_batch(ins, lbls,
                                              sync=pipeline is None)
                    dispatch_s = time.perf_counter() - t1
                    if pipeline is not None and losses:
                        pipeline.submit(losses[0], global_step,
                                        collate_s=collate_s,
                                        dispatch_s=dispatch_s)
                    logs = self._step_logs(losses, step, batch_size)
                    cbks.on_batch_end("train", step, logs)
                    step += 1
                    global_step += 1
                    if num_iters is not None and global_step >= num_iters:
                        self.stop_training = True
                        break
            finally:
                # retire outstanding tickets before eval/save callbacks
                # touch the params, and surface any deferred step failure
                # (AsyncStepError names the poisoned step) inside fit
                if pipeline is not None:
                    pipeline.drain()
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(eval_loader, verbose=0,
                                          _inside_fit=cbks)
                logs.update({"eval_" + k: v for k, v in eval_logs.items()})
            cbks.on_epoch_end(epoch, logs)
        cbks.on_end("train", logs)

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, _inside_fit=None):
        loader = self._make_loader(eval_data, batch_size, False,
                                   num_workers=num_workers)
        self._reset_metrics()
        losses_sum, n = 0.0, 0
        cbks = _inside_fit
        if cbks is None and (callbacks or verbose):
            cbks = cbks_mod.config_callbacks(
                callbacks, model=self, verbose=verbose, log_freq=log_freq,
                steps=self._len_or_none(loader), mode="eval")
        if cbks:
            cbks.on_begin("eval")
        for step, batch in enumerate(loader):
            ins, lbls = self._split_batch(batch)
            losses = self.eval_batch(ins, lbls)
            if losses:
                losses_sum += losses[0]
                n += 1
        logs = {}
        if n:
            logs["loss"] = losses_sum / n
        for m in self._metrics:
            logs.update(self._metric_items(m))
        if cbks:
            cbks.on_end("eval", logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        loader = self._make_loader(test_data, batch_size, False,
                                   num_workers=num_workers)
        outputs = []
        for batch in loader:
            ins, _ = self._split_batch(batch, has_labels=False)
            outputs.append(self.predict_batch(ins))
        # transpose: list-of-batches -> per-output list
        n_out = len(outputs[0]) if outputs else 0
        per_out = [[b[i] for b in outputs] for i in range(n_out)]
        if stack_outputs:
            per_out = [np.concatenate(o, axis=0) for o in per_out]
        return per_out

    # ------------------------------------------------------------------
    def _inputs_spec(self):
        """InputSpec list for inference export (Model(net, inputs=...))."""
        from ..static import InputSpec
        if self._inputs is None:
            raise ValueError(
                "Model.save(training=False) needs the Model constructed "
                "with inputs=[InputSpec(...)] so the exported program's "
                "signature is known")
        out = []
        for s in _as_list(self._inputs):
            if isinstance(s, InputSpec):
                out.append(s)
            else:
                out.append(InputSpec(tuple(s.shape), str(s.dtype)))
        return out

    # ------------------------------------------------------------------
    def _split_batch(self, batch, has_labels=True):
        batch = batch if isinstance(batch, (list, tuple)) else [batch]
        if self._inputs is not None:
            n_in = len(_as_list(self._inputs))
            ins = list(batch[:n_in])
            lbls = list(batch[n_in:]) if has_labels else []
            return ins, lbls
        # no input spec: (x, y) convention — trailing element is the label,
        # dropped (not fed to the network) in predict mode
        n_lbl = 1 if len(batch) > 1 else 0
        if n_lbl == 0:
            return list(batch), []
        return list(batch[:-n_lbl]), \
            (list(batch[-n_lbl:]) if has_labels else [])

    @staticmethod
    def _metric_items(m):
        """paddle Metric.name()/accumulate() may return scalars or lists
        (Accuracy with multiple topk)."""
        names = m.name()
        vals = m.accumulate()
        names = names if isinstance(names, (list, tuple)) else [names]
        vals = vals if isinstance(vals, (list, tuple)) else [vals]
        return list(zip(names, vals))

    def _step_logs(self, losses, step, batch_size):
        logs = {"loss": losses[0] if losses else 0.0, "step": step,
                "batch_size": batch_size}
        # the strategy training step computes only the loss — metric
        # states never update during fit there, so reporting
        # accumulate() would print frozen zeros as if they were live
        if getattr(self, "_strategy", None) is None:
            for m in self._metrics:
                logs.update(self._metric_items(m))
        return logs

    def _reset_metrics(self):
        for m in self._metrics:
            m.reset()

    @staticmethod
    def _len_or_none(loader):
        try:
            return len(loader)
        except Exception:
            return None

    # ------------------------------------------------------------------
    def _sync_network(self):
        """Write jitted-step params back into the Layer tree."""
        if getattr(self, "_dist_prog", None) is not None:
            self._dist_prog.write_back()
        if self._jit_step is not None:
            self._write_back(self._params, self._state)

    def save(self, path, training=True):
        """training=True: checkpoint (state dict + optimizer slots).
        training=False: inference export — serialized StableHLO + params
        via paddle_tpu.jit.save, loadable without the model class
        (reference Model.save hapi/model.py -> save_inference_model)."""
        self._sync_network()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        if not training:
            from .. import jit as jit_mod
            spec = self._inputs_spec()
            jit_mod.save(self.network, path, input_spec=spec)
            return
        from ..framework import save as fsave
        fsave(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            opt_sd = self._optimizer.state_dict()
            if getattr(self, "_dist_prog", None) is not None:
                opt_sd["functional_state"] = jax.device_get(
                    self._dist_prog.opt_state)
            elif self._jit_step is not None:
                opt_sd["functional_state"] = jax.device_get(self._opt_state)
            with open(path + ".pdopt", "wb") as f:
                pickle.dump(opt_sd, f, protocol=4)

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework import load as fload
        sd = fload(path + ".pdparams")
        self.network.set_state_dict(sd)
        self._invalidate()
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + ".pdopt"):
            with open(path + ".pdopt", "rb") as f:
                opt_sd = pickle.load(f)
            # functional slots (Adam moments etc.) re-seed the next jit step
            self._restored_opt_state = opt_sd.pop("functional_state", None)
            self._optimizer.set_state_dict(opt_sd)
        return self

    def parameters(self, *a, **k):
        return self.network.parameters(*a, **k)

    def summary(self, input_size=None, dtype=None):
        from .summary import summary as _summary
        return _summary(self.network, input_size, dtypes=dtype)
