"""Strategy compiler: DistributedStrategy + Layer + Optimizer -> one jitted
SPMD train step.

Reference analog: fleet/base/strategy_compiler.py + the meta-optimizer
stack (fleet/meta_optimizers/*, SURVEY.md §2 row 37) which rewrite the
Program op-by-op (insert c_broadcast/c_allreduce, cast ops, recompute
clones). Here each strategy toggle maps to a functional transform or a
sharding assignment and XLA emits the collectives:

  amp            -> autocast ctx inside the traced step (+ bf16: no loss
                    scaling needed on TPU, bf16 exponent == fp32)
  recompute      -> jax.checkpoint around the forward
  tensor_parallel-> model-supplied param PartitionSpecs ('tp' axis)
  sharding (ZeRO)-> optimizer-state/grad/param specs over 'dp'
  dp             -> batch PartitionSpec over 'dp'
  gradient_merge -> microbatch lax.scan accumulating grads
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core import nan_inf
from ...core import random as random_mod
from ...framework import MethodAdapter, functional_call, param_arrays, \
    state_arrays, unaliased_put
from .. import sharding as zero_mod
from .strategy import DistributedStrategy


class CompiledTrainStep:
    """Holds the jitted step + sharded live arrays; call(step_fn) style:
        prog = compile_train_step(layer, opt, strategy, loss_method="loss")
        loss = prog.step(ids, labels)        # updates internal params
    """

    def __init__(self, step, params, state, opt_state, shardings, mesh,
                 layer, data_sharding):
        self._step = step
        self.params = params
        self.state = state
        self.opt_state = opt_state
        self.shardings = shardings
        self.mesh = mesh
        self.layer = layer
        self.data_sharding = data_sharding
        self._opt = None
        self._step_label = "fleet.train_step"
        self._aot = None
        self._guard = None
        self.compile_stats = None

    def step(self, *data, lr=None):
        data = tuple(self._put_data(d) for d in data)
        key = random_mod.next_key()
        if lr is None:
            # follow the optimizer's configured lr / scheduler
            lr = self._opt.get_lr() if self._opt is not None else 1e-3
        lr = jnp.asarray(lr, jnp.float32)
        args = (self.params, self.state, self.opt_state, key, lr, data)
        # every strategy path (SPMD jit, pipeline, grad_comm shard_map)
        # funnels here: AOT-compile once (timed, persistent-cache aware)
        # and watch the data signature instead of silently retracing
        from ...jit import compile_cache
        if self._guard is None:
            self._guard = compile_cache.RetraceGuard(self._step_label)
        verdict = self._guard.check(data=data)
        if self._aot is None or verdict == "retrace":
            # CPU + multi-device mesh: never serve this executable from
            # the persistent cache — deserializing a sub-mesh SPMD
            # executable on the CPU backend corrupts the heap (observed
            # under xla_force_host_platform_device_count); TPU keeps it
            n_mesh = int(getattr(self.mesh, "size", 1) or 1) \
                if self.mesh is not None else 1
            use_cache = not (n_mesh > 1
                             and jax.default_backend() == "cpu")
            self._aot, self.compile_stats = compile_cache.aot_compile(
                self._step, *args, label=self._step_label,
                use_cache=use_cache)
        loss, self.params, self.state, self.opt_state = self._aot(*args)
        return loss

    def eval_step(self, *data):
        """Loss on a batch under the SAME shardings as training — no
        host gather, no parameter replication onto one device (the
        reference evaluates pp/tp models through the sharded program
        too; a single-device eval of a model that only fits sharded
        would OOM). Built lazily on first use; traced in eval mode
        (dropout off)."""
        if getattr(self, "_eval_jitted", None) is None:
            builder = getattr(self, "_eval_builder", None)
            if builder is None:
                raise NotImplementedError(
                    "this compiled program has no eval path")
            self._eval_jitted = builder()
        data = tuple(self._put_data(d) for d in data)
        return self._eval_jitted(self.params, self.state, data)

    def _fit_sharding(self, d):
        """This program's input sharding for one data arg; the spec is
        truncated to the array's rank (a [B] per-sample tensor under
        dp x sp sharding takes P('dp'))."""
        sh = self.data_sharding
        if isinstance(sh, NamedSharding) and len(sh.spec) > d.ndim:
            sh = NamedSharding(sh.mesh, P(*sh.spec[:d.ndim]))
        return sh

    def _is_placed(self, d):
        """True when d already went through put_batch (prefetch thread)
        or is a committed device array on this program's input sharding
        with no pending host-side preproc — the per-step preproc +
        device_put is skipped so prefetched batches cost the step loop
        nothing."""
        placed = getattr(self, "_placed", None)
        try:
            if placed is not None and d in placed:
                return True
        except TypeError:
            return False
        if getattr(self, "_data_preproc", None) is not None:
            # sharding equality can't prove the microbatch reshape ran;
            # only put_batch-registered arrays skip on this path
            return False
        if not isinstance(d, jax.Array):
            return False
        try:
            return d.committed and d.sharding == self._fit_sharding(d)
        except Exception:
            return False

    def put_batch(self, d):
        """Public placement hook (io.device_prefetch `place=`): preproc
        + shard one data arg onto this program's input sharding ahead of
        the step. Idempotent — an array that already went through here
        passes straight through in step()."""
        out = self._put_data(d)
        if isinstance(out, jax.Array):
            if getattr(self, "_placed", None) is None:
                import weakref
                self._placed = weakref.WeakSet()
            self._placed.add(out)
        return out

    def _put_data(self, d):
        """Shard one data arg. An optional _data_preproc (pipeline:
        host-side microbatch reshape) runs BEFORE device_put so the
        program never reshapes across sharded dims — that reshape forced
        the SPMD partitioner into replicate-then-repartition fallbacks."""
        if self._is_placed(d):
            return d
        d = jnp.asarray(d)
        pre = getattr(self, "_data_preproc", None)
        if pre is not None:
            d = pre(d)
        return jax.device_put(d, self._fit_sharding(d))

    def write_back(self):
        """Copy sharded params back into the Layer tree (host-gathered)."""
        lookup = dict(self.layer.named_parameters())
        lookup.update(dict(self.layer.named_buffers()))
        for k, v in {**self.params, **self.state}.items():
            if k in lookup:
                lookup[k]._data = jax.device_get(v)

    # -- sharded checkpoint (io/checkpoint.py) -----------------------------
    def save_checkpoint(self, path, step=0, meta=None):
        """Per-process shard files + PartitionSpec metadata; resumable on a
        different mesh shape (io/checkpoint.py)."""
        from ...io.checkpoint import save_checkpoint as _save
        _save(path, self.params, self.opt_state, self.state, step=step,
              meta=meta)

    def restore_checkpoint(self, path):
        """Restore params/opt state onto THIS program's shardings (the
        saved mesh shape may differ — shards re-tile)."""
        from ...io.checkpoint import load_checkpoint as _load
        sh = {"params": self.shardings["params"],
              "opt": self.shardings["opt"]}
        params, opt, state, step, meta = _load(path, mesh=self.mesh,
                                               shardings=sh)
        self.params = params
        if opt:     # a params-only checkpoint keeps the live slots
            self.opt_state = opt
        if state:
            self.state = state
        return step, meta


def _maybe_swap_optimizer(optimizer, strategy):
    """lars/lamb meta-optimizers: the reference rewrites momentum ->
    lars_momentum / adam -> lamb ops in the program
    (fleet/meta_optimizers/lars_optimizer.py, lamb_optimizer.py); here the
    toggle swaps the optimizer class, carrying over lr and parameters."""
    from ... import optimizer as opt_mod
    # carry grad_clip over; weight decay uses Lars/Lamb's own decoupled
    # lars_weight_decay / lamb_weight_decay defaults (the reference meta-
    # optimizers likewise source decay from their own configs)
    kw = dict(grad_clip=optimizer._grad_clip)
    if getattr(strategy, "lamb", False) and not isinstance(
            optimizer, opt_mod.Lamb):
        return opt_mod.Lamb(learning_rate=optimizer._learning_rate,
                            parameters=optimizer._parameter_list, **kw)
    if getattr(strategy, "lars", False) and not isinstance(
            optimizer, opt_mod.Lars):
        return opt_mod.Lars(learning_rate=optimizer._learning_rate,
                            parameters=optimizer._parameter_list, **kw)
    return optimizer


def _tp_specs(layer, params, strategy) -> Dict[str, P]:
    """Tensor-parallel specs via the model's `param_shardings` protocol
    (GPT implements it with its Megatron rules); replicated otherwise."""
    fn = getattr(layer, "param_shardings", None)
    if callable(fn):
        return fn(params, mesh_axis_tp="tp")
    return {k: P(*([None] * getattr(v, "ndim", 0)))
            for k, v in params.items()}


def _merge_specs(base: Dict[str, P], extra: Dict[str, P]) -> Dict[str, P]:
    """Combine TP specs with ZeRO specs: ZeRO claims a dimension the TP
    spec left unsharded; on conflict TP wins (matches Megatron+ZeRO
    practice: never double-shard one dim)."""
    out = {}
    for k, tp in base.items():
        z = extra.get(k)
        if z is None:
            out[k] = tp
            continue
        merged = []
        for i in range(len(tp)):
            t = tp[i] if i < len(tp) else None
            s = z[i] if i < len(z) else None
            merged.append(t if t is not None else s)
        out[k] = P(*merged)
    return out


def _scan_stacked_names(layer):
    """Fully-qualified names of params living in a ScanBlockStack: their
    dim 0 is the lax.scan xs axis (see sharding.shard_specs
    ``skip_leading``)."""
    walk = getattr(layer, "named_sublayers", None)
    if walk is None:        # facade layers (hapi adapters) without one
        return set()
    names = set()
    for pfx, sub in [("", layer)] + list(walk()):
        if getattr(sub, "_scan_stack", False):
            p = pfx + "." if pfx else ""
            names.update(p + rel for rel in sub._rels)
    return names


def _slot_shardings(mesh, opt_state, params, slot_specs):
    """Optimizer-slot shardings: a slot shaped like its parameter follows
    the parameter's spec; scalars (beta powers, steps) replicate."""
    return {n: {sl: (NamedSharding(mesh, slot_specs[n])
                     if tuple(getattr(v, "shape", ())) ==
                     tuple(params[n].shape)
                     else NamedSharding(mesh, P()))
                for sl, v in st.items()}
            for n, st in opt_state.items()}


def _put_opt_state(opt_state, s_sh):
    return {n: {sl: jax.device_put(v, s_sh[n][sl]) for sl, v in st.items()}
            for n, st in opt_state.items()}


def compile_train_step(layer, optimizer, strategy: DistributedStrategy,
                       loss_method: str = "loss", mesh=None,
                       lr_default: float = 1e-3) -> CompiledTrainStep:
    mesh = mesh or strategy.build_mesh()
    optimizer = _maybe_swap_optimizer(optimizer, strategy)
    if not getattr(strategy, "scan_layers", True):
        # escape hatch: trace scan-stacked models as an unrolled Python
        # loop over the stacked params (depth-linear HLO again)
        setter = getattr(layer, "set_scan_unroll", None)
        if setter is not None:
            setter(True)
    if hasattr(layer, "named_parameters"):
        # per-param ParamAttr regularizers, keyed for the functional path
        # (pipeline layouts rename params — those fall back to the
        # optimizer-wide weight_decay)
        optimizer.collect_param_regularizers(layer)
    if int(mesh.shape.get("pp", 1)) > 1:
        return _compile_pipeline_step(layer, optimizer, strategy, mesh)
    from .grad_comm import active_mode, compile_explicit_dp_step
    if active_mode(strategy):
        # localsgd / adaptive_localsgd / dgc / fp16_allreduce need manual
        # control of the dp gradient exchange (fleet/grad_comm.py)
        return compile_explicit_dp_step(layer, optimizer, strategy, mesh,
                                        loss_method=loss_method)
    wrapped = MethodAdapter(layer, loss_method) if loss_method else layer
    params = param_arrays(layer)
    state = state_arrays(layer)
    opt_state = optimizer.functional_init(params)

    amp_on = bool(strategy.amp)
    pure_bf16 = amp_on and strategy.amp_configs.use_pure_bf16
    recompute = bool(strategy.recompute)
    n_tp = int(mesh.shape.get("tp", 1))
    n_dp = int(mesh.shape.get("dp", 1))
    n_sp = int(mesh.shape.get("sp", 1))
    n_ep = int(mesh.shape.get("ep", 1))
    stage = strategy.sharding_stage()
    k_merge = (strategy.gradient_merge_configs.k_steps
               if strategy.gradient_merge else 1)

    # ---- parameter/state shardings ---------------------------------------
    tp_specs = _tp_specs(layer, params, strategy) \
        if (n_tp > 1 or n_ep > 1) else \
        {k: P(*([None] * getattr(v, "ndim", 0))) for k, v in params.items()}
    scan_stacked = _scan_stacked_names(layer)
    if stage >= 1:
        zspecs = zero_mod.shard_specs(params, "dp", n_dp,
                                      skip_leading=scan_stacked)
        pspecs = _merge_specs(tp_specs, zspecs if stage >= 3 else
                              {k: P(*([None] * getattr(v, "ndim", 0)))
                               for k, v in params.items()})
        slot_specs = _merge_specs(tp_specs, zspecs)
    else:
        pspecs = tp_specs
        slot_specs = tp_specs

    p_sh = {k: NamedSharding(mesh, pspecs[k]) for k in params}
    s_sh = _slot_shardings(mesh, opt_state, params, slot_specs)
    buf_sh = {k: NamedSharding(mesh, P(*([None] * getattr(v, "ndim", 0))))
              for k, v in state.items()}
    # batch over dp; with sequence parallel the seq dim rides 'sp' too
    data_sh = NamedSharding(mesh, P("dp", "sp") if n_sp > 1 else P("dp"))

    # ---- the traced step -------------------------------------------------
    def _run_contexts():
        """One source of truth for the amp + attention-mesh scopes the
        train AND eval traces run under. With sp > 1 attention is the
        shard_map-inner ring/Ulysses; otherwise, on a multi-device mesh,
        the flash kernel runs shard_map-inner on its dp/tp shard (GSPMD
        cannot partition a Mosaic call)."""
        import contextlib

        from ... import amp as amp_mod
        from ...nn.functional.attention import (flash_mesh_scope,
                                                seq_parallel_scope)
        batch_axis = "dp" if n_dp > 1 else None
        head_axis = "tp" if n_tp > 1 else None
        if n_sp > 1:
            attn_ctx = seq_parallel_scope(
                mesh, "sp", impl=strategy.sequence_parallel_impl,
                batch_axis=batch_axis, head_axis=head_axis)
        elif mesh.size > 1:
            attn_ctx = flash_mesh_scope(mesh, batch_axis, head_axis)
        else:
            attn_ctx = contextlib.nullcontext()
        amp_ctx = amp_mod.auto_cast(enable=amp_on,
                                    level="O2" if pure_bf16 else "O1",
                                    dtype="bfloat16")
        return attn_ctx, amp_ctx

    def forward_loss(p, st, key, *data):
        attn_ctx, amp_ctx = _run_contexts()
        with random_mod.key_scope(key):
            with amp_ctx:
                with attn_ctx:
                    out, new_state = functional_call(wrapped, p, st, *data)
        return out, new_state

    if recompute:
        # reference RecomputeOptimizer/backward.py:725; on TPU this is
        # jax.checkpoint — recompute activations in backward instead of
        # storing them (SURVEY.md §8.4). Models exposing the per-block
        # protocol get block-scoped checkpoints (peak memory = ONE
        # block's activations); a whole-forward checkpoint is the
        # fallback and only trades compute, not peak memory.
        policy = getattr(jax.checkpoint_policies,
                         strategy.recompute_configs.policy, None)
        if hasattr(layer, "enable_block_recompute"):
            # set/restore AROUND the traced forward only — a persistent
            # flag would leak block remat into later compiles of the
            # same layer and into eager jax.grad use
            _inner_fl = forward_loss

            def forward_loss(p, st, key, *data):
                prev = getattr(layer, "_recompute_blocks", False)
                prev_pol = getattr(layer, "_recompute_policy", None)
                layer.enable_block_recompute(True, policy=policy)
                try:
                    return _inner_fl(p, st, key, *data)
                finally:
                    layer._recompute_blocks = prev
                    layer._recompute_policy = prev_pol
        else:
            forward_loss = jax.checkpoint(
                forward_loss, policy=policy, static_argnums=())

    def train_step(p, st, opt_st, key, lr, data):
        if k_merge > 1:
            # gradient merge: split the batch into k microbatches and
            # accumulate grads in a scan (GradientMergeOptimizer analog)
            def micro(carry, mb):
                acc, st_c, i = carry
                def loss_of(pp):
                    out, new_st = forward_loss(pp, st_c,
                                               jax.random.fold_in(key, i),
                                               *mb)
                    return out, new_st
                (loss, new_st), g = jax.value_and_grad(
                    loss_of, has_aux=True)(p)
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
                return (acc, new_st, i + 1), loss

            micro_data = [d.reshape((k_merge, d.shape[0] // k_merge)
                                    + d.shape[1:]) for d in data]
            zero = jax.tree_util.tree_map(jnp.zeros_like, p)
            (grads, new_state, _), losses = jax.lax.scan(
                micro, (zero, st, 0), tuple(micro_data))
            if strategy.gradient_merge_configs.avg:
                grads = jax.tree_util.tree_map(lambda g: g / k_merge, grads)
            loss = losses.mean()
        else:
            def loss_of(pp):
                out, new_st = forward_loss(pp, st, key, *data)
                return out, new_st
            (loss, new_state), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p)
        grads = nan_inf.guard_tree(grads)   # FLAGS_check_nan_inf, jit path
        if scan_stacked and stage >= 1 and n_dp > 1:
            # pin scan-stacked grads replicated: letting the dp-sharded
            # Adam slots propagate a partition into the scan-transpose's
            # dynamic_update_slice accumulator miscompiles in XLA:CPU
            # (heap corruption) — reshard at the update instead
            grads = {k: (jax.lax.with_sharding_constraint(
                             v, NamedSharding(mesh,
                                              P(*([None] * v.ndim))))
                         if k in scan_stacked else v)
                     for k, v in grads.items()}
        new_p, new_opt = optimizer.functional_update(p, grads, opt_st, lr=lr)
        return loss, new_p, new_state, new_opt

    jitted = jax.jit(
        train_step,
        # data is a tuple pytree; a single sharding broadcasts to all leaves
        in_shardings=(p_sh, buf_sh, s_sh, None, None, None),
        out_shardings=(NamedSharding(mesh, P()), p_sh, buf_sh, s_sh),
        donate_argnums=(0, 2))

    # true copy on params only (donated argnum 0): an aliasing placement
    # would leave the program's donated buffers sharing storage with the
    # layer's own arrays, so the user's Tensors die after step 1 — and
    # device_put(may_alias=False) still aliases on this jax build's CPU
    # backend. state (argnum 1) is never donated.
    params = {k: unaliased_put(v, p_sh[k]) for k, v in params.items()}
    state = jax.device_put(state, buf_sh)
    opt_state = _put_opt_state(opt_state, s_sh)

    prog = CompiledTrainStep(jitted, params, state, opt_state,
                             {"params": p_sh, "opt": s_sh}, mesh, layer,
                             data_sh)
    prog._opt = optimizer

    def _eval_builder():
        # when the layer exposes loss_and_outs (hapi's adapter does),
        # the sharded eval also returns the forward outputs so Metric
        # states accumulate WITHOUT gathering params — only the batch's
        # outputs cross to host (reference hapi/model.py:810 runs
        # metrics uniformly through prepare/fit/evaluate)
        has_outs = getattr(layer, "loss_and_outs", None) is not None
        wrapped_eval = (MethodAdapter(layer, "loss_and_outs") if has_outs
                        else None)

        def eval_fn(p, st, data):
            # fixed key: eval-mode layers draw no dropout, and any
            # stray randomness must at least be deterministic
            if has_outs:
                attn_ctx, amp_ctx = _run_contexts()
                with random_mod.key_scope(jax.random.key(0)):
                    with amp_ctx:
                        with attn_ctx:
                            (loss, outs), _ = functional_call(
                                wrapped_eval, p, st, *data)
                return loss, outs
            out, _ = forward_loss(p, st, jax.random.key(0), *data)
            return out

        out_sh = ((NamedSharding(mesh, P()), None) if has_outs
                  else NamedSharding(mesh, P()))
        ejit = jax.jit(eval_fn, in_shardings=(p_sh, buf_sh, None),
                       out_shardings=out_sh)

        def runner(p, st, data):
            # trace under eval mode (dropout off, BN uses running stats)
            was = bool(getattr(layer, "training", False))
            if hasattr(layer, "eval"):
                layer.eval()
            try:
                return ejit(p, st, data)
            finally:
                if was and hasattr(layer, "train"):
                    layer.train()

        return runner

    prog._eval_builder = _eval_builder
    prog._eval_batch_divisor = max(n_dp, 1)
    prog._eval_returns_outs = (getattr(layer, "loss_and_outs", None)
                               is not None)
    return prog


# ---------------------------------------------------------------------------
# pipeline-parallel step (strategy.pipeline / pp_degree > 1)
# ---------------------------------------------------------------------------

def _claim_free_dim(spec, shape, axis, n):
    """Spec with `axis` claimed on the first unsharded dim divisible by n
    (unchanged if none qualifies) — the ZeRO slot-sharding rule."""
    dims = list(spec) + [None] * (len(shape) - len(spec))
    for i, (s, d) in enumerate(zip(dims, shape)):
        if s is None and d % n == 0 and d >= n:
            dims[i] = axis
            return P(*dims)
    return spec


def _check_pipeline_compat(strategy, mesh, what="pipeline",
                           allow_sp=False, allow_ep=False):
    if strategy.sharding and strategy.sharding_stage() >= 3:
        raise NotImplementedError(
            f"{what} + ZeRO-3 is not supported: stage-3 param sharding "
            "conflicts with the pipeline's stacked-over-'pp' param layout "
            "— use sharding stage 1/2 (optimizer-state sharding over dp)")
    if strategy.sharding and int(mesh.shape.get("dp", 1)) < 2:
        raise ValueError(f"{what} + sharding needs dp >= 2 in the mesh")
    if strategy.gradient_merge and strategy.gradient_merge_configs.k_steps > 1:
        raise NotImplementedError(
            f"{what} already microbatches via "
            "pipeline_configs.accumulate_steps; gradient_merge on top is "
            "not supported — fold k_steps into accumulate_steps")
    if int(mesh.shape.get("sp", 1)) > 1 and not allow_sp:
        raise NotImplementedError(
            f"{what} + sequence parallel needs the layer's "
            "pipeline_block_fn_sp protocol (models/gpt.py provides it)")
    if int(mesh.shape.get("ep", 1)) > 1 and not allow_ep:
        raise NotImplementedError(
            f"{what} + expert parallel needs the layer's "
            "pipeline_block_fn_ep protocol (models/gpt.py provides it "
            "for MoE configs)")


def _build_pipeline_program(layer, optimizer, strategy, mesh, *, block_fn,
                            embed_fn, head_loss_fn, ep, hp, stacked,
                            n_layers, stacked_pspec, prog_cls,
                            seq_axis=None, replicated_axes=(),
                            aux_from_blocks=False, aux_coef=0.01):
    """The machinery both pipeline branches share: flat param assembly
    (embed.* / head.* / stacked.*), shardings, the microbatched
    global-masked-mean loss, jit wiring and program construction. The
    branches differ only in how the stacked block params are laid out and
    what block_fn runs inside the pipeline shard_map."""
    from ..pipeline import pipeline_value_and_grad

    n_pp = int(mesh.shape["pp"])
    n_dp = int(mesh.shape.get("dp", 1))
    n_micro = max(int(strategy.pipeline_configs.accumulate_steps), 1)
    amp_on = bool(strategy.amp)
    pure_bf16 = amp_on and strategy.amp_configs.use_pure_bf16

    if strategy.recompute:
        policy = getattr(jax.checkpoint_policies,
                         strategy.recompute_configs.policy, None)
        block_fn = jax.checkpoint(block_fn, policy=policy)

    state = state_arrays(layer)
    flat = {}
    flat.update({f"embed.{k}": v for k, v in ep.items()})
    flat.update({f"head.{k}": v for k, v in hp.items()})
    flat.update({f"stacked.{k}": v for k, v in stacked.items()})
    opt_state = optimizer.functional_init(flat)

    def _pspec(k, v):
        if k.startswith("stacked."):
            return stacked_pspec(k[len("stacked."):], v)
        return P(*([None] * v.ndim))

    pspecs = {k: _pspec(k, v) for k, v in flat.items()}
    p_sh = {k: NamedSharding(mesh, pspecs[k]) for k in flat}
    # pipeline + ZeRO-1/2: optimizer slots additionally shard over 'dp'
    # on the first free, divisible dim (params keep the pipeline layout;
    # XLA re-tiles grads at the update boundary — the reduce-scatter)
    if strategy.sharding and strategy.sharding_stage() >= 1 and n_dp > 1:
        slot_specs = {k: _claim_free_dim(pspecs[k], flat[k].shape, "dp",
                                         n_dp)
                      for k in flat}
    else:
        slot_specs = pspecs
    s_sh = _slot_shardings(mesh, opt_state, flat, slot_specs)
    buf_sh = {k: NamedSharding(mesh, P(*([None] * getattr(v, "ndim", 0))))
              for k, v in state.items()}
    # data arrives pre-microbatched ([n_micro, mb, T] via _data_preproc),
    # so the spec leads with the unsharded micro dim
    data_sh = NamedSharding(
        mesh, P(None, "dp" if n_dp > 1 else None, seq_axis))

    # shard_map in_specs derive from the SAME pspecs the jit in_shardings
    # use — one source of truth for the stacked layout. Training runs the
    # true-1F1B fused fwd+bwd scheduler (O(n_stages) activation memory —
    # section_worker.cc:128-165's profile); jax.grad over the forward
    # scheduler would store residuals for all n_micro microbatches.
    import inspect as _inspect

    def _takes(fn_, name):
        try:
            return name in _inspect.signature(fn_).parameters
        except (TypeError, ValueError):
            return False

    schedule = getattr(strategy.pipeline_configs, "schedule_mode", "1F1B")
    if schedule not in ("1F1B", "F-then-B"):
        raise ValueError(
            f"pipeline_configs.schedule_mode must be '1F1B' or "
            f"'F-then-B', got {schedule!r} (reference "
            f"distributed_strategy.proto schedule_mode)")

    pipe_vag = pipeline_value_and_grad(
        block_fn, embed_fn, head_loss_fn, n_pp, n_micro, mesh, axis="pp",
        batch_axis="dp" if n_dp > 1 else None,
        param_specs={k[len("stacked."):]: v for k, v in pspecs.items()
                     if k.startswith("stacked.")},
        seq_axis=seq_axis,
        block_takes_key=_takes(block_fn, "key"),
        embed_takes_key=_takes(embed_fn, "key"),
        replicated_axes=replicated_axes,
        aux_from_blocks=aux_from_blocks, aux_coef=aux_coef)

    # F-then-B (stored residuals): jax.grad over the forward scheduler —
    # residuals for all n_micro microbatches stay live (GPipe memory
    # profile) but the backward re-executes NOTHING, the reference's
    # no-recompute SectionWorker profile (section_worker.cc:128-165).
    # 1F1B (default) re-linearizes per backward slot: O(n_stages)
    # activation memory at a ~1.3x forward-FLOPs tax.
    from ..pipeline import pipeline_spmd as _pipe_fwd_builder
    pipe_fwd = _pipe_fwd_builder(
        block_fn, n_pp, n_micro, mesh, axis="pp",
        batch_axis="dp" if n_dp > 1 else None,
        param_specs={k[len("stacked."):]: v for k, v in pspecs.items()
                     if k.startswith("stacked.")},
        seq_axis=seq_axis, aux_from_blocks=aux_from_blocks)
    embed_takes_key = _takes(embed_fn, "key")
    block_takes_key = _takes(block_fn, "key")

    def _sub(p, prefix):
        cut = len(prefix)
        return {k[cut:]: v for k, v in p.items() if k.startswith(prefix)}

    def _fthenb_loss(p, ids, labels, key):
        epp = _sub(p, "embed.")
        hpp = _sub(p, "head.")
        spp = _sub(p, "stacked.")
        n_local = n_layers // n_pp
        batch_axis = "dp" if n_dp > 1 else None

        if embed_takes_key and key is not None:
            # embed dropout must draw per-(data-shard, microbatch) masks
            # with the SAME fold order as the 1F1B scheduler
            # (data ranks -> microbatch -> embed tag) so the two
            # schedule modes are mask-identical
            def emb_sm(ep_, ids_, k_):
                from ..pipeline import embed_key_tag, fold_data_axes
                k_ = fold_data_axes(k_, batch_axis, seq_axis)
                t_loc = ids_.shape[-1]
                pos_off = (jax.lax.axis_index(seq_axis) * t_loc
                           if seq_axis is not None else 0)

                def one(ids_m, m):
                    k_m = jax.random.fold_in(k_, m)
                    kw = {"key": embed_key_tag(k_m, n_local * n_pp)}
                    if seq_axis is not None:
                        kw["pos_offset"] = pos_off
                    return embed_fn(ep_, ids_m, **kw)
                return jax.vmap(one)(ids_, jnp.arange(n_micro))
            rep = jax.tree_util.tree_map(
                lambda v: P(*([None] * v.ndim)), epp)
            hspec = P(None, batch_axis, seq_axis, None)
            h = jax.shard_map(
                emb_sm, mesh=mesh,
                in_specs=(rep, P(None, batch_axis, seq_axis), P()),
                out_specs=hspec, check_vma=False)(epp, ids, key)
        else:
            h = jax.vmap(lambda i_: embed_fn(epp, i_))(ids)
        out = pipe_fwd(spp, h, key if block_takes_key else None)
        if aux_from_blocks:
            h_out, aux_s = out
        else:
            h_out, aux_s = out, 0.0
        sums, counts = jax.vmap(
            head_loss_fn, in_axes=(None, None, 0, 0))(hpp, epp, h_out,
                                                      labels)
        loss = sums.sum() / jnp.maximum(counts.sum(), 1.0)
        if aux_from_blocks:
            loss = loss + aux_coef * aux_s / (n_layers * n_micro)
        return loss

    def train_step_fthenb(p, st, opt_st, key, lr, data):
        ids, labels = data
        from ... import amp as amp_mod
        with random_mod.key_scope(key):
            with amp_mod.auto_cast(enable=amp_on,
                                   level="O2" if pure_bf16 else "O1",
                                   dtype="bfloat16"):
                loss, grads = jax.value_and_grad(
                    lambda pp: _fthenb_loss(pp, ids, labels, key))(p)
        grads = nan_inf.guard_tree(grads)
        new_p, new_opt = optimizer.functional_update(p, grads, opt_st,
                                                     lr=lr)
        return loss, new_p, st, new_opt

    def train_step(p, st, opt_st, key, lr, data):
        ids, labels = data
        from ... import amp as amp_mod
        with random_mod.key_scope(key):
            with amp_mod.auto_cast(enable=amp_on,
                                   level="O2" if pure_bf16 else "O1",
                                   dtype="bfloat16"):
                epp = _sub(p, "embed.")
                hpp = _sub(p, "head.")
                spp = _sub(p, "stacked.")
                out = pipe_vag(spp, epp, hpp, ids, labels, key)
                if aux_from_blocks:
                    sums, counts, d_sp, d_ep, d_hp, aux_s = out
                else:
                    sums, counts, d_sp, d_ep, d_hp = out
        # global masked mean across all microbatches: grads came back as
        # grads of loss_SUM; the valid-count denominator is
        # label-determined (param-independent), so scaling is exact
        denom = jnp.maximum(counts, 1.0)
        loss = sums / denom
        if aux_from_blocks:
            # the scheduler pre-scaled the aux grad seed by denom, so
            # the /denom below lands both terms at this exact loss
            loss = loss + aux_coef * aux_s / (n_layers * n_micro)
        grads = {}
        grads.update({f"embed.{k}": v / denom for k, v in d_ep.items()})
        grads.update({f"head.{k}": v / denom for k, v in d_hp.items()})
        grads.update({f"stacked.{k}": v / denom for k, v in d_sp.items()})
        grads = nan_inf.guard_tree(grads)   # FLAGS_check_nan_inf, jit path
        new_p, new_opt = optimizer.functional_update(p, grads, opt_st, lr=lr)
        return loss, new_p, st, new_opt

    jitted = jax.jit(
        train_step_fthenb if schedule == "F-then-B" else train_step,
        in_shardings=(p_sh, buf_sh, s_sh, None, None, None),
        out_shardings=(NamedSharding(mesh, P()), p_sh, buf_sh, s_sh),
        donate_argnums=(0, 2))

    # true copy on the donated params only (see compile_train_step)
    flat = {k: unaliased_put(v, p_sh[k]) for k, v in flat.items()}
    state = jax.device_put(state, buf_sh)
    opt_state = _put_opt_state(opt_state, s_sh)

    prog = prog_cls(jitted, flat, state, opt_state,
                    {"params": p_sh, "opt": s_sh}, mesh, layer, data_sh)
    prog._opt = optimizer
    prog._n_layers = n_layers
    prog._step_label = "fleet.pipeline_step"

    def _microbatch(d):
        if d.shape[0] % n_micro:
            raise ValueError(
                f"pipeline batch {d.shape[0]} not divisible by "
                f"accumulate_steps {n_micro}")
        return d.reshape((n_micro, d.shape[0] // n_micro) + d.shape[1:])
    prog._data_preproc = _microbatch

    def _eval_builder():
        from ..pipeline import pipeline_spmd

        # forward-only pipeline: the GPipe-shaped residuals of
        # pipeline_spmd don't matter without a backward, and eval mode
        # draws no dropout so the blocks need no keys. MoE blocks keep
        # their aux so eval loss matches the train step's definition.
        pipe = pipeline_spmd(
            block_fn, n_pp, n_micro, mesh, axis="pp",
            batch_axis="dp" if n_dp > 1 else None,
            param_specs={k[len("stacked."):]: v
                         for k, v in pspecs.items()
                         if k.startswith("stacked.")},
            seq_axis=seq_axis, aux_from_blocks=aux_from_blocks)

        def eval_fn(p, st, data):
            ids, labels = data
            from ... import amp as amp_mod
            with amp_mod.auto_cast(enable=amp_on,
                                   level="O2" if pure_bf16 else "O1",
                                   dtype="bfloat16"):
                epp = _sub(p, "embed.")
                hpp = _sub(p, "head.")
                spp = _sub(p, "stacked.")
                h = jax.vmap(embed_fn, in_axes=(None, 0))(epp, ids)
                out = pipe(spp, h)
                h, aux_s = out if aux_from_blocks else (out, 0.0)
                sums, counts = jax.vmap(
                    head_loss_fn, in_axes=(None, None, 0, 0))(
                    hpp, epp, h, labels)
            loss = sums.sum() / jnp.maximum(counts.sum(), 1.0)
            if aux_from_blocks:
                loss = loss + aux_coef * aux_s / (n_layers * n_micro)
            return loss

        ejit = jax.jit(eval_fn, in_shardings=(p_sh, buf_sh, None),
                       out_shardings=NamedSharding(mesh, P()))

        def runner(p, st, data):
            was = bool(getattr(layer, "training", False))
            if hasattr(layer, "eval"):
                layer.eval()
            try:
                return ejit(p, st, data)
            finally:
                if was and hasattr(layer, "train"):
                    layer.train()

        return runner

    prog._eval_builder = _eval_builder
    # batch divisibility the sharded eval requires (partial final
    # batches fall back to the caller's synced path)
    prog._eval_batch_divisor = n_micro * max(n_dp, 1)
    return prog


def _compile_pipeline_step(layer, optimizer, strategy, mesh):
    """PP branch of the strategy compiler.

    Reference: PipelineOptimizer splits the Program into per-stage sections
    executed by SectionWorker 1F1B loops (optimizer.py:3718,
    section_worker.cc:98-165). TPU-native: the layer supplies an
    (embed, blocks, head) decomposition; homogeneous blocks are stacked on
    a leading layer axis sharded over 'pp' and driven by the SPMD schedule
    in distributed/pipeline.py (ppermute ring inside one jitted scan).
    Composes with dp (microbatch dim sharded over 'dp'), tp (the manual-tp
    branch below), recompute (jax.checkpoint per block) and AMP (autocast
    inside the traced blocks). Microbatches = accumulate_steps.
    """
    from ..pipeline import stack_stage_params

    n_tp = int(mesh.shape.get("tp", 1))
    n_sp = int(mesh.shape.get("sp", 1))
    if n_tp > 1:
        return _compile_pipeline_tp_step(layer, optimizer, strategy, mesh,
                                         n_tp, n_sp=n_sp)
    n_ep = int(mesh.shape.get("ep", 1))
    sp_block = getattr(layer, "pipeline_block_fn_sp", None)
    ep_block = getattr(layer, "pipeline_block_fn_ep", None)
    _check_pipeline_compat(strategy, mesh,
                           allow_sp=callable(sp_block),
                           allow_ep=callable(ep_block))
    split = getattr(layer, "pipeline_split_params", None)
    fns = getattr(layer, "pipeline_fns", None)
    if not (callable(split) and callable(fns)):
        raise TypeError(
            "pipeline=True requires the layer to implement "
            "pipeline_split_params(params) and pipeline_fns() "
            "(see models/gpt.py for the protocol)")

    params = param_arrays(layer)
    ep, blocks_list, hp = split(params)
    n_pp = int(mesh.shape["pp"])
    if len(blocks_list) % n_pp:
        raise ValueError(f"{len(blocks_list)} blocks not divisible by "
                         f"pp={n_pp}")
    embed_fn, block_fn, head_loss_fn = fns()
    if n_ep > 1:
        # pp x ep: activations replicate over 'ep'; each member runs its
        # local expert slab and one psum sums contributions (manual form
        # of the GSPMD einsum dispatch). Stacked expert banks shard their
        # E dim over 'ep' via the layer's block_ep_specs.
        experts = getattr(getattr(layer, "cfg", None), "moe_experts", None)
        if experts is not None and experts % n_ep:
            raise ValueError(f"{experts} experts not divisible by "
                             f"ep={n_ep}")
        # Switch load-balance aux rides the 1F1B backward slot (blocks
        # return (h, aux)); routing IS regularized on this path. With
        # sp > 1 the block additionally runs ring/Ulysses attention over
        # the sequence shards (pp x sp x ep — formerly refused)
        ep_kw = {}
        if n_sp > 1:
            heads_ep = getattr(getattr(layer, "cfg", None), "heads", None)
            if (strategy.sequence_parallel_impl == "ulysses"
                    and heads_ep is not None and heads_ep % n_sp):
                raise ValueError(
                    f"pipeline + ep + ulysses: {heads_ep} attention heads "
                    f"not divisible by sp={n_sp} (use impl='ring' or "
                    f"adjust sep_degree)")
            ep_kw = {"axis_sp": "sp",
                     "impl": strategy.sequence_parallel_impl}
        block_fn = ep_block(
            axis_ep="ep",
            compute_dtype="bfloat16" if strategy.amp else None,
            with_aux=True, **ep_kw)
        ep_specs = layer.block_ep_specs(axis_pp="pp", axis_ep="ep")

        def ep_pspec(rel, v):
            spec = ep_specs.get(rel)
            if spec is None:
                raise KeyError(f"block_ep_specs missing {rel!r}")
            return spec

        return _build_pipeline_program(
            layer, optimizer, strategy, mesh, block_fn=block_fn,
            embed_fn=embed_fn, head_loss_fn=head_loss_fn, ep=ep, hp=hp,
            stacked=stack_stage_params(blocks_list),
            n_layers=len(blocks_list), stacked_pspec=ep_pspec,
            prog_cls=_PipelineTrainStep, replicated_axes=("ep",),
            seq_axis="sp" if n_sp > 1 else None,
            aux_from_blocks=True,
            aux_coef=float(getattr(getattr(layer, "cfg", None),
                                   "moe_aux_coef", 0.01)))
    if n_sp > 1:
        # pp x sp: blocks see local sequence shards; attention is the
        # shard_map-inner ring/Ulysses (the sp collectives live in the
        # block, the pipeline just also shards the data's seq dim)
        heads = getattr(getattr(layer, "cfg", None), "heads", None)
        if (strategy.sequence_parallel_impl == "ulysses"
                and heads is not None and heads % n_sp):
            raise ValueError(
                f"pipeline + ulysses: {heads} attention heads not "
                f"divisible by sp={n_sp} (use impl='ring' or adjust "
                f"sep_degree)")
        sp_is_moe = bool(getattr(getattr(layer, "cfg", None),
                                 "moe_experts", 0))
        block_fn = sp_block(
            axis_sp="sp", impl=strategy.sequence_parallel_impl,
            compute_dtype="bfloat16" if strategy.amp else None,
            with_aux=sp_is_moe)
    return _build_pipeline_program(
        layer, optimizer, strategy, mesh, block_fn=block_fn,
        embed_fn=embed_fn, head_loss_fn=head_loss_fn, ep=ep, hp=hp,
        stacked=stack_stage_params(blocks_list),
        n_layers=len(blocks_list),
        stacked_pspec=lambda rel, v: P("pp", *([None] * (v.ndim - 1))),
        prog_cls=_PipelineTrainStep,
        seq_axis="sp" if n_sp > 1 else None,
        # plain-branch MoE blocks emit (h, aux) via collect_aux_losses;
        # the sp branch's raw-jnp MoE block threads its aux explicitly
        aux_from_blocks=bool(
            getattr(getattr(layer, "cfg", None), "moe_experts", 0)
            if n_sp > 1
            else getattr(layer, "pipeline_block_emits_aux", False)),
        aux_coef=float(getattr(getattr(layer, "cfg", None),
                               "moe_aux_coef", 0.01)))


def _compile_pipeline_tp_step(layer, optimizer, strategy, mesh, n_tp,
                              n_sp=1):
    """pp x tp (x sp) (x dp) branch: the pipeline shard_map keeps every
    mesh axis manual, so the block function is the layer's hand-written
    Megatron block (models/gpt.py pipeline_block_fn_tp: split qkv head
    groups, explicit psums over 'tp') and the stacked block params are
    physically sharded with the layer's block_tp_specs. With sp > 1 the
    block is pipeline_block_fn_tp_sp — ring/Ulysses attention over 'sp'
    on the local tp head group — and the data's sequence dim shards over
    'sp' (the v5p-64 long-context mesh). Reference analog: a program
    pass emitting c_allreduce inside each pipeline section."""
    from ..pipeline import stack_stage_params

    need_fns = ["split_block_params_tp", "block_tp_specs",
                "pipeline_split_params", "pipeline_fns",
                "pipeline_block_fn_tp_sp" if n_sp > 1
                else "pipeline_block_fn_tp"]
    for need in need_fns:
        if not callable(getattr(layer, need, None)):
            raise TypeError(
                f"pipeline + tensor_parallel{' + sequence_parallel' if n_sp > 1 else ''} "
                f"requires the layer to implement {need} "
                f"(see models/gpt.py)")
    _check_pipeline_compat(strategy, mesh,
                           what="pipeline+tp" + ("+sp" if n_sp > 1
                                                 else ""),
                           allow_sp=n_sp > 1)
    heads = getattr(getattr(layer, "cfg", None), "heads", None)
    if heads is not None and heads % n_tp:
        raise ValueError(f"{heads} attention heads not divisible by "
                         f"tp={n_tp}")
    if (n_sp > 1 and strategy.sequence_parallel_impl == "ulysses"
            and heads is not None and (heads // n_tp) % n_sp):
        raise ValueError(
            f"pipeline + tp + ulysses: local head count "
            f"{heads // n_tp} (= {heads} heads / tp={n_tp}) not "
            f"divisible by sp={n_sp} (use impl='ring' or adjust "
            f"degrees)")

    params = param_arrays(layer)
    ep, blocks_list, hp = layer.pipeline_split_params(params)
    n_pp = int(mesh.shape["pp"])
    if len(blocks_list) % n_pp:
        raise ValueError(f"{len(blocks_list)} blocks not divisible by "
                         f"pp={n_pp}")
    embed_fn, _, head_loss_fn = layer.pipeline_fns()
    tp_is_moe = bool(getattr(getattr(layer, "cfg", None),
                             "moe_experts", 0))
    if tp_is_moe:
        # expert hidden dims shard over tp (block_tp_specs moe.* rows)
        ffn_hidden = int(getattr(layer.cfg, "ffn_mult", 4)) * \
            int(getattr(layer.cfg, "hidden"))
        if ffn_hidden % n_tp:
            raise ValueError(f"MoE expert hidden {ffn_hidden} not "
                             f"divisible by tp={n_tp}")
    # raw-jnp block ops bypass the autocast dispatcher hook, so AMP is
    # delivered as an explicit compute dtype
    if n_sp > 1:
        block_fn = layer.pipeline_block_fn_tp_sp(
            axis_tp="tp", axis_sp="sp",
            impl=strategy.sequence_parallel_impl,
            compute_dtype="bfloat16" if strategy.amp else None,
            with_aux=tp_is_moe)
    else:
        block_fn = layer.pipeline_block_fn_tp(
            axis_tp="tp",
            compute_dtype="bfloat16" if strategy.amp else None,
            with_aux=tp_is_moe)
    split_blocks = [layer.split_block_params_tp(b) for b in blocks_list]
    tp_specs = layer.block_tp_specs(axis_pp="pp", axis_tp="tp")

    def stacked_pspec(rel, v):
        spec = tp_specs.get(rel)
        if spec is None:
            raise KeyError(f"block_tp_specs missing {rel!r}")
        return spec

    return _build_pipeline_program(
        layer, optimizer, strategy, mesh, block_fn=block_fn,
        embed_fn=embed_fn, head_loss_fn=head_loss_fn, ep=ep, hp=hp,
        stacked=stack_stage_params(split_blocks),
        n_layers=len(blocks_list), stacked_pspec=stacked_pspec,
        prog_cls=_PipelineTpTrainStep, replicated_axes=("tp",),
        seq_axis="sp" if n_sp > 1 else None,
        aux_from_blocks=tp_is_moe,
        aux_coef=float(getattr(getattr(layer, "cfg", None),
                               "moe_aux_coef", 0.01)))



class _PipelineTrainStep(CompiledTrainStep):
    """CompiledTrainStep whose param dict uses the pipeline layout
    (embed.* / head.* / stacked.*[L, ...]); write_back unstacks."""

    def write_back(self):
        lookup = dict(self.layer.named_parameters())
        lookup.update(dict(self.layer.named_buffers()))
        stacked = {}
        for k, v in self.params.items():
            if k.startswith("embed.") or k.startswith("head."):
                name = k.split(".", 1)[1]
                if name in lookup:
                    lookup[name]._data = jax.device_get(v)
            elif k.startswith("stacked."):
                stacked[k[len("stacked."):]] = jax.device_get(v)
        self._write_back_stacked(lookup, stacked)
        for k, v in self.state.items():
            if k in lookup:
                lookup[k]._data = jax.device_get(v)

    def _write_back_stacked(self, lookup, stacked):
        for rel, arr in stacked.items():
            name = "blocks." + rel
            if name in lookup and \
                    tuple(lookup[name]._data.shape) == tuple(arr.shape):
                # scan layout: the layer itself holds the [L, ...] stack
                lookup[name]._data = arr
                continue
            for i in range(self._n_layers):
                name = f"blocks.{i}.{rel}"
                if name in lookup:
                    lookup[name]._data = arr[i]


class _PipelineTpTrainStep(_PipelineTrainStep):
    """Pipeline layout with manual-tp split blocks: write_back merges the
    split q/k/v back into the packed qkv params (layer protocol
    merge_block_params_tp)."""

    def _write_back_stacked(self, lookup, stacked):
        scan_rows = {}          # scan layout: collect rows, stack once
        for i in range(self._n_layers):
            split_i = {rel: arr[i] for rel, arr in stacked.items()}
            for rel, arr in self.layer.merge_block_params_tp(
                    split_i).items():
                name = f"blocks.{i}.{rel}"
                if name in lookup:
                    lookup[name]._data = arr
                else:
                    scan_rows.setdefault(rel, []).append(arr)
        for rel, rows in scan_rows.items():
            name = "blocks." + rel
            if name in lookup and len(rows) == self._n_layers:
                lookup[name]._data = np.stack(
                    [np.asarray(r) for r in rows])
