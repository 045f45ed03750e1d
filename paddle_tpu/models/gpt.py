"""GPT decoder language-model family, TPU-first.

Capability analog of the reference's transformer stack
(/root/reference/python/paddle/nn/layer/transformer.py:115 MultiHeadAttention,
:437 TransformerEncoderLayer) arranged as a pre-LN causal LM (the reference
ships no GPT model class; its GPT-class benchmark configs are external — we
provide the architecture natively since BASELINE.md configs 4-5 are GPT-2
345M / GPT-3 1.3B).

TPU-first design decisions:
  * weights are [in, out] so the hot matmuls are plain `x @ w` on the MXU —
    no transposes in the step function;
  * attention uses F.scaled_dot_product_attention which lowers to the Pallas
    flash kernel on TPU and an XLA composition elsewhere;
  * `gpt_param_shardings` gives the Megatron-style tensor-parallel
    PartitionSpec for every parameter, so `jit(..., in_shardings=...)` over a
    ('dp','tp') mesh runs the model tensor-parallel with XLA inserting the
    all-reduces (the reference reaches multi-device only via graph rewrite
    passes — ir/multi_devices_graph_pass — which XLA subsumes here).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .. import nn
from .. import ops as F_ops
from ..core.tensor import Tensor
from ..nn import functional as F


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded to a multiple of 128 (MXU lane width)
    max_seq_len: int = 1024
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    ffn_mult: int = 4
    dropout: float = 0.0
    dtype: str = "float32"
    moe_experts: int = 0         # >0: MoE FFN with this many experts
    moe_top_k: int = 2
    moe_aux_coef: float = 0.01   # Switch load-balance pressure
    # scan-over-layers: stack block params on a leading [layers] axis and
    # run the stack as one jax.lax.scan step, making HLO size and XLA
    # compile time (near-)invariant in depth. None = auto: on unless MoE
    # (aux losses cannot escape a scan body). False forces the unrolled
    # Python loop (per-block LayerList).
    scan_layers: bool = None
    # tied-head CE kernel choice: None = auto (XLA recompute path below
    # V=64k, Pallas streaming kernel above), True/False forces. True is
    # the memory-optimal setting for big models on one chip — the f32
    # [tokens, V] logits never hit HBM at all (fused_ce.py)
    fused_head_ce: bool = None

    @property
    def head_dim(self):
        return self.hidden // self.heads


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=512, max_seq_len=128, hidden=64, layers=2,
                     heads=4, **kw)


def gpt2_124m(**kw):
    return GPTConfig(hidden=768, layers=12, heads=12, **kw)


def gpt2_345m(**kw):
    return GPTConfig(hidden=1024, layers=24, heads=16, **kw)


def gpt3_1p3b(**kw):
    return GPTConfig(hidden=2048, layers=24, heads=16, max_seq_len=2048, **kw)



def _pp_mm(cd):
    """Matmul helper for the hand-written pipeline blocks: bf16 operands
    when cd is set (AMP), f32 accumulate/output."""
    def mm(a, w):
        if cd is not None:
            return (a.astype(cd) @ w.astype(cd)).astype(jnp.float32)
        return a @ w
    return mm


def _pp_ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _pp_dropout(x, key, p):
    """Inverted dropout on raw jnp arrays (the pipeline's pure per-stage
    fns bypass the Tensor-level F.dropout)."""
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


def _pp_moe(xt, bp, E, K, C, axis_ep=None, axis_tp=None, axis_sp=None):
    """Dense Switch-MoE FFN on raw jnp arrays for the pipeline blocks
    (same routing math as nn/layer/moe.py), in three partitionings:

      axis_ep: each member holds E/n_ep experts; contributions psum over
               'ep' (activations replicated).
      axis_tp: every member holds ALL experts but only Hf/n_tp of each
               expert's hidden dim; partial expert outputs psum over 'tp'
               (Megatron row-parallel w_out).
      axis_sp: experts fully replicated; each member routes its LOCAL
               token shard; the aux statistics pmean over 'sp' BEFORE
               the product so the load-balance value matches the global
               computation exactly (mean-of-products != product-of-means).

    Returns (y [N, H], aux scalar)."""
    if axis_tp is not None and axis_ep is not None:
        raise NotImplementedError(
            "_pp_moe: tp x ep expert sharding in one block is not "
            "supported (pick one; the combine below reduces over a "
            "single axis)")
    N, H = xt.shape
    logits = (xt @ bp["moe.gate_w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates_list, onehot_list = [], []
    masked = probs
    for _ in range(K):
        idx = masked.argmax(axis=-1)
        oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        gates_list.append((probs * oh).sum(-1))
        onehot_list.append(oh)
        masked = masked * (1.0 - oh)
    flat_oh = jnp.concatenate(onehot_list, 0)
    pos = jnp.cumsum(flat_oh, axis=0) - flat_oh
    keep = (pos < C) * flat_oh
    pos_id = (pos * flat_oh).sum(-1).astype(jnp.int32)
    cap_oh = jax.nn.one_hot(pos_id, C, dtype=jnp.float32)
    gates = jnp.concatenate(gates_list, 0)
    dispatch = keep[:, :, None] * cap_oh[:, None, :]       # [KN, E, C]
    combine = dispatch * gates[:, None, None]

    if axis_ep is not None:
        e_loc = bp["moe.w_in"].shape[0]
        e0 = jax.lax.axis_index(axis_ep) * e_loc
        disp_l = jax.lax.dynamic_slice_in_dim(dispatch, e0, e_loc, 1)
        comb_l = jax.lax.dynamic_slice_in_dim(combine, e0, e_loc, 1)
    else:
        disp_l, comb_l = dispatch, combine

    xrep = jnp.tile(xt, (K, 1)).astype(jnp.float32)
    expert_in = jnp.einsum("nec,nm->ecm", disp_l, xrep)
    hh = jnp.einsum("ecm,emh->ech", expert_in,
                    bp["moe.w_in"].astype(jnp.float32)) \
        + bp["moe.b_in"][:, None, :]
    hh = jax.nn.gelu(hh)
    eout = jnp.einsum("ech,ehm->ecm", hh,
                      bp["moe.w_out"].astype(jnp.float32))
    # combine is linear, so collectives ride the [KN, M] combined output
    # rather than the ~K*cap_f-times-larger [E, C, M] expert tensor;
    # the bias contribution einsum('nec,em->nm') is exact because each
    # dispatched slot receives its expert's bias once
    y_core = jnp.einsum("nec,ecm->nm", comb_l, eout)
    bias_t = jnp.einsum("nec,em->nm", comb_l, bp["moe.b_out"])
    if axis_tp is not None:
        # hidden dim is tp-local: partial combined outputs meet here;
        # bias (replicated) is added once, after the psum
        y = jax.lax.psum(y_core, axis_tp) + bias_t
    elif axis_ep is not None:
        # each member contributes its local experts' outputs AND their
        # bias share; the psum assembles both
        y = jax.lax.psum(y_core + bias_t, axis_ep)
    else:
        y = y_core + bias_t
    y = y.reshape(K, N, H).sum(0)

    frac = onehot_list[0].mean(0)
    mean_p = probs.mean(0)
    if axis_sp is not None:
        # exact global load-balance statistics across sequence shards
        frac = jax.lax.pmean(frac, axis_sp)
        mean_p = jax.lax.pmean(mean_p, axis_sp)
    aux = (frac * mean_p).sum() * E
    return y, aux


def masked_linear_ce(h, weight, labels, ignore_index=-100, fused=None):
    """Tied-head CE via linear_cross_entropy (ops/pallas/fused_ce.py),
    shared by the GPT and BERT heads: the [tokens, vocab] logits are
    never saved as backward residuals — the head matmul is recomputed in
    the VJP (and with fused=True never hits HBM at all). Masking matches
    F.cross_entropy's ignore_index semantics: ignored rows contribute 0
    to the sum and are excluded from the mean's denominator; an
    all-ignored batch yields 0 loss, not 0/0."""
    C = h.shape[-1]
    lab = F_ops.reshape(labels, [-1])
    valid = F_ops.not_equal(lab, F_ops.full_like(lab, ignore_index))
    safe = F_ops.where(valid, lab, F_ops.zeros_like(lab))
    rows = F.linear_cross_entropy(F_ops.reshape(h, [-1, C]), weight, safe,
                                  fused=fused, reduction="none")
    rows = F_ops.where(valid, rows, F_ops.zeros_like(rows))
    n_valid = F_ops.sum(F_ops.cast(valid, "float32"))
    n_valid = F_ops.maximum(n_valid, F_ops.ones_like(n_valid))
    return F_ops.sum(rows) / n_valid


class CausalSelfAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.qkv = nn.Linear(cfg.hidden, 3 * cfg.hidden)
        self.proj = nn.Linear(cfg.hidden, cfg.hidden)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x):
        B, T, C = x.shape
        H, D = self.cfg.heads, self.cfg.head_dim
        qkv = self.qkv(x)                                   # [B,T,3C]
        q, k, v = qkv.chunk(3, axis=-1)
        q = q.reshape([B, T, H, D])
        k = k.reshape([B, T, H, D])
        v = v.reshape([B, T, H, D])
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = out.reshape([B, T, C])
        return self.drop(self.proj(out))


class Block(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden)
        self.attn = CausalSelfAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden)
        if cfg.moe_experts > 0:
            # expert-parallel FFN (nn/layer/moe.py; new capability — the
            # reference has no MoE)
            self.moe = nn.MoELayer(cfg.hidden, cfg.ffn_mult * cfg.hidden,
                                   cfg.moe_experts, top_k=cfg.moe_top_k)
        else:
            self.fc1 = nn.Linear(cfg.hidden, cfg.ffn_mult * cfg.hidden)
            self.fc2 = nn.Linear(cfg.ffn_mult * cfg.hidden, cfg.hidden)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x):
        x = x + self.attn(self.ln1(x))
        if hasattr(self, "moe"):
            h = self.moe(self.ln2(x))
        else:
            h = self.fc2(F.gelu(self.fc1(self.ln2(x))))
        return x + self.drop(h)


class GPT(nn.Layer):
    """Pre-LN GPT decoder LM. forward(token_ids [B,T]) -> logits [B,T,V]."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        from ..framework import ParamAttr
        from ..nn import initializer as I
        emb_init = ParamAttr(initializer=I.Normal(0.0, 0.02))  # GPT-2 init
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden, weight_attr=emb_init)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden, weight_attr=emb_init)
        self.drop = nn.Dropout(cfg.dropout)
        scan = cfg.scan_layers
        if scan is None:
            scan = cfg.moe_experts == 0  # MoE aux losses can't leave a scan
        elif scan and cfg.moe_experts > 0:
            raise ValueError("scan_layers=True is incompatible with MoE "
                             "blocks (collect_aux_losses cannot cross a "
                             "jax.lax.scan body)")
        per_block = [Block(cfg) for _ in range(cfg.layers)]
        self.blocks = (nn.ScanBlockStack(per_block) if scan
                       else nn.LayerList(per_block))
        self.ln_f = nn.LayerNorm(cfg.hidden)
        # weight tying (lm_head = wte.T) keeps the embedding matmul on-MXU
        # and halves embedding memory, standard for the GPT family.

    def enable_block_recompute(self, flag=True, policy=None):
        """Per-BLOCK activation recomputation (strategy-compiler
        protocol): each transformer block runs under jax.checkpoint, so
        the live set during backward is one block's activations plus the
        per-block boundary residuals — a whole-forward checkpoint keeps
        peak memory unchanged (everything rematerializes at once), which
        is how the 1.3B config OOMed a 16 GB chip. `policy` is a
        jax.checkpoint_policies entry applied per block. The compiler
        sets/restores this around the traced forward only (the flag must
        not leak into later compiles or eager use)."""
        self._recompute_blocks = bool(flag)
        self._recompute_policy = policy
        return self

    def forward_hidden(self, idx):
        """Final-layer-norm hidden states [B,T,C] (everything but the tied
        LM head) — the input the fused linear+CE loss consumes."""
        B, T = idx.shape
        from ..ops.creation import arange
        pos = arange(T, dtype="int64").unsqueeze(0)
        x = self.drop(self.wte(idx) + self.wpe(pos))
        if isinstance(self.blocks, nn.ScanBlockStack):
            self.blocks.set_recompute(
                getattr(self, "_recompute_blocks", False),
                getattr(self, "_recompute_policy", None))
            x = self.blocks(x)
        elif getattr(self, "_recompute_blocks", False):
            from ..distributed.fleet.utils import recompute
            pol = getattr(self, "_recompute_policy", None)
            for blk in self.blocks:
                x = recompute(blk, x, checkpoint_policy=pol)
        else:
            for blk in self.blocks:
                x = blk(x)
        return self.ln_f(x)

    def set_scan_unroll(self, flag=True):
        """Escape hatch (DistributedStrategy.scan_layers = False): run the
        stacked params through a Python loop instead of jax.lax.scan."""
        if isinstance(self.blocks, nn.ScanBlockStack):
            self.blocks.set_unroll(flag)
        return self

    def forward(self, idx):
        x = self.forward_hidden(idx)
        logits = F.linear(x, self.wte.weight.transpose([1, 0]))
        return logits

    def _head_ce(self, h, labels, ignore_index=-100):
        return masked_linear_ce(h, self.wte.weight, labels,
                                ignore_index=ignore_index,
                                fused=self.cfg.fused_head_ce)

    def loss(self, idx, labels, moe_aux_coef=None):
        if moe_aux_coef is None:
            moe_aux_coef = getattr(self.cfg, "moe_aux_coef", 0.01)
        if self.cfg.moe_experts > 0:
            from ..nn.layer.moe import collect_aux_losses
            with collect_aux_losses() as auxes:
                h = self.forward_hidden(idx)
            ce = self._head_ce(h, labels)
            # Switch load-balance pressure so experts don't collapse
            total_aux = auxes[0]
            for a in auxes[1:]:
                total_aux = total_aux + a
            return ce + moe_aux_coef * total_aux / max(len(auxes), 1)
        return self._head_ce(self.forward_hidden(idx), labels)

    def num_params(self) -> int:
        return sum(int(math.prod(p.shape)) for p in self.parameters())

    def flops_per_token(self, seq_len=None) -> int:
        """Train-step (fwd+bwd) FLOPs per token: 6N for the parameter
        matmuls plus the attention score/value matmuls, which contribute
        12 * layers * hidden * seq per token (fwd QK^T and AV are each
        2*T*hidden per token per layer; x3 for fwd+bwd)."""
        n = self.num_params()
        c = self.cfg
        attn = 12 * c.layers * c.hidden * (seq_len or c.max_seq_len)
        return 6 * n + attn

    def param_shardings(self, params, mesh_axis_tp="tp"):
        """Strategy-compiler protocol (fleet/compiler.py `_tp_specs`):
        Megatron tensor-parallel PartitionSpecs for every parameter."""
        return gpt_param_shardings(params, mesh_axis_tp=mesh_axis_tp)

    # -- pipeline-parallel protocol (fleet/compiler.py pipeline branch) ----
    def pipeline_split_params(self, params):
        """Split the flat functional param dict into (embed, [block_i],
        head) for the SPMD pipeline: homogeneous blocks are stacked and
        sharded over 'pp'; embed/head run replicated outside the pipelined
        region (reference program splitter: PipelineOptimizer
        optimizer.py:3718 assigns ops to stages; here the split is by
        construction)."""
        embed = {k: v for k, v in params.items()
                 if k.startswith(("wte.", "wpe."))}
        head = {k: v for k, v in params.items() if k.startswith("ln_f.")}
        blocks = []
        if isinstance(self.blocks, nn.ScanBlockStack):
            # scan layout: params carry stacked "blocks.{rel}" [L, ...]
            # arrays — slice the leading axis back into per-stage dicts
            stacked = {k[len("blocks."):]: v for k, v in params.items()
                       if k.startswith("blocks.")}
            for i in range(self.cfg.layers):
                blocks.append({rel: v[i] for rel, v in stacked.items()})
            return embed, blocks, head
        for i in range(self.cfg.layers):
            pref = f"blocks.{i}."
            blocks.append({k[len(pref):]: v for k, v in params.items()
                           if k.startswith(pref)})
        return embed, blocks, head

    def pipeline_fns(self, ignore_index=-100):
        """Pure (embed_fn, block_fn, head_loss_fn) for the pipeline step.
        block_fn reuses blocks[0] as the shared functional template (all
        blocks are structurally identical; layer i's params are fed in).

        Dropout rides an explicit key: the 1F1B scheduler folds
        (microbatch, global-layer, data-axis ranks) into the step key and
        hands each block call its own subkey (`key_scope` makes the
        Layer-level F.dropout draw from it), so the backward slot's remat
        reproduces the forward's masks exactly — the reference threads
        seeds the same way in its recompute pass
        (fluid/backward.py modify_forward_desc_for_recompute).
        embed_fn's pos_offset shifts wpe lookups for sequence-parallel
        shards (local T/sp window into the global positions)."""
        from ..core import random as random_mod
        from ..framework import functional_call
        from ..ops.pallas.fused_ce import linear_cross_entropy
        blk0 = self.blocks[0]
        p_drop = float(self.cfg.dropout)

        def embed_fn(ep, ids, pos_offset=0, key=None):
            T = ids.shape[-1]
            pos = jnp.arange(T) + pos_offset
            x = ep["wte.weight"][ids] + ep["wpe.weight"][pos]
            # self.training read at trace time — the same capture moment
            # as blk0.training inside functional_call, so embed and block
            # dropout always agree on train/eval mode
            if p_drop > 0 and key is not None and self.training:
                x = _pp_dropout(x, key, p_drop)
            return x

        emits_aux = self.cfg.moe_experts > 0

        def _call_block(bp, h, key):
            """One block through functional_call; MoE configs also return
            the Switch load-balance aux (the 1F1B scheduler threads it
            into the objective — reference analog: the aux-loss fetch the
            pipeline trainer skips, here actually propagated)."""
            import contextlib

            ctx = random_mod.key_scope(key) if key is not None \
                else contextlib.nullcontext()
            if emits_aux:
                from ..nn.layer.moe import collect_aux_losses
                with collect_aux_losses() as auxes, ctx:
                    out, _ = functional_call(blk0, bp, {}, h,
                                             mutable_state=False)
                total = auxes[0]
                for a in auxes[1:]:
                    total = total + a
                total = total._data if hasattr(total, "_data") else total
                return out, total
            with ctx:
                out, _ = functional_call(blk0, bp, {}, h,
                                         mutable_state=False)
            return out

        if p_drop > 0:
            def block_fn(bp, h, key=None):
                if key is None and blk0.training:
                    # no key in TRAIN mode -> trace-time constant masks;
                    # refuse loudly (eval mode draws no dropout and is
                    # fine keyless — the pipelined eval path)
                    raise NotImplementedError(
                        "GPT pipeline block with dropout > 0 needs the "
                        "scheduler to thread a PRNG key (use the "
                        "fleet-compiled train step)")
                return _call_block(bp, h, key)
        else:
            def block_fn(bp, h):
                return _call_block(bp, h, None)

        eps = self.ln_f._epsilon

        def head_loss_fn(hp, ep, h, labels):
            """Returns (loss_sum, valid_token_count) so the caller can form
            the GLOBAL masked mean over all microbatches — a per-microbatch
            mean-of-means would weight unevenly-padded microbatches
            differently from the sequential path."""
            g, b = hp["ln_f.weight"], hp["ln_f.bias"]
            mu = h.mean(-1, keepdims=True)
            var = ((h - mu) ** 2).mean(-1, keepdims=True)
            hn = (h - mu) / jnp.sqrt(var + eps) * g + b
            H = hn.shape[-1]
            lab = labels.reshape(-1).astype(jnp.int32)
            valid = lab != ignore_index
            # tied head via the fused linear+CE op (same ignore_index
            # masking as F.cross_entropy: padded rows contribute 0)
            rows = linear_cross_entropy(
                hn.reshape(-1, H), ep["wte.weight"],
                jnp.where(valid, lab, 0))
            rows = jnp.where(valid, rows, 0.0)
            return rows.sum(), valid.astype(jnp.float32).sum()

        # label-only count for the scheduler's aux-gradient pre-scaling
        head_loss_fn.valid_count = lambda labels: (
            labels.reshape(-1).astype(jnp.int32) != ignore_index
        ).astype(jnp.float32).sum()
        return embed_fn, block_fn, head_loss_fn

    @property
    def pipeline_block_emits_aux(self):
        """True when pipeline_fns' block_fn returns (h, aux) — MoE
        configs carry the Switch load-balance loss through the 1F1B
        scheduler."""
        return self.cfg.moe_experts > 0

    # -- manual-tp pipeline protocol (pp x tp composition) -----------------
    # The SPMD pipeline runs inside a shard_map where every mesh axis is
    # manual, so tensor parallelism inside a stage cannot rely on GSPMD:
    # the packed qkv matrix must be physically split per head-group and
    # the two Megatron reductions (after attn-proj and after fc2) are
    # explicit psums over 'tp'. Reference analog: the hand-inserted
    # c_allreduce ops a Megatron program rewrite would emit.

    TP_SPLIT_KEYS = ("q_w", "q_b", "k_w", "k_b", "v_w", "v_b")

    @staticmethod
    def split_block_params_tp(bp):
        """One block's params -> manual-tp layout: packed qkv split into
        q/k/v so a last-dim shard holds whole heads."""
        import numpy as _np
        qkv_w = _np.asarray(bp["attn.qkv.weight"])     # [H, 3H]
        qkv_b = _np.asarray(bp["attn.qkv.bias"])       # [3H]
        q_w, k_w, v_w = _np.split(qkv_w, 3, axis=1)
        q_b, k_b, v_b = _np.split(qkv_b, 3)
        out = {k: v for k, v in bp.items()
               if not k.startswith("attn.qkv.")}
        out.update({"q_w": q_w, "k_w": k_w, "v_w": v_w,
                    "q_b": q_b, "k_b": k_b, "v_b": v_b})
        return out

    @staticmethod
    def merge_block_params_tp(split):
        """Inverse of split_block_params_tp (for write_back)."""
        import numpy as _np
        out = {k: v for k, v in split.items()
               if k not in GPT.TP_SPLIT_KEYS}
        out["attn.qkv.weight"] = _np.concatenate(
            [split["q_w"], split["k_w"], split["v_w"]], axis=1)
        out["attn.qkv.bias"] = _np.concatenate(
            [split["q_b"], split["k_b"], split["v_b"]])
        return out

    @staticmethod
    def block_tp_specs(axis_pp="pp", axis_tp="tp"):
        """Stacked-layout PartitionSpecs for the split-tp block params
        ([L, ...] leading layer dim over pp; Megatron col/row over tp)."""
        from jax.sharding import PartitionSpec as P
        return {
            "ln1.weight": P(axis_pp, None), "ln1.bias": P(axis_pp, None),
            "ln2.weight": P(axis_pp, None), "ln2.bias": P(axis_pp, None),
            "q_w": P(axis_pp, None, axis_tp), "q_b": P(axis_pp, axis_tp),
            "k_w": P(axis_pp, None, axis_tp), "k_b": P(axis_pp, axis_tp),
            "v_w": P(axis_pp, None, axis_tp), "v_b": P(axis_pp, axis_tp),
            "attn.proj.weight": P(axis_pp, axis_tp, None),
            "attn.proj.bias": P(axis_pp, None),
            "fc1.weight": P(axis_pp, None, axis_tp),
            "fc1.bias": P(axis_pp, axis_tp),
            "fc2.weight": P(axis_pp, axis_tp, None),
            "fc2.bias": P(axis_pp, None),
            # MoE under tp: every member holds all experts, hidden dim
            # sharded (Megatron column/row split per expert); router and
            # output biases replicate
            "moe.gate_w": P(axis_pp, None, None),
            "moe.w_in": P(axis_pp, None, None, axis_tp),   # [L,E,M,Hf]
            "moe.b_in": P(axis_pp, None, axis_tp),
            "moe.w_out": P(axis_pp, None, axis_tp, None),  # [L,E,Hf,M]
            "moe.b_out": P(axis_pp, None, None),
        }

    def pipeline_block_fn_tp(self, axis_tp="tp", compute_dtype=None,
                             with_aux=False, axis_sp=None, impl="ring"):
        """block_fn for the manual-tp pipeline: local head-group attention
        + Megatron MLP with explicit psums over `axis_tp`. Operates on the
        split layout from split_block_params_tp (local tp shards).

        With `axis_sp` set this is the pp x tp x SP block (the v5p-64
        long-context mesh; VERDICT r4 Next #7): h is the LOCAL sequence
        shard [B, T/sp, H] and attention runs as ring/Ulysses over
        `axis_sp` on the local head group — attention is per-head, so
        the sp ring composes with the tp head split directly; LN/MLP are
        sequence-elementwise and keep the same tp psums.

        MoE configs replace the MLP with the Switch FFN partitioned the
        Megatron way: every member holds all experts but only Hf/n_tp of
        each expert's hidden dim (block_tp_specs moe.* entries), partial
        expert outputs psum over 'tp' (_pp_moe axis_tp; with axis_sp the
        routing stats additionally fold over the sequence shards).
        Routing runs on the tp-replicated stream, so members agree
        without a collective; with_aux threads the load-balance aux to
        the scheduler.

        compute_dtype="bfloat16": matmul/einsum operands cast to bf16 (the
        AMP-O1 treatment — raw jnp ops here bypass the autocast dispatcher
        hook, so the cast must be explicit); LN stats, softmax and the
        residual stream stay f32.

        Dropout (Block's two sites: after attn-proj, after fc2) rides the
        scheduler-threaded key, folded by the sp rank when axis_sp is set
        (different tokens per shard) and NEVER by tp rank: the residual
        stream is replicated over 'tp', so every member must draw the
        identical mask or the manual psums stop agreeing (the scheduler's
        fold_data_axes enforces both)."""
        attn_impl = None
        if axis_sp is not None:
            from ..distributed.sequence_parallel import (ring_attention,
                                                         ulysses_attention)
            impls = {"ring": ring_attention, "ulysses": ulysses_attention}
            if impl not in impls:
                raise ValueError(
                    f"sequence_parallel impl must be 'ring' or "
                    f"'ulysses', got {impl!r}")
            attn_impl = impls[impl]
        is_moe = self.cfg.moe_experts > 0
        if with_aux and not is_moe:
            raise ValueError("with_aux needs a MoE config")
        E = self.cfg.moe_experts
        K = self.cfg.moe_top_k if is_moe else 0
        cap_f = self.blocks[0].moe.capacity_factor if is_moe else 0.0
        D = self.cfg.head_dim
        eps1 = self.blocks[0].ln1._epsilon
        eps2 = self.blocks[0].ln2._epsilon
        cd = jnp.bfloat16 if compute_dtype in ("bfloat16", "bf16",
                                               jnp.bfloat16) else None
        mm, ln = _pp_mm(cd), _pp_ln
        p_drop = float(self.cfg.dropout)
        gpt_self = self

        def _drop(x, key, site):
            if p_drop <= 0 or key is None or not gpt_self.training:
                return x
            return _pp_dropout(x, jax.random.fold_in(key, site), p_drop)

        def _block_core(bp, h, key):
            B, T, H = h.shape                   # T is T/sp under axis_sp
            h1 = ln(h, bp["ln1.weight"], bp["ln1.bias"], eps1)
            q = mm(h1, bp["q_w"]) + bp["q_b"]   # [B,T,H/ntp] local heads
            k = mm(h1, bp["k_w"]) + bp["k_b"]
            v = mm(h1, bp["v_w"]) + bp["v_b"]
            nloc = q.shape[-1] // D
            q = q.reshape(B, T, nloc, D)
            k = k.reshape(B, T, nloc, D)
            v = v.reshape(B, T, nloc, D)
            if cd is not None:
                q, k, v = q.astype(cd), k.astype(cd), v.astype(cd)
            if attn_impl is not None:
                o = attn_impl(q, k, v, axis=axis_sp, causal=True) \
                    .reshape(B, T, -1).astype(jnp.float32)
            else:
                # causal attention on the local head group — same op
                # order as F.scaled_dot_product_attention's XLA core
                # (attention.py _sdpa_xla) so pp x tp matches the
                # sequential loss closely
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
                    * (1.0 / math.sqrt(D))
                s = s.astype(jnp.float32)
                causal = jnp.tril(jnp.ones((T, T), bool))
                s = jnp.where(causal[None, None], s, -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
                o = jnp.einsum("bhqk,bkhd->bqhd", p, v) \
                    .reshape(B, T, -1).astype(jnp.float32)
            # row-parallel proj: partial sums meet across head groups
            att = jax.lax.psum(mm(o, bp["attn.proj.weight"]), axis_tp) \
                + bp["attn.proj.bias"]
            h = h + _drop(att, key, 0)
            h2 = ln(h, bp["ln2.weight"], bp["ln2.bias"], eps2)
            if is_moe:
                N = B * T
                C = max(int(math.ceil(cap_f * N * K / E)), 1)
                y, aux = _pp_moe(h2.reshape(N, H), bp, E, K, C,
                                 axis_tp=axis_tp, axis_sp=axis_sp)
                out = h + _drop(y.reshape(B, T, H).astype(h.dtype), key, 1)
                return (out, aux) if with_aux else out
            m = jax.nn.gelu(mm(h2, bp["fc1.weight"]) + bp["fc1.bias"],
                            approximate=False)   # Block uses exact gelu
            mo = jax.lax.psum(mm(m, bp["fc2.weight"]), axis_tp) \
                + bp["fc2.bias"]
            return h + _drop(mo, key, 1)

        if p_drop > 0:
            def block_fn(bp, h, key=None):
                return _block_core(bp, h, key)
        else:
            def block_fn(bp, h):
                return _block_core(bp, h, None)

        return block_fn


    def pipeline_block_fn_tp_sp(self, axis_tp="tp", axis_sp="sp",
                                impl="ring", compute_dtype=None,
                                with_aux=False):
        """pp x tp x sp block (strategy-compiler protocol name): the tp
        block with ring/Ulysses attention over `axis_sp` — one
        implementation, see pipeline_block_fn_tp's axis_sp mode."""
        return self.pipeline_block_fn_tp(
            axis_tp=axis_tp, compute_dtype=compute_dtype,
            with_aux=with_aux, axis_sp=axis_sp, impl=impl)

    def pipeline_block_fn_sp(self, axis_sp="sp", impl="ring",
                             compute_dtype=None, with_aux=False):
        """block_fn for the pipeline x sequence-parallel mesh: the block
        sees the LOCAL sequence shard [B, T/sp, C]; attention runs as
        ring attention (K/V rotation over `axis_sp`) or Ulysses — both
        shard_map-inner (distributed/sequence_parallel.py), which is what
        the pipeline's all-manual region requires. LN/MLP are sequence-
        elementwise, so they need no collectives at all.

        Dropout rides the scheduler key, which the 1F1B scheduler FOLDS
        by the sp rank (pipeline_value_and_grad's data-axis folding) —
        each shard holds different tokens, so masks must decorrelate.

        MoE: experts replicate; each member routes its local tokens with
        local capacity (_pp_moe axis_sp folds the load-balance stats
        across shards so the aux matches the global value exactly)."""
        from ..distributed.sequence_parallel import (ring_attention,
                                                     ulysses_attention)
        impls = {"ring": ring_attention, "ulysses": ulysses_attention}
        if impl not in impls:
            raise ValueError(
                f"sequence_parallel impl must be 'ring' or 'ulysses', "
                f"got {impl!r}")
        attn_impl = impls[impl]
        is_moe = self.cfg.moe_experts > 0
        if with_aux and not is_moe:
            raise ValueError("with_aux needs a MoE config")
        E = self.cfg.moe_experts
        K = self.cfg.moe_top_k if is_moe else 0
        cap_f = self.blocks[0].moe.capacity_factor if is_moe else 0.0
        D = self.cfg.head_dim
        eps1 = self.blocks[0].ln1._epsilon
        eps2 = self.blocks[0].ln2._epsilon
        cd = jnp.bfloat16 if compute_dtype in ("bfloat16", "bf16",
                                               jnp.bfloat16) else None
        mm, ln = _pp_mm(cd), _pp_ln
        p_drop = float(self.cfg.dropout)
        gpt_self = self

        def _drop(x, key, site):
            if p_drop <= 0 or key is None or not gpt_self.training:
                return x
            return _pp_dropout(x, jax.random.fold_in(key, site), p_drop)

        def _core(bp, h, key):
            B, Tl, H = h.shape
            h1 = ln(h, bp["ln1.weight"], bp["ln1.bias"], eps1)
            qkv = mm(h1, bp["attn.qkv.weight"]) + bp["attn.qkv.bias"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            nh = H // D
            q = q.reshape(B, Tl, nh, D)
            k = k.reshape(B, Tl, nh, D)
            v = v.reshape(B, Tl, nh, D)
            if cd is not None:
                q, k, v = q.astype(cd), k.astype(cd), v.astype(cd)
            o = attn_impl(q, k, v, axis=axis_sp, causal=True)
            o = o.reshape(B, Tl, H).astype(jnp.float32)
            att = mm(o, bp["attn.proj.weight"]) + bp["attn.proj.bias"]
            h = h + _drop(att, key, 0)
            h2 = ln(h, bp["ln2.weight"], bp["ln2.bias"], eps2)
            if is_moe:
                N = B * Tl
                C = max(int(math.ceil(cap_f * N * K / E)), 1)
                y, aux = _pp_moe(h2.reshape(N, H), bp, E, K, C,
                                 axis_sp=axis_sp)
                out = h + _drop(y.reshape(B, Tl, H).astype(h.dtype),
                                key, 1)
                return (out, aux) if with_aux else out
            m = jax.nn.gelu(mm(h2, bp["fc1.weight"]) + bp["fc1.bias"],
                            approximate=False)
            return h + _drop(mm(m, bp["fc2.weight"]) + bp["fc2.bias"],
                             key, 1)

        if p_drop > 0:
            def block_fn(bp, h, key=None):
                return _core(bp, h, key)
        else:
            def block_fn(bp, h):
                return _core(bp, h, None)

        return block_fn


    @staticmethod
    def block_ep_specs(axis_pp="pp", axis_ep="ep"):
        """Stacked-layout PartitionSpecs for a MoE block under manual
        expert parallelism: expert banks shard their E dim over 'ep',
        everything else replicates (attention is untouched by ep)."""
        from jax.sharding import PartitionSpec as P

        def expert(ndim):
            return P(axis_pp, axis_ep, *([None] * (ndim - 2)))

        return {
            "ln1.weight": P(axis_pp, None), "ln1.bias": P(axis_pp, None),
            "ln2.weight": P(axis_pp, None), "ln2.bias": P(axis_pp, None),
            "attn.qkv.weight": P(axis_pp, None, None),
            "attn.qkv.bias": P(axis_pp, None),
            "attn.proj.weight": P(axis_pp, None, None),
            "attn.proj.bias": P(axis_pp, None),
            "moe.gate_w": P(axis_pp, None, None),
            "moe.w_in": expert(4),   # [L, E, M, H]
            "moe.b_in": expert(3),
            "moe.w_out": expert(4),
            "moe.b_out": expert(3),
        }

    def pipeline_block_fn_ep(self, axis_ep="ep", compute_dtype=None,
                             with_aux=False, axis_sp=None, impl="ring"):
        """block_fn for pipeline x expert parallelism: activations are
        REPLICATED across 'ep' members, each member runs only its local
        expert slab (E/n_ep experts of the stacked bank), and one psum
        over 'ep' sums the per-expert contributions — the manual form of
        the GSPMD einsum dispatch in nn/layer/moe.py.

        With `axis_sp` set this is the pp x sp x EP block (formerly an
        explicit refusal): the stream is the LOCAL sequence shard, the
        attention is ring/Ulysses over `axis_sp`, each member routes its
        local tokens with local capacity, and _pp_moe folds the
        load-balance statistics over 'sp' (exact global aux) while the
        expert-slab psum stays over 'ep'.

        with_aux=True: the block also returns the Switch load-balance
        aux (E * sum_e frac_tokens_e * mean_prob_e, same formula as
        nn/layer/moe.py) — the 1F1B scheduler threads it into the
        objective, so expert-collapse pressure IS applied on the
        pipeline path."""
        if self.cfg.moe_experts <= 0:
            raise ValueError("pipeline_block_fn_ep requires a MoE config "
                             "(GPTConfig.moe_experts > 0)")
        attn_impl = None
        if axis_sp is not None:
            from ..distributed.sequence_parallel import (
                ring_attention, ulysses_attention)
            impls = {"ring": ring_attention, "ulysses": ulysses_attention}
            if impl not in impls:
                raise ValueError(
                    f"sequence_parallel impl must be 'ring' or "
                    f"'ulysses', got {impl!r}")
            attn_impl = impls[impl]
        D = self.cfg.head_dim
        E = self.cfg.moe_experts
        K = self.cfg.moe_top_k
        cap_f = self.blocks[0].moe.capacity_factor
        eps1 = self.blocks[0].ln1._epsilon
        eps2 = self.blocks[0].ln2._epsilon
        cd = jnp.bfloat16 if compute_dtype in ("bfloat16", "bf16",
                                               jnp.bfloat16) else None
        mm, ln = _pp_mm(cd), _pp_ln
        p_drop = float(self.cfg.dropout)
        gpt_self = self

        def _drop(x, key, site):
            # key identical across 'ep' members (the scheduler folds only
            # data axes): the residual stream is replicated over ep, so
            # every member must draw the same mask or the psum breaks
            if p_drop <= 0 or key is None or not gpt_self.training:
                return x
            return _pp_dropout(x, jax.random.fold_in(key, site), p_drop)

        def _core(bp, h, key):
            B, T, H = h.shape
            h1 = ln(h, bp["ln1.weight"], bp["ln1.bias"], eps1)
            qkv = mm(h1, bp["attn.qkv.weight"]) + bp["attn.qkv.bias"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            nh = H // D
            q = q.reshape(B, T, nh, D)
            k = k.reshape(B, T, nh, D)
            v = v.reshape(B, T, nh, D)
            if attn_impl is not None:
                if cd is not None:   # AMP: ring traffic + matmuls in bf16
                    q, k, v = q.astype(cd), k.astype(cd), v.astype(cd)
                o = attn_impl(q, k, v, axis=axis_sp, causal=True) \
                    .reshape(B, T, H).astype(jnp.float32)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
                    * (1.0 / math.sqrt(D))
                s = s.astype(jnp.float32)
                causal = jnp.tril(jnp.ones((T, T), bool))
                s = jnp.where(causal[None, None], s, -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
                o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, H) \
                    .astype(jnp.float32)
            att = mm(o, bp["attn.proj.weight"]) + bp["attn.proj.bias"]
            h = h + _drop(att, key, 0)

            # --- MoE FFN, manual ep: full routing, local expert slab ---
            h2 = ln(h, bp["ln2.weight"], bp["ln2.bias"], eps2)
            N = B * T
            C = max(int(math.ceil(cap_f * N * K / E)), 1)
            y, aux = _pp_moe(h2.reshape(N, H), bp, E, K, C,
                             axis_ep=axis_ep, axis_sp=axis_sp)
            out = h + _drop(y.reshape(B, T, H).astype(h.dtype), key, 1)
            # routing is replicated over 'ep' so every member computes
            # the identical aux value
            return (out, aux) if with_aux else out

        if p_drop > 0:
            def block_fn(bp, h, key=None):
                return _core(bp, h, key)
        else:
            def block_fn(bp, h):
                return _core(bp, h, None)

        return block_fn


def gpt_param_shardings(params, mesh_axis_tp="tp"):
    """Megatron-style TP PartitionSpecs keyed by the functional param dict
    names produced by `framework.functional_call` on a GPT instance.

    Column-parallel (shard output dim): qkv and ffn-in weights.
    Row-parallel (shard input dim): attn proj and ffn-out weights — XLA
    inserts the psum where the partial sums meet, exactly the Megatron
    f/g collectives, but compiler-derived instead of hand-written.
    Embeddings shard over vocab/feature rows.
    """
    import re

    from jax.sharding import PartitionSpec as P
    specs = {}
    for name, v in params.items():
        ndim = len(v.shape)
        # scan layout: "blocks.{rel}" (no block index) carries a leading
        # [layers] scan axis — shard the per-block dims, replicate layers
        stacked = (name.startswith("blocks.")
                   and not re.match(r"blocks\.\d+\.", name))
        if stacked:
            ndim -= 1
        if ".moe." in name and name.rsplit(".", 1)[-1] in (
                "w_in", "b_in", "w_out", "b_out"):
            spec = P("ep", *([None] * (ndim - 1)))       # expert parallel
        elif "qkv.weight" in name or "fc1.weight" in name:
            spec = P(None, mesh_axis_tp)                 # column parallel
        elif "qkv.bias" in name or "fc1.bias" in name:
            spec = P(mesh_axis_tp)
        elif "proj.weight" in name or "fc2.weight" in name:
            spec = P(mesh_axis_tp, None)                 # row parallel
        elif "wte.weight" in name:
            spec = P(mesh_axis_tp, None)                 # vocab parallel
        elif ndim >= 2:
            spec = P(*([None] * ndim))
        else:
            spec = P()                                   # replicate ln/bias
        specs[name] = P(None, *spec) if stacked else spec
    return specs


# ---------------------------------------------------------------------------
# Incremental (KV-cache) decode forward — inference/decode.py's compute core
# ---------------------------------------------------------------------------

def split_decode_params(params, cfg: GPTConfig):
    """Split a GPT functional param dict into (embed, [block_i], head)
    for the decode fns, accepting BOTH parameter layouts a GPT instance
    can produce: per-block indexed names ("blocks.3.attn.qkv.weight")
    and the scan-stacked layout ("blocks.attn.qkv.weight" with a leading
    [layers] axis). Slicing the stack here keeps the decode step a plain
    Python loop over layers — each step traces once per shape rung, so
    scan's compile-time advantage does not apply."""
    import re
    embed = {k: v for k, v in params.items()
             if k.startswith(("wte.", "wpe."))}
    head = {k: v for k, v in params.items() if k.startswith("ln_f.")}
    stacked = {k[len("blocks."):]: v for k, v in params.items()
               if k.startswith("blocks.")
               and not re.match(r"blocks\.\d+\.", k)}
    blocks = []
    if stacked:
        for i in range(cfg.layers):
            blocks.append({rel: v[i] for rel, v in stacked.items()})
    else:
        for i in range(cfg.layers):
            pref = f"blocks.{i}."
            blocks.append({k[len(pref):]: v for k, v in params.items()
                           if k.startswith(pref)})
    return embed, blocks, head


def _qmm(bp, name, x):
    """Weight matmul over a possibly PTQ-quantized decode param dict.

    `quant.ptq.quantize_params` stores an int8 weight under its original
    key with an fp32 per-output-channel scale sibling at `name::scale`.
    When the sibling is absent this is literally `x @ w` — the fp32 path
    traces identically to unquantized code — otherwise the scale factors
    out of the product (`ops.pallas.quant_matmul`)."""
    s = bp.get(name + "::scale")
    if s is None:
        return x @ bp[name]
    from ..ops.pallas.quant_matmul import int8_weight_matmul
    return int8_weight_matmul(x, bp[name], s)


# A K/V pool is a tuple of `layers` layer pools, one array a layer:
# fp32 `[P, page_tokens, heads * head_dim]` (a token's row is its heads
# side by side: whole 128-lane tiles at any head size, so the compiled
# step scatters into the array it was given and gathers from it as it
# lies), or the int8 pair (quant/kv.py) `(data int8 [P, page_tokens,
# heads * head_dim], scale f32 [P, page_tokens, heads])` with one scale
# per (page, row, head). The page axis is 0 on every leaf. The helpers
# below branch on a layer pool's structure at trace time, so every
# paged program serves both pool dtypes from one code path.

def _kv_pool_write(pool, page_idx, offset, rows):
    """Scatter fresh fp32 K/V rows [..., heads, head_dim] into one
    layer's pool at [page_idx, offset]; an int8 pool quantizes the rows
    per (row, head) inside the same executable."""
    flat = rows.shape[:-2] + (-1,)
    if isinstance(pool, tuple):
        from ..quant.kv import quantize_kv
        data, scale = pool
        q, s = quantize_kv(rows)
        return (data.at[page_idx, offset].set(q.reshape(flat)),
                scale.at[page_idx, offset].set(s))
    return pool.at[page_idx, offset].set(rows.reshape(flat))


def _page_address(tables, pos, pt, valid=None):
    """Where position `pos` ([B], or [B, K] for K positions a row) lies
    through the block tables [B, W]: (page id, row in the page). A slot
    past the table reads the table's last entry, and table padding is
    null pages; where `valid` is given and false (a position at or past
    max_seq_len) the address is the null page's, so the write of an
    overrun never lands on live rows."""
    slot = jnp.minimum(pos // pt, tables.shape[1] - 1)
    if pos.ndim == 1:
        page_idx = jnp.take_along_axis(tables, slot[:, None], axis=1)[:, 0]
    else:
        page_idx = jnp.take_along_axis(tables, slot, axis=1)
    if valid is not None:
        page_idx = jnp.where(valid, page_idx, 0)
    return page_idx, pos % pt


def _embed(embed, tok, pos):
    return embed["wte.weight"][tok] + embed["wpe.weight"][pos]


def _ffn(bp, x, eps):
    h2 = _pp_ln(x, bp["ln2.weight"], bp["ln2.bias"], eps)
    m = jax.nn.gelu(_qmm(bp, "fc1.weight", h2) + bp["fc1.bias"],
                    approximate=False)
    return x + _qmm(bp, "fc2.weight", m) + bp["fc2.bias"]


def _serving_block(bp, x, eps, attend):
    """One block of every serving program: ln1 -> qkv -> split, the
    program's own `attend(q, k_new, v_new) -> (o, kept)` over whole rows
    [..., heads * head_dim], the proj residual, the FFN. `kept` is what
    the program keeps of the layer's K/V: its pools after the write
    (step, rollout), or the fresh rows to land later (verify, prefill).

    The math mirrors the pipeline block cores above (same op order as
    F.scaled_dot_product_attention's XLA path: f32 scores, -1e30 mask,
    f32 softmax, exact gelu), so prefill + N steps reproduce the full
    forward within fp32 tolerance — tests/test_decode.py enforces it."""
    h1 = _pp_ln(x, bp["ln1.weight"], bp["ln1.bias"], eps)
    qkv = _qmm(bp, "attn.qkv.weight", h1) + bp["attn.qkv.bias"]
    q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
    o, kept = attend(q, k_new, v_new)
    x = x + _qmm(bp, "attn.proj.weight", o) + bp["attn.proj.bias"]
    with jax.named_scope("mlp"):
        x = _ffn(bp, x, eps)
    return x, kept


def _head(embed, head, x, eps, lens=None):
    """ln_f and the tied embedding's logits; of x [B, T, C], `lens` [B]
    keeps each row's position lens - 1 between the two."""
    with jax.named_scope("head"):
        xf = _pp_ln(x, head["ln_f.weight"], head["ln_f.bias"], eps)
        if lens is not None:
            last = jnp.clip(lens.astype(jnp.int32) - 1, 0, x.shape[1] - 1)
            xf = jnp.take_along_axis(xf, last[:, None, None], axis=1)[:, 0]
        return xf @ embed["wte.weight"].T


def gpt_dense_prefill(cfg: GPTConfig, params, tokens, lens, eps=1e-5):
    """The whole prompt in one parallel pass, dense causal attention:

    gpt_dense_prefill(cfg, params, tokens [B,T] i32, lens [B] i32)
        -> (logits [B,V] at each row's position lens-1,
            k, v    [layers, B, T, heads, head_dim])

    The body of `gpt_paged_fns`' prefill, which lands the panels in pool
    pages, and what tests hold the landed pages against. Rows past
    `lens` compute garbage that causality keeps out of every live row's
    logits."""
    nh, D = cfg.heads, cfg.head_dim
    scale = 1.0 / math.sqrt(D)
    embed, blocks, head = split_decode_params(params, cfg)
    B, T = tokens.shape
    pos = jnp.arange(T, dtype=jnp.int32)
    x = _embed(embed, tokens, pos)
    ks, vs = [], []
    causal = jnp.tril(jnp.ones((T, T), bool))

    def attend(q, k, v):
        q = q.reshape(B, T, nh, D)
        k = k.reshape(B, T, nh, D)
        v = v.reshape(B, T, nh, D)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = s.astype(jnp.float32)
        s = jnp.where(causal[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, -1)
        return o, (k, v)

    for bp in blocks:
        x, (k, v) = _serving_block(bp, x, eps, attend)
        ks.append(k)
        vs.append(v)
    return _head(embed, head, x, eps, lens), jnp.stack(ks), jnp.stack(vs)


def gpt_paged_fns(cfg: GPTConfig, eps: float = 1e-5, page_tokens: int = 16,
                  prefill_name: str = "prefill"):
    """The four pure programs the decode engines run over a PAGED KV
    cache, `(prefill, paged_step, paged_verify, paged_rollout)`, each
    over `pools = (k_pool, v_pool)` as one pytree in and out (a pool:
    layers x [P, page_tokens, heads * head_dim], or the int8 pair).
    `tables` [B, W] int32 are the block tables, unused entries aimed at
    the null page 0; position p of a row lives at page
    tables[b, p // page_tokens], row p % page_tokens, in every program.

    prefill(params, pools, toks [1, R], tables [1, W], n [1])
        -> (logits [1, V], pools)
      Fused prefill-into-pages: `gpt_dense_prefill` over the prompt
      padded to the rung R, each layer's panel cut into whole pages and
      page j landed on tables[0, j] (`write_pages`, one index per page,
      in place), so an admission is a single dispatch and nothing of K
      or V crosses to the host. Rows at or past `n` are written as
      zeros, so rung garbage never enters the pool and a page's tail
      holds nothing stale; table padding aims at the null page, which
      takes whatever falls there. An int8 pool quantizes the pages per
      (row, head) inside the same executable. `logits` is the last
      position's — callers that only want the K/V ignore it. The
      target's admission, the KV-handoff export and the draft model's
      prefill all run it; `prefill_name` is the function's name, and so
      the compiled program's in a device trace (`jit_<name>`): an engine
      that runs two of these (target and draft) names them apart.

    paged_step(params, pools, tables, last_tok [B], cache_len [B])
        -> (logits [B, V], pools)
      The new token's K/V lands at its page via one row scatter per
      layer pool, in place (padded batch rows carry all-null tables, so
      their garbage writes fall into the reserved scratch page);
      attention then reads the pool through the block table
      (`ops.pallas.decode_attention.paged_decode_attention`). One
      executable serves every occupancy of a (batch-rung x page-rung)
      bucket, and capacity growth is just a wider block table, never a
      cache copy.

    paged_verify(params, pools, tables, toks [B, K1], cache_len [B])
        -> (logits [B, K1, V], argmax [B, K1] int32, pools)
      The target side of speculative decoding. Row i of `toks` is the
      token at absolute position `cache_len + i`; `logits[b, i]` is the
      next-token distribution AFTER consuming toks[b, :i+1], so one call
      scores every drafted position at once, and position p attends keys
      0..p, drafted predecessors included. Positions at or past
      max_seq_len redirect their writes to the null page, so padded
      verify rows near the sequence cap never clobber live data. A
      verified-and-accepted token stream is argmax-identical to plain
      incremental decode.

    paged_rollout(params, pools, tables, forced [B, K], cache_len [B])
        -> (drafts [B, K] int32, pools)
      The draft side of speculative decoding: K greedy steps fused into
      ONE executable (`fori_loop` over the step's body), so a scheduler
      tick costs two dispatches (rollout + verify) instead of k + 1.
      Step i consumes one token at position `cache_len + i` —
      `forced[b, i]` where it is >= 0 (a committed token the draft has
      not seen: catch-up), else the previous step's own argmax — and
      records its greedy argmax in `drafts[b, i]`. `forced[:, 0]` must
      be >= 0: the engine always has at least one committed token the
      draft has not consumed. Overruns of max_seq_len write to the null
      page, as in verify.
    """
    if cfg.moe_experts > 0:
        raise NotImplementedError(
            "gpt_paged_fns: MoE blocks have no KV-decode path yet")
    from ..memory.page_allocator import write_pages
    from ..ops.pallas.decode_attention import (gathered_panel, head_mix,
                                               head_scores,
                                               paged_decode_attention)
    D = cfg.head_dim
    nh = cfg.heads
    pt = int(page_tokens)

    @jax.jit
    def block(bp, x, k_layer, v_layer, tables, page_idx, offset, lengths):
        """One block of the step (and of each step of a rollout) over
        its layer's pools: the new rows written in place, then the pool
        attended through the block table. A jit of its own, so the
        step's trace holds the block once and calls it a layer: every
        layer has the same shapes, and an engine traces the step once
        a (batch rung, page rung) — forty times at a hundred slots,
        most of what its warm-up costs. The compiler inlines the calls;
        the pools are still written in place."""
        B = x.shape[0]

        def attend(q, k_new, v_new):
            q = q.reshape(B, nh, D)
            k_new = k_new.reshape(B, nh, D)
            v_new = v_new.reshape(B, nh, D)
            with jax.named_scope("pool_write"):
                k = _kv_pool_write(k_layer, page_idx, offset, k_new)
                v = _kv_pool_write(v_layer, page_idx, offset, v_new)
            with jax.named_scope("attention"):  # "page_gather" inside it
                o = paged_decode_attention(q, k, v, tables, lengths)
            return o.reshape(B, -1), (k, v)

        x, (k_layer, v_layer) = _serving_block(bp, x, eps, attend)
        return x, k_layer, v_layer

    def one_token(embed, blocks, head, pools, tables, tok, pos, valid=None):
        """Every row one token on: `tok` [B] at position `pos` [B]."""
        x = _embed(embed, tok, pos)
        page_idx, offset = _page_address(tables, pos, pt, valid)
        lengths = pos + 1                 # the row just written is live
        k_pool, v_pool = list(pools[0]), list(pools[1])
        for i, bp in enumerate(blocks):
            x, k_pool[i], v_pool[i] = block(
                bp, x, k_pool[i], v_pool[i], tables, page_idx, offset,
                lengths)
        return _head(embed, head, x, eps), (tuple(k_pool), tuple(v_pool))

    def paged_step(params, pools, tables, last_tok, cache_len):
        parts = split_decode_params(params, cfg)
        # live rows never overrun (the engine finishes a stream at the
        # cap), so the step clips and has no redirect to pay for
        pos = jnp.clip(cache_len.astype(jnp.int32), 0,
                       cfg.max_seq_len - 1)
        return one_token(*parts, pools, tables, last_tok, pos)

    def paged_rollout(params, pools, tables, forced, cache_len):
        parts = split_decode_params(params, cfg)
        B, K = forced.shape
        base = cache_len.astype(jnp.int32)

        def step(i, carry):
            prev, drafts, pools = carry
            want = jax.lax.dynamic_slice_in_dim(forced, i, 1, axis=1)[:, 0]
            tok = jnp.where(want >= 0, want, prev)
            pos = base + i
            logits, pools = one_token(
                *parts, pools, tables, tok,
                jnp.minimum(pos, cfg.max_seq_len - 1),
                pos < cfg.max_seq_len)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            drafts = jax.lax.dynamic_update_slice_in_dim(
                drafts, nxt[:, None], i, axis=1)
            return nxt, drafts, pools

        _, drafts, pools = jax.lax.fori_loop(
            0, K, step, (forced[:, 0], jnp.zeros((B, K), jnp.int32),
                         (tuple(pools[0]), tuple(pools[1]))))
        return drafts, pools

    def paged_verify(params, pools, tables, toks, cache_len):
        embed, blocks, head = split_decode_params(params, cfg)
        k_pool, v_pool = pools
        B, K1 = toks.shape
        pos = cache_len.astype(jnp.int32)[:, None] \
            + jnp.arange(K1, dtype=jnp.int32)[None]          # [B, K1]
        valid = pos < cfg.max_seq_len
        pos_c = jnp.minimum(pos, cfg.max_seq_len - 1)
        x = _embed(embed, toks, pos_c)
        page_idx, offset = _page_address(tables, pos_c, pt, valid)
        kcap = tables.shape[1] * pt
        # Attention is split prefix/window so the pool gathers stand
        # before every write: the committed prefix (rows < cache_len) is
        # gathered from each layer's pool as the call found it, while
        # the K1 in-flight tokens attend each other directly from this
        # dispatch's fresh K/V under an in-window causal triangle. Score
        # layout per query is [prefix rows | window rows]; one softmax
        # over the concat keeps the math identical to the single-gather
        # formulation. Rows stay whole ([.., heads * head_dim]) through
        # scores and sums, as in the step.
        keys_all = [gathered_panel(p, tables) for p in k_pool]
        vals_all = [gathered_panel(p, tables) for p in v_pool]
        prefix_live = jnp.arange(kcap, dtype=jnp.int32)[None, :] \
            < cache_len.astype(jnp.int32)[:, None]            # [B, kcap]
        prefix_live = prefix_live[:, None, None, :]           # [B,1,1,kcap]
        win = jnp.arange(K1, dtype=jnp.int32)
        win_causal = (win[None, :] <= win[:, None])[None, :, None]  # [1,K1,1,K1]
        k_news, v_news = [], []
        for i, bp in enumerate(blocks):
            def attend(q, k_new, v_new, keys=keys_all[i], vals=vals_all[i]):
                sp = jnp.where(prefix_live, head_scores(q, keys, nh),
                               -1e30)                         # [B,K1,nh,kcap]
                sw = jnp.where(win_causal, head_scores(q, k_new, nh), -1e30)
                s = jnp.concatenate([sp, sw], axis=-1)
                p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
                o = head_mix(p[..., :kcap], vals) \
                    + head_mix(p[..., kcap:], v_new)          # [B, K1, C]
                return o, (k_new, v_new)

            x, (k_new, v_new) = _serving_block(bp, x, eps, attend)
            k_news.append(k_new)
            v_news.append(v_new)
        # the fresh K/V of every layer (page_idx/offset are
        # layer-invariant); accepted rows persist, rejected rows become
        # garbage above the rolled-back cache_len, overruns hit page 0
        k_pool = tuple(
            _kv_pool_write(p, page_idx, offset, r.reshape(B, K1, nh, D))
            for p, r in zip(k_pool, k_news))
        v_pool = tuple(
            _kv_pool_write(p, page_idx, offset, r.reshape(B, K1, nh, D))
            for p, r in zip(v_pool, v_news))
        logits = _head(embed, head, x, eps)
        amax = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, amax, (k_pool, v_pool)

    def prefill(params, pools, toks, tables, n):
        k_pool, v_pool = pools
        R = toks.shape[1]
        W = tables.shape[1]
        logits, k, v = gpt_dense_prefill(cfg, params, toks, n, eps)
        live = (jnp.arange(R, dtype=jnp.int32) < n[0])[:, None, None]

        def pages(panel):              # [1, R, nh, D] -> [W, pt, nh * D]
            rows = jnp.where(live, panel[0], 0.0)
            rows = jnp.pad(rows, ((0, W * pt - R), (0, 0), (0, 0)))
            if isinstance(k_pool[0], tuple):
                from ..quant.kv import quantize_kv
                q, s = quantize_kv(rows)
                return q.reshape(W, pt, -1), s.reshape(W, pt, -1)
            return rows.reshape(W, pt, -1)

        def land(pool, panels):
            return tuple(write_pages(p, pages(panels[i]), tables[0])
                         for i, p in enumerate(pool))

        return logits, (land(k_pool, k), land(v_pool, v))

    prefill.__name__ = prefill.__qualname__ = prefill_name
    return prefill, paged_step, paged_verify, paged_rollout
