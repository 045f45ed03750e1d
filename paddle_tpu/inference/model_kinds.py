"""What the decode engine asks of a model: one object per model kind.

`DecodeEngine` keeps the scheduler, the page allocator, block tables,
the prefix trie, the ladders, sampling and the spans; everything that
knows an architecture is behind the object here: the step and the
prefill-into-pages functions, the page pools as ONE pytree (made,
described, copied a page at a time), bytes a page, the positions limit,
the vocabulary, a fingerprint, and which of the engine's optional
features the kind has. The engine threads `pools` through every
dispatch, donated, and never looks inside.

    step(params, pools, tables [B, W], last_tok [B], cache_len [B])
        -> (logits [B, V] float32, pools)
    prefill(params, pools, toks [1, R], tables [1, W], n [1])
        -> (logits [1, V] float32, pools)

The logits stay on the device unless a row samples: a tick of greedy
rows stores the engine's `greedy_picks` of them in its slots' last
tokens, on the device, where the next step's `last_tok` is gathered
from; the host pulls one id a slot, after that step is dispatched. The
signatures above know nothing of it. A kind that
has speculative decoding also gives `verify_fn` and `rollout_fn`, which
`SpecDecodeEngine` asks of its target and of its draft.

**State that lives by slot.** A kind whose streams keep state that is
no page (a recurrent state of fixed size) sets `slot_state = True`. Its
pools then hold, beside the pages, arrays with a SLOT axis of
`slots + 1` entries (`pools_sds` / `pools_zeros` take `slots`; the last
entry is the null slot), and its functions take the slot:

    step(params, pools, tables, last_tok, cache_len, slots [B])
    prefill(params, pools, toks, tables, n, slot [])

The engine hands the step the slot of every row (a padding row carries
the slot COUNT, i.e. the null slot) and the prefill the slot it gave
the request. Who owns a slot's state when:

* *admission* OVERWRITES it: a prefill runs its sequence from an empty
  state and writes the state after its last position into its slot,
  whatever was there (a reused slot never sees the last stream's; a
  resume after preemption is such a prefill over prompt + generated);
* *a step* advances the slots of its live rows in place, and with its
  padding rows only the null slot;
* *finish and preemption* ABANDON it: the engine frees the slot and
  keeps nothing of its state (`state_bytes` of the pool stay
  allocated); a step still in flight may write the abandoned slot once
  more, before, in device order, whatever admission takes it next;
* it is never shared: the engine builds no prefix trie for such a kind
  (a page hit without the state at that boundary would serve wrong
  tokens), so no copy-on-write and nothing stashed at preemption.

`slot_bytes()` of such a kind counts the state with the pages of a
slot; `state_bytes(slots)` is the state alone. The `gpt` and `axk1`
kinds have no such state and their signatures carry no slot. State by
slot need not be a recurrence: the `afmoe` kind keeps CACHE ROWS so, a
ring of the last `sliding_window` positions of each window layer.

Kinds: `gpt` (`GPTKind`: a K and a V pool, each one array a layer `[P,
page_tokens, heads * head_dim]`, float32 or int8; its programs are the
four of the one builder `models.gpt.gpt_paged_fns`, returned as they
are) and `axk1` (`AXK1Kind`: one latent pool a layer, bfloat16, plus the
routed-assignment counters; `models.axk1.axk1_paged_fns`). Both keep
the page axis at 0 on every
page-holding leaf and a token's row whole and lane-dense, so the
compiled step writes rows into the arrays it was given and
`memory.page_allocator`'s page ops serve either. `kimi_linear`
(`KimiLinearKind`, an `AXK1Kind` with slot state) holds latent pages for
its MLA layers and, by slot, the recurrent and convolution state of its
KDA layers (`models.kimi_linear.kimi_linear_paged_fns`). `afmoe`
(`AfmoeKind`, slot state too) holds K and V pages, bfloat16, for its
full-attention layers and, by slot, a ring of `sliding_window` K and V
rows for each window layer, in pools of the same page shape
(`models.afmoe.afmoe_paged_fns`). The manifest of a
`save_for_decode` artifact names its kind under `"model_kind"`; one
without the key is a GPT. Each kind names its config and its model
class (`config_cls`, `model_cls`): `for_config` and `for_model` look
both up in `KINDS`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core import flags as _flags
from ..memory.page_allocator import copy_page
from ..models.afmoe import (Afmoe, AfmoeConfig, afmoe_paged_fns,
                            afmoe_pools_sds)
from ..models.axk1 import (AXK1, AXK1Config, axk1_paged_fns,
                           latent_pools_sds)
from ..models.gpt import GPT, GPTConfig, gpt_paged_fns
from ..models.kimi_linear import (KimiLinear, KimiLinearConfig,
                                  kimi_linear_paged_fns,
                                  kimi_linear_pools_sds)
from ..quant.kv import kv_pool_sds, kv_pool_zeros, validate_kv_dtype
from .errors import ERR_FAILED_PRECONDITION, TypedServeError

_PAGE_TOKENS_ENV = "PADDLE_TPU_DECODE_PAGE_TOKENS"


def _fingerprint(spec: Dict, params: Dict) -> str:
    spec = dict(spec, params=sorted(
        (str(k), list(v.shape), str(np.dtype(v.dtype)))
        for k, v in params.items()))
    return hashlib.sha1(
        json.dumps(spec, sort_keys=True).encode()).hexdigest()[:16]


def unsupported(kind: str, feature: str, roadmap: str):
    """The typed refusal of a feature a model kind does not have yet."""
    return TypedServeError(
        ERR_FAILED_PRECONDITION,
        f"model kind {kind!r} does not support {feature} yet "
        f"(ROADMAP.md {roadmap})")


# ------------------------------------------------------------------ gpt


def kv_page_bytes(cfg: GPTConfig, page_tokens: int,
                  kv_dtype: str = "float32") -> int:
    """HBM bytes one K+V page occupies at the pool dtype. The int8 pool
    (quant/kv.py) pays 1 byte per element plus one fp32 scale per
    (token row, head) — 1 + 4/head_dim bytes/element vs 4 for fp32."""
    rows = cfg.layers * 2 * int(page_tokens) * cfg.heads
    if validate_kv_dtype(kv_dtype) == "int8":
        return rows * cfg.head_dim + rows * 4
    return rows * cfg.head_dim * 4


# How a `gpt` page lies in a handoff payload, folded into the
# fingerprint: an engine from before the pools were one array a layer
# (leaves `[layers, n, page_tokens, heads, head_dim]`, page axis 1) has
# another fingerprint, so its payload is refused as any other model's
# would be, with the typed FAILED_PRECONDITION of the compat check.
KV_PAGE_LAYOUT = "layer x [page, token, head*dim]"


def kv_fingerprint(cfg: GPTConfig, eps: float, params: Dict) -> str:
    """16-hex-char identity of (config, eps, page layout, parameter
    names/shapes/dtypes). Two engines with equal fingerprints run the
    same forward over the same weights *layout* into pages of the same
    layout, so their KV pages are interchangeable — the model-identity
    leg of the KV-handoff compat contract. Weight VALUES are deliberately not hashed (hashing GBs of
    params per engine start is not worth catching an operator loading
    two different checkpoints of the same architecture under one
    fingerprint — the serve artifact prefix already pins the weights)."""
    return _fingerprint({"config": dataclasses.asdict(cfg),
                         "eps": float(eps),
                         "page_layout": KV_PAGE_LAYOUT}, params)


class GPTKind:
    """`models.gpt`: pools `(k_pool, v_pool)`, each a tuple of `layers`
    layer pools `[P, page_tokens, heads * head_dim]` float32, or the
    int8 `(data, scale)` pair of `quant.kv` a layer. One layout for
    every `GPTConfig`: a row of `heads * head_dim` lanes fills whole
    tiles at any head size, and one array a layer lets the step's row
    scatter and the admission's page scatter update their operand in
    place (a stacked `[layers, ..]` pool made the compiler slice a
    layer out, and `[.., heads, head_dim]` rows made it copy every
    pool into another layout and back, each step)."""

    name = "gpt"
    config_cls = GPTConfig
    model_cls = GPT

    def __init__(self, cfg: GPTConfig, eps: Optional[float] = None):
        self.cfg = cfg
        self.eps = 1e-5 if eps is None else float(eps)
        self.vocab_size = cfg.vocab_size
        self.max_seq_len = cfg.max_seq_len

    @classmethod
    def from_model(cls, model, eps=None):
        return cls(model.cfg, model.ln_f._epsilon if eps is None else eps)

    @classmethod
    def from_manifest(cls, meta):
        return cls(GPTConfig(**meta["config"]), meta.get("eps"))

    def manifest(self):
        return {"config": dataclasses.asdict(self.cfg), "eps": self.eps}

    def default_page_tokens(self):
        return int(_flags.env_value(_PAGE_TOKENS_ENV))

    def pool_dtype(self, kv_dtype, **features):
        """The pool dtype this engine runs (every optional feature of
        the engine exists for this kind)."""
        return validate_kv_dtype(
            kv_dtype if kv_dtype is not None
            else _flags.env_value("PADDLE_TPU_DECODE_KV_DTYPE"))

    def _fns(self, page_tokens, prefill_name="prefill"):
        return gpt_paged_fns(self.cfg, self.eps, page_tokens, prefill_name)

    def prefill_fn(self, page_tokens, name="prefill"):
        return self._fns(page_tokens, name)[0]

    def step_fn(self, page_tokens):
        return self._fns(page_tokens)[1]

    def verify_fn(self, page_tokens):
        """The target side of speculative decoding (`SpecDecodeEngine`):
        verify(params, pools, tables [B, W], toks [B, K1], cache_len [B])
        -> (logits [B, K1, V], argmax [B, K1], pools)."""
        return self._fns(page_tokens)[2]

    def rollout_fn(self, page_tokens):
        """The draft side: rollout(params, pools, tables [B, W],
        forced [B, K], cache_len [B]) -> (drafts [B, K], pools)."""
        return self._fns(page_tokens)[3]

    def _pool_shape(self, num_pages, page_tokens):
        c = self.cfg
        return (c.layers, int(num_pages), int(page_tokens), c.heads,
                c.head_dim)

    def pools_sds(self, num_pages, page_tokens, kv_dtype):
        p = kv_pool_sds(self._pool_shape(num_pages, page_tokens), kv_dtype)
        return (p, p)

    def pools_zeros(self, num_pages, page_tokens, kv_dtype):
        shape = self._pool_shape(num_pages, page_tokens)
        return (kv_pool_zeros(shape, kv_dtype),
                kv_pool_zeros(shape, kv_dtype))

    # K and V, every layer: one executable covers all the copies
    copy_page = staticmethod(copy_page)

    def page_bytes(self, page_tokens, kv_dtype):
        return kv_page_bytes(self.cfg, page_tokens, kv_dtype)

    def slot_bytes(self):
        return self.page_bytes(self.max_seq_len, "float32")

    def fingerprint(self, params):
        return kv_fingerprint(self.cfg, self.eps, params)

    def counters(self, pools):
        return {}


# ----------------------------------------------------------------- axk1


class AXK1Kind:
    """`models.axk1`: one latent pool a layer plus the device-side
    routed-assignment counters (`latent_pools_sds`), bfloat16 as the
    config says. Not in this kind yet, each a typed refusal at
    construction: speculative decoding, int8 pages, host tiering, KV
    handoff (ROADMAP R3)."""

    name = "axk1"
    DEFAULT_PAGE_TOKENS = 128   # 640 bfloat16 lanes a row: 160 KB a page
    config_cls = AXK1Config
    model_cls = AXK1
    pages = "latent pages"      # what its refusals call a page
    paged_fns = staticmethod(axk1_paged_fns)
    roadmap = "R3"              # where what it refuses is queued

    def __init__(self, cfg, eps: Optional[float] = None):
        if eps is not None and float(eps) != float(cfg.rms_norm_eps):
            raise ValueError(f"{type(self).__name__}: eps is the config's "
                             f"rms_norm_eps")
        self.cfg = cfg
        self.eps = float(cfg.rms_norm_eps)
        self.vocab_size = cfg.vocab_size
        self.max_seq_len = cfg.max_seq_len

    @classmethod
    def from_model(cls, model, eps=None):
        return cls(model.cfg, eps)

    @classmethod
    def from_manifest(cls, meta):
        return cls(cls.config_cls(**meta["config"]))

    def manifest(self):
        return {"config": dataclasses.asdict(self.cfg), "eps": self.eps}

    def default_page_tokens(self):
        """The flag where it is set; else pages of 128 tokens: at the
        flag's default of 16 a page is 18 KB and the attention kernel's
        grid has eight times the cells."""
        if os.environ.get(_PAGE_TOKENS_ENV, "").strip():
            return int(_flags.env_value(_PAGE_TOKENS_ENV))
        return self.DEFAULT_PAGE_TOKENS

    def pool_dtype(self, kv_dtype, host_pages=0, handoff=False,
                   speculative=False):
        if speculative:
            raise unsupported(self.name, "speculative decoding "
                              "(SpecDecodeEngine)", self.roadmap)
        if kv_dtype not in (None, self.cfg.dtype):
            raise unsupported(self.name, f"kv_dtype={kv_dtype!r} "
                              f"({self.pages} are {self.cfg.dtype})",
                              self.roadmap)
        if host_pages:
            raise unsupported(self.name, f"host tiering of {self.pages} "
                              f"(host_pages)", self.roadmap)
        if handoff:
            raise unsupported(self.name, f"KV handoff of {self.pages}",
                              self.roadmap)
        return self.cfg.dtype

    def step_fn(self, page_tokens):
        return self.paged_fns(self.cfg, page_tokens)[1]

    def prefill_fn(self, page_tokens, name="prefill"):
        return self.paged_fns(self.cfg, page_tokens, prefill_name=name)[0]

    def pools_sds(self, num_pages, page_tokens, kv_dtype):
        return latent_pools_sds(self.cfg, num_pages, page_tokens)

    def pools_zeros(self, num_pages, page_tokens, kv_dtype, **slots):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.pools_sds(num_pages, page_tokens, kv_dtype,
                                           **slots))

    @staticmethod
    def copy_page(pools, src, dst):
        return dict(pools, latent=copy_page(pools["latent"], src, dst))

    def page_bytes(self, page_tokens, kv_dtype):
        c = self.cfg
        return self.latent_layers * int(page_tokens) * c.pool_row_width \
            * jnp.dtype(c.dtype).itemsize

    @property
    def latent_layers(self):
        return self.cfg.num_hidden_layers

    def slot_bytes(self):
        return self.page_bytes(self.max_seq_len, None)

    def fingerprint(self, params):
        return _fingerprint({"kind": self.name,
                             "config": dataclasses.asdict(self.cfg)},
                            params)

    def counters(self, pools):
        """{"routed": [expert layers][held] assignments, "routed_tokens":
        tokens through the routers}: one device read, for `stats()`."""
        if pools is None:
            return {}
        return {"routed": np.asarray(pools["routed"]).tolist(),
                "routed_tokens": int(pools["routed_tokens"])}


# ---------------------------------------------------------- kimi_linear


class KimiLinearKind(AXK1Kind):
    """`models.kimi_linear`: two kinds of state in one pools pytree.
    Latent pages (as `axk1`'s, bfloat16, page axis 0) for the MLA
    layers ONLY, and for the KDA layers state that lives by slot: a
    recurrent state `[slots + 1, H, K, V]` float32 and the convolution's
    last inputs `[slots + 1, 3 x 3 x width]`, one array a layer each,
    updated in place; plus the routed counters. The module docstring
    has the seam's contract for slot state. Refused, typed, at
    construction: what `axk1` refuses; prefix reuse is off (ROADMAP
    R5 keeps state snapshots at page boundaries)."""

    name = "kimi_linear"
    config_cls = KimiLinearConfig
    model_cls = KimiLinear
    paged_fns = staticmethod(kimi_linear_paged_fns)
    roadmap = "R5"
    slot_state = True

    def pools_sds(self, num_pages, page_tokens, kv_dtype, slots):
        return kimi_linear_pools_sds(self.cfg, num_pages, page_tokens, slots)

    @property
    def latent_layers(self):
        return len(self.cfg.mla_index)

    def state_bytes(self, slots):
        """Bytes of the state pool of an engine of `slots` slots (its
        null slot counted)."""
        return (int(slots) + 1) * self.cfg.state_slot_bytes

    def slot_bytes(self):
        return self.page_bytes(self.max_seq_len, None) \
            + self.cfg.state_slot_bytes


# ---------------------------------------------------------------- afmoe


class AfmoeKind(AXK1Kind):
    """`models.afmoe`: two classes of cache in one pools pytree. K and V
    pages (bfloat16, `[P, page_tokens, kv heads x head_dim]`, page axis
    0) for the full-attention layers ONLY, addressed through the block
    table; and for each window layer a RING of `sliding_window` K and V
    rows that lives by slot, `sliding_window / page_tokens` pages of a
    pool `[(slots + 1) x ring pages, page_tokens, ..]` of its own, the
    row of position p at `p mod sliding_window`; plus the routed
    counters. The module docstring has the seam's contract for slot
    state: an admission overwrites the ring, a step advances it in
    place, a finish abandons it. Refused, typed, at construction: what
    `axk1` refuses; prefix reuse is off (a page hit without the ring at
    that boundary would serve wrong tokens; ROADMAP R2)."""

    name = "afmoe"
    config_cls = AfmoeConfig
    model_cls = Afmoe
    pages = "K/V pages"
    paged_fns = staticmethod(afmoe_paged_fns)
    roadmap = "R2"
    slot_state = True

    def pools_sds(self, num_pages, page_tokens, kv_dtype, slots):
        return afmoe_pools_sds(self.cfg, num_pages, page_tokens, slots)

    @staticmethod
    def copy_page(pools, src, dst):
        return dict(pools, k=copy_page(pools["k"], src, dst),
                    v=copy_page(pools["v"], src, dst))

    def page_bytes(self, page_tokens, kv_dtype):
        c = self.cfg
        return len(c.full_index) * 2 * int(page_tokens) * c.kv_width \
            * jnp.dtype(c.dtype).itemsize

    def state_bytes(self, slots):
        """Bytes of the rings of an engine of `slots` slots (its null
        slot counted)."""
        return (int(slots) + 1) * self.cfg.ring_slot_bytes

    def slot_bytes(self):
        return self.page_bytes(self.max_seq_len, None) \
            + self.cfg.ring_slot_bytes


KINDS = {kind.name: kind
         for kind in (GPTKind, AXK1Kind, KimiLinearKind, AfmoeKind)}


def _kind_of(what, attr):
    """The kind whose `attr` ("config_cls" | "model_cls") `what` is an
    instance of."""
    for kind in KINDS.values():
        if isinstance(what, getattr(kind, attr)):
            return kind
    return None


def for_config(cfg, eps=None):
    kind = _kind_of(cfg, "config_cls")
    if kind is None:
        raise TypeError(f"no decode model kind for config "
                        f"{type(cfg).__name__}")
    return kind(cfg, eps)


def for_model(model, eps=None):
    """The kind of a model of the framework; a model that is none of
    the kinds' classes is served as a GPT (a subclass of it, or a layer
    that has its `cfg` and `ln_f`), as before the kinds had names."""
    return (_kind_of(model, "model_cls") or GPTKind).from_model(model, eps)


def from_manifest(meta):
    """The kind a `save_for_decode` manifest names; one without
    `"model_kind"` is a GPT (every artifact before the key existed)."""
    name = meta.get("model_kind", GPTKind.name)
    if name not in KINDS:
        raise ValueError(f"decode artifact of unknown model kind {name!r} "
                         f"(known: {sorted(KINDS)})")
    return KINDS[name].from_manifest(meta)
