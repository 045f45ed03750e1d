"""Seeded RNG built on jax's splittable PRNG.

TPU-native analog of /root/reference/paddle/fluid/framework/generator.cc and
pybind/generator_py.cc (global + per-device generators). The reference keeps
stateful Philox generators per device; on TPU the idiomatic design is a
*splittable functional* key — we keep a small stateful wrapper so eager code
gets fresh randomness per call (dygraph parity) while jitted code threads keys
explicitly (`split_key`).
"""
from __future__ import annotations

import threading

import jax
import numpy as np


class Generator:
    """Stateful wrapper over a jax PRNG key chain."""

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._key = None        # materialised lazily: creating a key at
        self._offset = 0        # import would initialise the XLA backend
        self._replay = 0
        return self

    def _ensure_key(self):
        if self._key is None:
            # concrete even when first asked under someone's trace
            with jax.ensure_compile_time_eval():
                self._key = jax.random.key(self._seed)
                for _ in range(getattr(self, "_replay", 0)):
                    self._key, _ = jax.random.split(self._key)
            self._replay = 0

    @property
    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        """Return a fresh key; advances internal state (eager use only).
        Under someone's trace with no `key_scope` (a layer constructed
        inside `jax.eval_shape`, say) the split is evaluated at trace
        time, so the chain stays concrete: a traced key kept here would
        outlive its trace and fail the next eager draw with an
        escaped-tracer error."""
        with self._lock:
            self._ensure_key()
            if jax.core.trace_ctx.is_top_level():
                self._key, sub = jax.random.split(self._key)
            else:
                with jax.ensure_compile_time_eval():
                    self._key, sub = jax.random.split(self._key)
            self._offset += 1
            return sub

    def get_state(self):
        return {"seed": self._seed, "offset": self._offset}

    def set_state(self, state):
        # record only; the chain replays inside _ensure_key so restoring a
        # checkpoint before fleet.init keeps the backend untouched
        self.manual_seed(state["seed"])
        self._offset = state["offset"]
        self._replay = state["offset"]


_default_generator = Generator(np.random.randint(0, 2**31 - 1))

# Functional key scope: inside jit-traced code (functional_call / train step)
# randomness must derive from an explicit traced key, not the eager global
# generator (which would bake a constant into the compiled program). A scope
# holds a mutable key cell that next_key() splits from while active.
_scope = threading.local()


class key_scope:
    """`with key_scope(step_key): ...` — eager random ops inside draw
    deterministic splits of `step_key` (thread each step's key explicitly)."""

    def __init__(self, key):
        self._cell = [key]

    def __enter__(self):
        stack = getattr(_scope, "stack", None)
        if stack is None:
            stack = _scope.stack = []
        stack.append(self._cell)
        return self

    def __exit__(self, *exc):
        _scope.stack.pop()
        return False


def seed(s: int):
    """paddle.seed parity: reseed the global generator (and numpy for loaders)."""
    _default_generator.manual_seed(s)
    np.random.seed(s % (2**32))
    return _default_generator


def default_generator() -> Generator:
    return _default_generator


def next_key():
    stack = getattr(_scope, "stack", None)
    if stack:
        cell = stack[-1]
        cell[0], sub = jax.random.split(cell[0])
        return sub
    return _default_generator.next_key()


def split_key(key, num: int = 2):
    return jax.random.split(key, num)


def get_rng_state():
    return _default_generator.get_state()


def set_rng_state(state):
    _default_generator.set_state(state)
