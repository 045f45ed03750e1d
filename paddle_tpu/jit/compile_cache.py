"""Depth-invariant-compilation support: persistent XLA compile cache,
explicit AOT warmup, and a retrace guard.

Three small pieces shared by the hapi single-device train step and the
fleet ``CompiledTrainStep`` (SPMD / pipeline / explicit-DP shard_map — all
strategy paths funnel through ``CompiledTrainStep.step``):

* ``setup_compilation_cache()`` makes sure jax's persistent compilation
  cache has a directory, so a recompile of an identical HLO module is a
  disk read, not an XLA run. Placement is jax's own:
  ``JAX_COMPILATION_CACHE_DIR`` (or a ``jax_compilation_cache_dir`` the
  caller configured) is left exactly as it is; only when neither is set
  does the cache go to ``.jax_cache/`` at the root of this checkout — a
  fixed path, because the path is part of what a later run must find
  again. ``JAX_ENABLE_COMPILATION_CACHE=false`` disables it.
* ``aot_compile(jitted, *args)`` replaces the first-step implicit compile
  with an explicit ``.lower().compile()``, timed and reported through
  ``paddle_tpu.profiler.record_compile`` with a cache hit/miss verdict
  (detected by diffing the cache directory around the compile).
* ``RetraceGuard`` fingerprints the (shape, dtype, sharding) signature of
  the step inputs; a mid-run change emits ONE structured warning naming
  the input that changed instead of silently recompiling.
  ``PADDLE_TPU_RETRACE=error`` escalates to ``RetraceError`` for CI;
  ``=off`` silences the warning (the recompile still happens).
"""
from __future__ import annotations

import os
import time
import warnings
from typing import Any, Dict, Optional, Tuple

from ..core import flags as _flags

__all__ = ["setup_compilation_cache", "suspend_compilation_cache",
           "cache_dir", "aot_compile", "AotCache",
           "RetraceGuard", "RetraceError", "RetraceWarning"]

# the one in-code default: <checkout>/.jax_cache (git-ignored)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_wired = [False]        # thresholds + default dir applied once per process
_suspended = [False]    # this module switched the cache off (CPU meshes)


def _reset_jax_cache():
    """jax builds its cache object lazily on the first compile and then
    never looks at the config again; drop it so a changed setting takes
    effect."""
    from jax.experimental.compilation_cache import compilation_cache as _cc

    _cc.reset_cache()


def cache_dir() -> Optional[str]:
    """Directory jax's persistent cache is using, or None when it is
    disabled."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir


def setup_compilation_cache() -> Optional[str]:
    """Idempotently wire jax's persistent compilation cache; returns the
    active cache directory (None when disabled)."""
    import jax

    if _suspended[0]:
        jax.config.update("jax_enable_compilation_cache", True)
        _suspended[0] = False
        _reset_jax_cache()
    if not _wired[0]:
        _wired[0] = True
        # Default thresholds skip "cheap" (sub-second / small) compiles —
        # most of a serving ladder; cache everything instead.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        if jax.config.jax_compilation_cache_dir is None:
            jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
        _reset_jax_cache()
    return cache_dir()


def _cache_listing(d: Optional[str]) -> Optional[set]:
    if d is None:
        return None
    try:
        return set(os.listdir(d))
    except OSError:
        return set()        # jax creates the directory on its first write


def suspend_compilation_cache() -> None:
    """Switch the persistent cache off (until the next
    ``setup_compilation_cache`` call). Used for compiles that must not be
    served from disk — deserializing a multi-device executable on the CPU
    backend corrupts the heap (observed with forced-host-device meshes),
    so those compiles opt out via ``aot_compile(use_cache=False)``. The
    one caller gates it on the CPU backend; a TPU never gets here."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return
    jax.config.update("jax_enable_compilation_cache", False)
    _suspended[0] = True
    _reset_jax_cache()


def aot_compile(jitted, *args, label: str = "step", use_cache: bool = True,
                **kwargs) -> Tuple[Any, Dict[str, Any]]:
    """Explicit ``jitted.lower(*args).compile()`` with timing + cache stats.

    Returns ``(compiled_executable, stats)`` where stats holds ``label``,
    ``compile_s`` and ``cache`` ("hit" | "miss" | "off"). The executable
    must be called directly (lowering does NOT seed the jit wrapper's own
    in-memory cache). Also records the compile via
    ``paddle_tpu.profiler.record_compile`` so bench/tools can report it.
    ``use_cache=False`` detaches the persistent cache for this compile
    (see :func:`suspend_compilation_cache`)."""
    if use_cache:
        d = setup_compilation_cache()
    else:
        suspend_compilation_cache()
        d = None
    before = _cache_listing(d)
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **kwargs).compile()
    dt = time.perf_counter() - t0
    if before is None:
        cache = "off"
    else:
        after = _cache_listing(d)
        cache = "miss" if after is None or (after - before) else "hit"
    stats = {"label": label, "compile_s": round(dt, 4), "cache": cache}
    from .. import profiler

    profiler.record_compile(label, dt, cache)
    from ..observability import tracez as _tracez

    _tracez.RING.complete(f"compile:{label}", t0, t0 + dt,
                          {"cache": cache})
    return compiled, stats


class _ProfiledExecutable:
    """The per-executable dispatch hook shared by tracez and profilez.

    Wraps one compiled executable, with two ways to call it.
    ``exe(*args)`` is timed twice — the call itself (JAX dispatches
    asynchronously, so this is host dispatch cost) and
    ``block_until_ready`` on the outputs (device execution) — for the
    call sites that read the outputs on the host right after
    dispatching: blocking there moves the wait, it does not add one.
    ``exe.dispatch(*args)`` returns as soon as the program is enqueued,
    for the one caller that has host work to do meanwhile (the decode
    engine's tick); whoever reads an output waits for it. Either way
    the call lands in the tracez event ring (one live ``exec:<label>``
    span per dispatch — the whole call, so dispatch -> ready for
    ``exe(...)`` and the dispatch alone for ``dispatch`` — and a
    profiler annotation too whenever a profiler session is on) and in
    the profilez ``paddle_tpu_exec_*`` aggregates, keyed by the owning
    cache's label (``dispatch`` has no block time to give). A poisoned
    dispatch is NOT re-raised from the hook — it surfaces at the
    caller's read with its original traceback, exactly as without the
    wrapper.
    """

    __slots__ = ("_exe", "_label", "_span_name", "_donate")

    def __init__(self, exe, label: str, donate_argnums: Tuple[int, ...]):
        self._exe = exe
        self._label = label
        self._span_name = f"exec:{label}"
        self._donate = donate_argnums

    def __getattr__(self, name):      # cost_analysis() etc. pass through
        return getattr(self._exe, name)

    def _call(self, args, wait: bool):
        import jax

        from ..observability import profilez as _profilez
        from ..observability import tracez as _tracez

        donated = 0
        for i in self._donate:
            if i < len(args):
                donated += int(getattr(args[i], "nbytes", 0) or 0)
        with _tracez.RING.span(self._span_name) as span:
            out = self._exe(*args)
            t1 = time.perf_counter()
            if wait:
                try:
                    jax.block_until_ready(out)
                except Exception:
                    pass               # deferred failure: caller's read
        _profilez.PROFILER.observe(self._label, t1 - span.t0,
                                   span.t1 - t1 if wait else 0.0, donated)
        return out

    def __call__(self, *args):
        return self._call(args, wait=True)

    def dispatch(self, *args):
        """The call without the wait."""
        return self._call(args, wait=False)


class AotCache:
    """Keyed cache of AOT-compiled executables — the serving bucket ladder's
    compile boundary.

    One executable per input-shape signature; a miss goes through
    :func:`aot_compile` (and is therefore recorded via
    ``profiler.record_compile``), a hit is a dict lookup with no jax
    dispatch-cache probe at all. The no-new-compiles-after-warmup property
    the serving engine asserts is exactly "every steady-state key is
    already in this dict". Thread-safe; a per-key pending event gives
    concurrent batch workers once-semantics (no duplicated XLA run)
    while the compile itself happens *outside* the map lock, so a cold
    bucket compiling never blocks hits on warmed buckets (tsan-lite
    flagged the old compile-under-lock hold as TPR102).

    Cached executables are returned wrapped in
    :class:`_ProfiledExecutable`, so every dispatch feeds the tracez
    event ring and the profilez per-executable aggregates for free."""

    def __init__(self, jitted, label: str = "aot",
                 donate_argnums: Tuple[int, ...] = ()):
        import threading

        self._jitted = jitted
        self._label = label
        # mirror of the jit's donate_argnums, used only to account
        # donated input bytes per dispatch (paddle_tpu_exec_donated_bytes)
        self._donate = tuple(donate_argnums or ())
        self._cache: Dict[tuple, Any] = {}
        self._lock = threading.Lock()
        self._pending: Dict[tuple, Any] = {}  # key -> threading.Event

    @staticmethod
    def signature(arrays) -> tuple:
        """Hashable (shape, dtype) signature of a positional arg list.
        Works on concrete arrays and ShapeDtypeStructs alike."""
        return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)

    def get(self, key: tuple):
        with self._lock:
            return self._cache.get(key)

    def get_or_compile(self, *args, key: Optional[tuple] = None):
        """Return the executable for ``key`` (default: the signature of
        ``args``), compiling via ``jitted.lower(*args).compile()`` on a
        miss. ``args`` may mix concrete arrays (runtime miss) and
        ShapeDtypeStructs (warmup)."""
        import threading

        if key is None:
            key = self.signature(args)
        while True:
            with self._lock:
                exe = self._cache.get(key)
                if exe is not None:
                    return exe
                event = self._pending.get(key)
                if event is None:
                    event = self._pending[key] = threading.Event()
                    mine = True
                else:
                    mine = False
            if mine:
                try:
                    exe, stats = aot_compile(self._jitted, *args,
                                             label=f"{self._label}:{key}")
                    if stats:   # tests stub aot_compile with stats=None
                        from ..observability import profilez as _profilez

                        _profilez.PROFILER.record_compile(
                            self._label, stats["compile_s"])
                    exe = _ProfiledExecutable(exe, self._label,
                                              self._donate)
                    with self._lock:
                        self._cache[key] = exe
                    return exe
                finally:
                    with self._lock:
                        self._pending.pop(key, None)
                    event.set()
            # Another worker is compiling this key: wait for it, then
            # re-check (it may have failed — the loop retries the compile).
            event.wait(60.0)

    def keys(self):
        with self._lock:
            return list(self._cache)

    def __len__(self):
        with self._lock:
            return len(self._cache)


# ---------------------------------------------------------------------------
# retrace guard
# ---------------------------------------------------------------------------

class RetraceError(RuntimeError):
    """Raised on a mid-run input-signature change under
    ``PADDLE_TPU_RETRACE=error``."""


class RetraceWarning(UserWarning):
    """A compiled train step was handed inputs with a new signature."""


def _leaf_sig(leaf) -> tuple:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    sharding = getattr(leaf, "sharding", None)
    if shape is None:  # python static arg: fingerprint by value
        return ("static", repr(leaf))
    return (tuple(shape), str(dtype),
            None if sharding is None else str(sharding))


def _fingerprint(named_trees: Dict[str, Any]) -> Dict[str, tuple]:
    import jax

    fp = {}
    for group, tree in named_trees.items():
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        fp[group] = (str(treedef), tuple(_leaf_sig(l) for l in leaves))
    return fp


def _describe_diff(old: Dict[str, tuple], new: Dict[str, tuple]) -> str:
    import jax

    parts = []
    for group in new:
        o, n = old.get(group), new[group]
        if o == n:
            continue
        if o is None:
            parts.append(f"{group}: new input group")
            continue
        if o[0] != n[0]:
            parts.append(f"{group}: pytree structure changed")
            continue
        for i, (a, b) in enumerate(zip(o[1], n[1])):
            if a != b:
                parts.append(f"{group}[leaf {i}]: {a} -> {b}")
    for group in old:
        if group not in new:
            parts.append(f"{group}: input group removed")
    return "; ".join(parts) or "signature changed"


class RetraceGuard:
    """Per-compiled-step input-signature watchdog.

    ``check(**named_trees)`` returns ``"first"`` on the initial call,
    ``"match"`` while the signature is stable, and ``"retrace"`` when it
    changed — after emitting one :class:`RetraceWarning` naming the
    changed input (or raising :class:`RetraceError` when
    ``PADDLE_TPU_RETRACE=error``)."""

    def __init__(self, label: str = "step"):
        self.label = label
        self._fp: Optional[Dict[str, tuple]] = None
        self._warned = False

    def reset(self):
        self._fp = None
        self._warned = False

    def check(self, **named_trees) -> str:
        fp = _fingerprint(named_trees)
        if self._fp is None:
            self._fp = fp
            return "first"
        if fp == self._fp:
            return "match"
        diff = _describe_diff(self._fp, fp)
        mode = str(_flags.env_value("PADDLE_TPU_RETRACE")).strip().lower()
        msg = (f"paddle_tpu retrace guard [{self.label}]: compiled-step "
               f"input signature changed mid-run -> recompiling. "
               f"Changed: {diff}. (PADDLE_TPU_RETRACE=error makes this "
               f"fatal; =off silences it)")
        if mode == "error":
            raise RetraceError(msg)
        if mode != "off" and not self._warned:
            warnings.warn(msg, RetraceWarning, stacklevel=3)
            self._warned = True  # one structured warning per run
        self._fp = fp
        return "retrace"
