"""Compile observability plumbing (jit/compile_cache.py): persistent
XLA-cache hit/miss detection across two Model.prepare cycles, the retrace
guard (one structured warning on a mid-fit batch-shape change;
PADDLE_TPU_RETRACE=error escalates), and the fleet mesh fail-fast
warning."""
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.hapi import Model
from paddle_tpu.io import TensorDataset
from paddle_tpu.jit import compile_cache
from paddle_tpu.static import InputSpec

X = np.random.default_rng(0).standard_normal((64, 8)).astype("float32")
Y = np.random.default_rng(1).integers(0, 2, (64,)).astype("int64")


def _model(optimizer_cls=opt.Adam):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    m = Model(net, inputs=[InputSpec([None, 8], "float32")],
              labels=[InputSpec([None], "int64")])
    m.prepare(optimizer_cls(learning_rate=1e-3,
                            parameters=m.parameters()),
              loss=nn.CrossEntropyLoss())
    return m


@pytest.fixture
def cache_config():
    """Set jax's persistent-cache config for one test (the caller — not
    the framework — chooses the directory) and restore it afterwards."""
    import jax
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in keys}

    def set_(**kw):
        for k, v in kw.items():
            jax.config.update(k, v)
        compile_cache._reset_jax_cache()

    yield set_
    set_(**saved)


def test_cache_miss_then_hit_across_prepares(tmp_path, cache_config):
    cache_config(jax_compilation_cache_dir=str(tmp_path))
    m1 = _model()
    m1.train_batch([X[:16]], [Y[:16]])
    assert m1._compile_stats["cache"] == "miss"
    assert m1._compile_stats["compile_s"] > 0

    hits = []
    for _ in range(3):                       # later prepares, same HLO
        m2 = _model()
        m2.train_batch([X[:16]], [Y[:16]])
        assert m2._compile_stats["cache"] == "hit"
        hits.append(m2._compile_stats["compile_s"])
    # a hit reads the executable from disk instead of recompiling (the
    # best of three: one sub-second reading each way lost to the load of
    # a six-worker run)
    assert min(hits) < m1._compile_stats["compile_s"]

    from paddle_tpu import profiler
    labels = [e["label"] for e in profiler.compile_events()]
    assert "hapi.train_step" in labels


def test_cache_disabled_by_jax_switch(cache_config):
    """JAX_ENABLE_COMPILATION_CACHE=false (jax's own switch) is the one
    way to turn the cache off; the verdict then reads "off"."""
    cache_config(jax_enable_compilation_cache=False)
    assert compile_cache.cache_dir() is None
    m = _model()
    m.train_batch([X[:16]], [Y[:16]])
    assert m._compile_stats["cache"] == "off"


def test_cache_placement_is_jaxs_or_the_checkout(tmp_path, cache_config,
                                                 monkeypatch):
    """A directory jax already has (JAX_COMPILATION_CACHE_DIR, which jax
    reads into its config at import, or a caller's config.update) is
    never replaced in code; with none, the cache goes to the one fixed
    in-checkout path — never a temp/pid/time-derived one, never $HOME."""
    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.REPO_CACHE_DIR == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

    monkeypatch.setattr(compile_cache, "_wired", [False])
    cache_config(jax_compilation_cache_dir=str(tmp_path))     # "env set"
    assert compile_cache.setup_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    monkeypatch.setattr(compile_cache, "_wired", [False])
    cache_config(jax_compilation_cache_dir=None)              # "unset"
    assert compile_cache.setup_compilation_cache() == \
        compile_cache.REPO_CACHE_DIR


def test_fleet_children_inherit_the_cache_env(monkeypatch):
    """The router's backend supervisor hands its children the parent's
    environment untouched: JAX_COMPILATION_CACHE_DIR survives as set, no
    per-fleet temp directory is made up, and the removed
    PADDLE_TPU_COMPILE_CACHE is not reintroduced."""
    import tempfile

    from paddle_tpu.inference import router

    def no_mkdtemp(*a, **k):
        raise AssertionError("fleet cache must not be a temp directory")

    monkeypatch.setattr(tempfile, "mkdtemp", no_mkdtemp)
    for env in ({"JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": "/some/shared/dir"},
                {"JAX_PLATFORMS": "cpu"}):
        sup = router.BackendSupervisor("prefix", 2, router=None, env=env)
        assert sup._env == env


def test_retrace_guard_warns_once_and_recompiles(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_RETRACE", raising=False)
    m = _model()
    m.train_batch([X[:16]], [Y[:16]])
    with pytest.warns(compile_cache.RetraceWarning, match="hapi.train_step"):
        m.train_batch([X[:8]], [Y[:8]])      # batch 16 -> 8: one warning
    with warnings.catch_warnings():
        warnings.simplefilter("error", compile_cache.RetraceWarning)
        m.train_batch([X[:16]], [Y[:16]])    # changes again: stays silent


def test_retrace_guard_identifies_changed_input(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_RETRACE", raising=False)
    m = _model()
    m.train_batch([X[:16]], [Y[:16]])
    with pytest.warns(compile_cache.RetraceWarning) as rec:
        m.train_batch([X[:8]], [Y[:8]])
    msg = str(rec[0].message)
    assert "inputs" in msg and "(16, 8)" in msg and "(8, 8)" in msg


def test_retrace_guard_error_mode(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_RETRACE", "error")
    m = _model()
    m.train_batch([X[:16]], [Y[:16]])
    with pytest.raises(compile_cache.RetraceError):
        m.train_batch([X[:8]], [Y[:8]])


def test_retrace_guard_mid_fit(monkeypatch):
    """A non-divisible final batch is the classic silent-retrace source."""
    monkeypatch.delenv("PADDLE_TPU_RETRACE", raising=False)
    m = _model()
    ds = TensorDataset([X[:24], Y[:24]])     # 24 = 16 + trailing 8
    with pytest.warns(compile_cache.RetraceWarning):
        m.fit(ds, batch_size=16, epochs=1, verbose=0, shuffle=False)


def test_retrace_guard_unit():
    g = compile_cache.RetraceGuard("unit")
    a = {"x": np.zeros((4, 2), np.float32)}
    assert g.check(data=a) == "first"
    assert g.check(data=a) == "match"
    with pytest.warns(compile_cache.RetraceWarning):
        assert g.check(data={"x": np.zeros((2, 2), np.float32)}) \
            == "retrace"


def test_sgd_slotless_donation_skips_opt_state():
    """Slot-less SGD must not donate the (leaf-less) opt_state arg —
    that's what produced 'Some donated buffers were not usable'."""
    m = _model(optimizer_cls=opt.SGD)
    loss0 = m.train_batch([X[:16]], [Y[:16]])[0]
    loss1 = m.train_batch([X[:16]], [Y[:16]])[0]
    assert np.isfinite(loss0) and np.isfinite(loss1)
    import jax
    if not jax.tree_util.tree_leaves(m._opt_state):
        assert m._donate_argnums((0, 2), 2) == (0,)


def test_layer_tensors_survive_donated_steps():
    """The compiled step donates its param buffers; the Layer's own
    Tensors must never alias them (device_put(may_alias=False) still
    aliases on this jax build, so seeding goes through a true copy)."""
    import jax
    import paddle_tpu.optimizer as popt
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.fleet.compiler import compile_train_step
    from paddle_tpu.models import GPT, gpt_tiny

    paddle.seed(0)
    m = GPT(gpt_tiny())
    s = DistributedStrategy()
    mesh = s.build_mesh()
    prog = compile_train_step(
        m, popt.Adam(learning_rate=1e-3, parameters=list(m.parameters())),
        s, mesh=mesh)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 512, (8, 32)).astype(np.int64)
    y = rng.integers(0, 512, (8, 32)).astype(np.int64)
    for _ in range(2):
        prog.step(x, y, lr=1e-3)
    dead = [k for k, p in m.named_parameters() if p._data.is_deleted()]
    assert not dead, f"layer params deleted by donation: {dead[:3]}"
    m.state_dict()          # the user-visible symptom: state_dict raises


def test_fleet_init_warns_on_mesh_failure():
    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    s = DistributedStrategy()
    s.hybrid_configs.dp_degree = 3
    s.hybrid_configs.mp_degree = 5            # 3*5=15 != 8 devices
    with pytest.warns(RuntimeWarning, match="mesh build failed"):
        fleet.init(strategy=s)


def test_strategy_path_records_compile(tmp_path, cache_config):
    cache_config(jax_compilation_cache_dir=str(tmp_path))
    from paddle_tpu import profiler
    from paddle_tpu.distributed.fleet import DistributedStrategy
    profiler.reset_compile_events()
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    m = Model(net, inputs=[InputSpec([None, 8], "float32")],
              labels=[InputSpec([None], "int64")])
    m.prepare(opt.Adam(learning_rate=1e-3, parameters=m.parameters()),
              loss=nn.CrossEntropyLoss(), strategy=DistributedStrategy())
    m.train_batch([X[:16]], [Y[:16]])
    events = profiler.compile_events()
    assert any(e["label"] == "fleet.train_step" for e in events)
    assert m._dist_prog.compile_stats["compile_s"] > 0


# -- AotCache: compile outside the map lock (tsan-lite TPR102 regression) --

def test_aot_cache_compile_does_not_block_other_keys(monkeypatch):
    import threading
    import time

    calls = []
    gate = threading.Event()

    def fake_aot(jitted, *args, label=""):
        calls.append(label)
        if "slow" in label:
            gate.wait(10)
        return ("exe:" + label, None)

    monkeypatch.setattr(compile_cache, "aot_compile", fake_aot)
    cache = compile_cache.AotCache(jitted=None, label="t")
    fast = cache.get_or_compile(key=("fast",))

    t = threading.Thread(target=lambda: cache.get_or_compile(key=("slow",)),
                         daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while not any("slow" in c for c in calls) and time.monotonic() < deadline:
        time.sleep(0.005)
    assert any("slow" in c for c in calls)

    # A warmed-key hit must not wait out the in-flight compile.
    t0 = time.monotonic()
    assert cache.get_or_compile(key=("fast",)) == fast
    assert time.monotonic() - t0 < 1.0
    gate.set()
    t.join(5)
    assert not t.is_alive()
    assert len(cache) == 2


def test_aot_cache_concurrent_misses_compile_once(monkeypatch):
    import threading
    import time

    calls = []

    def fake_aot(jitted, *args, label=""):
        calls.append(label)
        time.sleep(0.05)
        return (object(), None)

    monkeypatch.setattr(compile_cache, "aot_compile", fake_aot)
    cache = compile_cache.AotCache(jitted=None, label="t")
    results = []
    threads = [
        threading.Thread(
            target=lambda: results.append(cache.get_or_compile(key=("k",))),
            daemon=True)
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert len(calls) == 1          # once-semantics: no duplicated XLA run
    assert len(results) == 4
    assert all(r is results[0] for r in results)
