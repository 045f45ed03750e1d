"""Nothing on the main paths hides the device (PR 21 bring-up).

What a CPU can check of it: a failed accelerator is a non-zero exit, not
a CPU re-run; a selected kernel that cannot run raises; the decode
engine's HBM-derived slot sizing follows the compiled step; one process
per chip — the router parent stays off JAX and pins its children; and
chip_smoke.py refuses to "smoke on CPU".
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=300, **env):
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO, **env))


# -- a failed accelerator is a traceback and a non-zero exit ----------------

def test_bench_backend_failure_exits_nonzero():
    """A backend that cannot initialise raises out of the benchmark: no
    result line, no rc 0, no second life on the CPU."""
    p = _run([sys.executable, "chipbench/run.py", "--workload",
              "serve-gpt2-124m-chat", "--seed", "1", "--seconds", "1"],
             JAX_PLATFORMS="no_such_backend")
    assert p.returncode != 0
    assert "no_such_backend" in p.stderr
    assert not p.stdout.strip(), p.stdout
    assert "retrying on CPU" not in p.stderr


def test_chip_smoke_refuses_cpu_and_parent_stays_off_jax():
    code = ("import sys, chip_smoke\n"
            "rc = chip_smoke.main([])\n"
            "from jax._src import xla_bridge\n"
            "print('PARENT_BACKEND', xla_bridge.backends_are_initialized())\n"
            "sys.exit(rc)\n")
    p = _run([sys.executable, "-c", code], JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "PARENT_BACKEND False" in p.stdout
    assert '"ok"' not in p.stdout


# -- a selected kernel that cannot run is an error ---------------------------

def test_interpret_mode_is_for_the_cpu_backend_only(monkeypatch):
    from paddle_tpu.ops.pallas import _common
    assert _common.interpret() is True                 # this suite: CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _common.interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="TPU .Mosaic. kernels"):
        _common.interpret()


def test_routed_flash_kernel_failure_raises(monkeypatch):
    """sdpa used to catch the kernel's ValueError, warn and compute with
    XLA; a call routed to the kernel now fails loudly."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops.pallas import flash_attention as fa

    def boom(*a, **k):
        raise ValueError("bad block override")

    monkeypatch.setattr(fa, "flash_attention", boom)
    x = paddle.to_tensor(np.zeros((1, 512, 1, 32), np.float32))
    with pytest.raises(ValueError, match="bad block override"):
        F.scaled_dot_product_attention(x, x, x, is_causal=True)
    # a length the kernel cannot tile is not routed to it at all
    y = paddle.to_tensor(np.zeros((1, 520, 1, 32), np.float32))
    assert F.scaled_dot_product_attention(
        y, y, y, is_causal=True).shape == [1, 520, 1, 32]


def test_fleet_step_aot_failure_raises(monkeypatch):
    """CompiledTrainStep used to swallow a failed AOT compile and keep
    the implicit jit path."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.fleet.compiler import compile_train_step
    from paddle_tpu.jit import compile_cache
    from paddle_tpu.models import GPT, gpt_tiny

    paddle.seed(0)
    m = GPT(gpt_tiny())
    s = DistributedStrategy()
    prog = compile_train_step(
        m, opt.Adam(learning_rate=1e-3, parameters=list(m.parameters())),
        s, loss_method="loss", mesh=s.build_mesh(devices=jax.devices()[:1]))

    def boom(*a, **k):
        raise RuntimeError("compile exploded")

    monkeypatch.setattr(compile_cache, "aot_compile", boom)
    ids = np.zeros((2, 16), np.int32)
    with pytest.raises(RuntimeError, match="compile exploded"):
        prog.step(ids, ids, lr=1e-3)


# -- slot sizing follows the compiled step ----------------------------------

def _linear_step(fixed, per_slot, device_limit):
    calls = []

    def step_bytes(n):
        calls.append(n)
        need = fixed + per_slot * n
        return None if need > device_limit else need

    return step_bytes, calls


def test_fit_slot_count_converges_on_the_budget():
    from paddle_tpu.inference.decode import fit_slot_count
    GB = 10 ** 9
    # GPT-2 124M on a v5e, roughly: 0.5 GB of weights, 0.4 GB a slot
    f, calls = _linear_step(GB // 2, 4 * GB // 10, 16 * GB)
    n = fit_slot_count(f, 8 * GB, upper=100)
    assert GB // 2 + 4 * GB // 10 * n <= 8 * GB
    assert n >= 17 and len(calls) <= 4
    # the logical-bytes upper bound caps it
    f, calls = _linear_step(GB // 10, GB // 100, 16 * GB)
    assert fit_slot_count(f, 8 * GB, upper=5) == 5 and calls == [5]
    # the compiler runs out of HBM at the first sizes tried: halve
    f, calls = _linear_step(5 * GB, 4 * GB, 16 * GB)
    assert fit_slot_count(f, 10 * GB, upper=6) == 1
    # one slot compiles but is over the budget: one slot is the floor
    f, _ = _linear_step(GB, 8 * GB, 16 * GB)
    assert fit_slot_count(f, 4 * GB, upper=50) == 1
    # not even one slot compiles
    f, _ = _linear_step(5 * GB, 12 * GB, 16 * GB)
    with pytest.raises(RuntimeError, match="does not fit"):
        fit_slot_count(f, 10 * GB, upper=6)


def test_hbm_derived_slot_count_uses_compiled_footprint(monkeypatch):
    """The HBM-derived path (never taken on CPU before: no memory stats
    means the 8-slot fallback) driven with made-up stats: the engine
    sizes itself so the largest compiled step fits the budget."""
    from paddle_tpu import profiler
    from paddle_tpu.core import monitor
    from paddle_tpu.inference import decode, model_kinds
    from paddle_tpu.models import GPT, gpt_tiny

    paddle.seed(0)
    model = GPT(gpt_tiny())
    used, limit = 1 << 20, 48 << 20
    monkeypatch.setattr(monitor, "hbm_usage", lambda device=None:
                        (used, limit))
    profiler.reset_compile_events()
    eng = decode.DecodeEngine(model, page_tokens=8)
    try:
        sized = [e["label"] for e in profiler.compile_events()
                 if e["label"].startswith("decode.sizing:")]
        assert sized and sized[-1] == f"decode.sizing:{eng.max_slots}"
        logical = (limit - used) \
            // model_kinds.for_model(model).slot_bytes()
        assert 1 <= eng.max_slots < logical      # padding + temporaries
    finally:
        eng.stop()
    # and a backend failure on that path surfaces instead of reading as
    # "CPU, 8 slots"
    def boom(device=None):
        raise RuntimeError("backend exploded")

    monkeypatch.setattr(monitor, "hbm_usage", boom)
    with pytest.raises(RuntimeError, match="backend exploded"):
        decode.DecodeEngine(model, page_tokens=8)


# -- one process per chip ---------------------------------------------------

def test_fleet_backends_are_pinned_one_per_chip(monkeypatch):
    from paddle_tpu.core import place
    from paddle_tpu.inference import router

    assert place.local_tpu_chip_count({"JAX_PLATFORMS": "cpu"}) == 0
    assert place.single_chip_env(2)["TPU_VISIBLE_CHIPS"] == "2"

    monkeypatch.setattr(router, "local_tpu_chip_count", lambda env: 2)
    with pytest.raises(ValueError, match="2 TPU chip"):
        router.BackendSupervisor("prefix", 3, router=None, env={})

    spawned = []

    class FakePopen:
        pid, stdout = 0, []

        def __init__(self, cmd, env=None, **kw):
            spawned.append(env)

    monkeypatch.setattr(router.subprocess, "Popen", FakePopen)
    sup = router.BackendSupervisor("prefix", 2, router=None,
                                   env={"KEEP": "1"})
    for slot in range(2):
        sup._spawn(slot)
    assert [e["TPU_VISIBLE_CHIPS"] for e in spawned] == ["0", "1"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" and e["KEEP"] == "1"
               for e in spawned)


def test_router_process_never_initialises_a_backend():
    """The router owns no device: serving its admin plane (collectors run
    on every scrape) must not call jax.devices() — on a TPU host that
    would take the chips its backends need."""
    code = (
        "import urllib.request\n"
        "from paddle_tpu.inference.router import ServeRouter\n"
        "r = ServeRouter([], port=0, metrics_port=0)\n"
        "for path in ('/metrics', '/statusz'):\n"
        "    urllib.request.urlopen(\n"
        "        f'http://127.0.0.1:{r.metrics_port}{path}').read()\n"
        "r.stop()\n"
        "from jax._src import xla_bridge\n"
        "print('BACKEND', xla_bridge.backends_are_initialized())\n")
    p = _run([sys.executable, "-c", code], JAX_PLATFORMS="cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "BACKEND False" in p.stdout


# -- a trace leaves no tracer behind in the global generator ----------------

def test_a_constructor_under_eval_shape_leaves_the_generator_usable():
    """`jax.eval_shape(lambda: param_arrays(GPT(cfg)))` (the probe below,
    the benchmark's `param_shapes`) draws initial weights under a trace.
    The global generator comes out of it holding a concrete key, its
    chain where the same draws made eagerly leave it: a traced key kept
    there failed whichever test drew next in the worker."""
    from paddle_tpu import framework
    from paddle_tpu.core import random as rng
    from paddle_tpu.models import GPT, gpt_tiny

    paddle.seed(3)
    jax.eval_shape(lambda: framework.param_arrays(GPT(gpt_tiny())))
    traced_state, traced_next = rng.get_rng_state(), rng.next_key()
    paddle.seed(3)
    GPT(gpt_tiny())
    assert rng.get_rng_state() == traced_state
    np.testing.assert_array_equal(jax.random.key_data(rng.next_key()),
                                  jax.random.key_data(traced_next))
    assert paddle.create_parameter([2, 2], "float32").shape == [2, 2]


# -- the gpt pools are used in place by the compiled step --------------------

@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a v5e this machine does not have, for the chipless
    compiler. Described inside the fixture (never at import: one
    process at a time may load the TPU's library, and every xdist
    worker imports this file)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pool_pass_probe(v5e_chip):
    """The gpt step and prefill-into-pages compiled for that chip (2
    layers, 768 wide, 12 heads of 64; 16 slots of 64 pages of 16), and
    what each makes at a layer pool's size."""
    import re

    import jax.numpy as jnp

    from paddle_tpu import framework
    from paddle_tpu.inference import model_kinds
    from paddle_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=512, max_seq_len=1024, hidden=768, layers=2,
                    heads=12)
    kind = model_kinds.for_config(cfg, 1e-5)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=v5e_chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e_chip)

    params = on_chip(jax.eval_shape(
        lambda: framework.param_arrays(GPT(cfg))))
    n, pt, W = 16, 16, 64
    P = n * W + 1
    pools = on_chip(kind.pools_sds(P, pt, "float32"))
    out = {"pool_bytes": sum(x.size * x.dtype.itemsize
                             for x in jax.tree.leaves(pools))}
    pool_shape = "f32[%d,%d,%d]" % (P, pt, cfg.heads * cfg.head_dim)
    # as served: matmuls at XLA's default precision (conftest.py asks
    # for "highest", for the CPU's sake)
    with jax.default_matmul_precision("default"):
        programs = {
            "step": jax.jit(kind.step_fn(pt), donate_argnums=(1,)).lower(
                params, pools, ints(n, W), ints(n), ints(n)),
            "prefill": jax.jit(kind.prefill_fn(pt),
                               donate_argnums=(1,)).lower(
                params, pools, ints(1, 256), ints(1, 16), ints(1))}
    for name, lowered in programs.items():
        exe = lowered.compile()
        text = exe.as_text()
        made = []        # what the program itself writes at a pool's size
        for line in text[text.index("ENTRY "):].splitlines():
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                         r"([\w\-]+)\(", line)
            if m and m.group(2) == pool_shape and m.group(3) not in (
                    "parameter", "bitcast", "get-tuple-element"):
                made.append((m.group(3), "scatter" in line))
        mem = exe.memory_analysis()
        out[name] = {"temp": mem.temp_size_in_bytes,
                     "alias": mem.alias_size_in_bytes, "made": made}
    return out


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_gpt_pools_are_written_in_place_on_v5e(pool_pass_probe, program):
    """What S5 was: with `[.., heads, head_dim]` minor dimensions the
    compiler copied each pool into a layout it could gather from and
    back, every step and every admission, and a stacked pool cost a
    slice a layer. One lane-dense array a layer: the only thing the
    program makes at a layer pool's size is the scatter into the very
    buffer it was given (two a layer, K and V), every pool byte is
    aliased to its output, and the temporaries are a fraction of the
    pools (they were four times the pools)."""
    got = pool_pass_probe[program]
    layers = 2
    assert len(got["made"]) == 2 * layers, got["made"]
    assert all(op == "fusion" and is_scatter
               for op, is_scatter in got["made"]), got["made"]
    assert got["alias"] >= pool_pass_probe["pool_bytes"]
    assert got["temp"] < pool_pass_probe["pool_bytes"] / 4, got


# -- the serving cells' prefill programs fit where the parent's did ----------

# temp_size_in_bytes of the parent of PR 34 (commit 8d7ae6f: `ragged_dot`
# over every assignment, 1,024 tokens at a time), the same compile
PARENT_PREFILL_TEMP = {
    ("kimi-linear-share2", 512): 93_569_536,
    ("kimi-linear-share2", 1024): 251_769_344,
    ("kimi-linear-share2", 2048): 798_129_152,
    ("axk1-share16", 2048): 630_308_864,
    ("axk1-share16", 4096): 772_045_824,
    ("axk1-share16", 8192): 1_412_020_736,
}
SLOTS = {"kimi-linear-share2": 256, "axk1-share16": 67}


@pytest.fixture(scope="module")
def as_on_a_tpu():
    """Code that asks `_common.on_tpu()` answers as the chip would (the
    chipless compiler runs under the CPU backend)."""
    from paddle_tpu.ops.pallas import _common
    keep = _common.on_tpu, _common.interpret
    _common.on_tpu, _common.interpret = (lambda: True), (lambda: False)
    yield
    _common.on_tpu, _common.interpret = keep


@pytest.mark.parametrize("config,rung", list(PARENT_PREFILL_TEMP))
def test_prefill_temporaries_are_no_larger_than_the_parents(
        v5e_chip, as_on_a_tpu, config, rung):
    """Slot sizing reads the step alone, so a prefill that grew would
    fail at run time, at 14.1 of 15.75 GB: every prefill rung of both
    serving configurations, at the benchmark's sizes, compiled for the
    chip; the expert layers run the grouped kernel (three calls a layer:
    gate and up fused, down; twice in the text, the loop's body being
    there twice) and nothing of `ragged_dot`."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from chipbench import harness
    from paddle_tpu.inference import model_kinds
    from paddle_tpu.ops.pallas.grouped_matmul import KERNEL_NAME

    _, sizes, family = harness.load_config(harness.load_benchmark(), config)
    kind = model_kinds.for_config(family._config(sizes), None)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=v5e_chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e_chip)

    pt, slots = 16, SLOTS[config]
    by_slot = getattr(kind, "slot_state", False)
    pools = on_chip(kind.pools_sds(
        slots * (sizes["max_seq_len"] // pt) + 1, pt, kind.pool_dtype(None),
        *((slots,) if by_slot else ())))
    with jax.default_matmul_precision("default"):
        exe = jax.jit(kind.prefill_fn(pt), donate_argnums=(1,)).lower(
            on_chip(family.param_shapes(sizes)), pools, ints(1, rung),
            ints(1, rung // pt), ints(1),
            *((ints(),) if by_slot else ())).compile()
    text = exe.as_text()
    assert KERNEL_NAME in text and "ragged-dot" not in text
    temp = exe.memory_analysis().temp_size_in_bytes
    assert temp <= PARENT_PREFILL_TEMP[config, rung], temp


# the fourth kind's programs, as PR 35 compiled them (bytes of temporaries)
AFMOE_TEMP = {"step": 16 * 2 ** 20, 2048: 200 * 2 ** 20, 16384: 1800 * 2 ** 20}


@pytest.mark.parametrize("program", list(AFMOE_TEMP))
def test_the_window_kinds_programs_compile_and_fit(v5e_chip, as_on_a_tpu,
                                                   program):
    """The `afmoe` kind at the long-document cell's sizes (26 slots of
    16,384 positions, pages of 128), compiled for the chip: the step
    runs the paged grouped-query reader once a layer over pages and
    rings alike and writes both in place; a prefill runs the flash
    forward once a layer (four in a band, one causal); weights, pools
    and the largest temporaries together stay inside the chip."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from chipbench import harness
    from paddle_tpu.inference import model_kinds
    from paddle_tpu.ops.pallas.gqa_attention import KERNEL_NAME

    _, sizes, family = harness.load_config(harness.load_benchmark(),
                                           "trinity-large-share8")
    kind = model_kinds.for_config(family._config(sizes), None)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=v5e_chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v5e_chip)

    pt, slots = kind.default_page_tokens(), 26
    W = sizes["max_seq_len"] // pt
    pools = on_chip(kind.pools_sds(slots * W + 1, pt, kind.pool_dtype(None),
                                   slots))
    params = on_chip(family.param_shapes(sizes))
    with jax.default_matmul_precision("default"):
        if program == "step":
            lowered = jax.jit(kind.step_fn(pt), donate_argnums=(1,)).lower(
                params, pools, ints(slots, W), ints(slots), ints(slots),
                ints(slots))
        else:
            lowered = jax.jit(kind.prefill_fn(pt), donate_argnums=(1,)).lower(
                params, pools, ints(1, program), ints(1, program // pt),
                ints(1), ints())
        exe = lowered.compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    if program == "step":
        assert KERNEL_NAME in text
        assert "flash_attention_fwd" not in text
    else:
        assert "flash_attention_fwd" in text and KERNEL_NAME not in text
    # every pool byte is aliased to its output: written where it lies
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pools))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes <= AFMOE_TEMP[program], mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30


def test_the_grouped_layer_compiles_for_tokens_of_no_whole_tile(
        v5e_chip, as_on_a_tpu):
    """A full-sequence forward hands the expert layer any token count
    (777 here, not a multiple of the 8 rows a float32 tile holds): the
    many-token path pads its accumulator, the kernels compile."""
    import jax.numpy as jnp

    from paddle_tpu.nn.layer import moe

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    N, H, F, count = 777, 512, 256, 4
    bf = jnp.bfloat16
    exe = jax.jit(lambda *a: moe._held_grouped(*a, 16)).lower(
        on_chip((N, H), bf), on_chip((N, 8), jnp.int32),
        on_chip((N, 8), jnp.bool_), on_chip((N, 8), jnp.float32),
        on_chip((count, H, F), bf), on_chip((count, H, F), bf),
        on_chip((count, F, H), bf)).compile()
    assert exe.output_shardings is not None
    assert "moe_grouped_matmul_add" in exe.as_text()
