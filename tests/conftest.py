"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding tests run without TPU hardware (SURVEY.md §4 — the reference runs
distributed tests as local subprocess simulations; on JAX the equivalent is
xla_force_host_platform_device_count).

Must run before the first `import jax` anywhere in the test process.
"""
import os

# Force-assign (not setdefault: a machine with a chip sets
# JAX_PLATFORMS=tpu,cpu) so the suite and every subprocess it spawns run on
# the CPU backend — the chip belongs to chip_smoke.py and the benchmarks,
# one process at a time. jax reads the variable when it is imported, and a
# `-p` plugin may have imported it before this file runs; the
# jax.config.update below covers that case.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

# Numeric tests check against float64 numpy references; this JAX build
# defaults matmuls to bf16-MXU-style passes even on CPU.
jax.config.update("jax_default_matmul_precision", "highest")

import pytest


@pytest.fixture(autouse=True)
def _restore_global_mesh():
    """Tests that build_mesh/set_mesh must not leak the global mesh into
    later tests (r2 verdict: a stale 2-device mesh from one test broke a
    4-device strategy in another)."""
    from paddle_tpu.distributed import mesh as mesh_mod

    prior = mesh_mod.get_mesh()
    yield
    mesh_mod.set_mesh(prior)


# ONE marker for ONE test until the first `benchmark` PR, and no way for
# a later PR to switch tests off. `tests/chipbench/` is the benchmark's
# (`paths` in BENCHMARK.json): only a `benchmark` PR may edit it. Its
# rehearsal test, as PR 25 left it, requires `admit.repack_ms_p50` and
# `admit.pwrite_ms_p50` to read; ISSUE 26 deleted what they read (the K/V
# panel's trip through numpy and an admission's second dispatch). The
# marker expires with the file: it holds only while the file is byte for
# byte PR 25's, so the first edit to it voids the marker, and the PR
# after that deletes this block (ROADMAP S3, PERF.md section 7). Strict:
# should the test pass again, it fails here.
_PR25_SPAN_METRICS_SHA256 = \
    "c0d433a2c1106986698457c2b93238902143628127a5710f95c9aab605a11b72"


def pytest_collection_modifyitems(items):
    import hashlib

    nodeid = ("tests/chipbench/test_chipbench_span_metrics.py::"
              "test_rehearsal_prints_the_ring_metrics_and_no_idle_share")
    for item in items:
        if item.nodeid != nodeid:
            continue
        with open(str(item.fspath), "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        if digest == _PR25_SPAN_METRICS_SHA256:
            item.add_marker(pytest.mark.xfail(strict=True, reason=(
                "PR 26: an admission is one dispatch; no decode.admit."
                "kv_pull/.repack/.upload span and no exec:decode.pwrite "
                "is written, so two of RING_METRICS read nothing")))
