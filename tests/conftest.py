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
