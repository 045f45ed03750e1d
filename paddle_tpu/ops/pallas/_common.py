"""Shared helpers for the Pallas TPU kernels (flash_attention, fused_ce)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

VMEM = pltpu.VMEM

NEG_INF = np.float32(-1e30)
LANE = 128      # TPU lane width: per-row scalars ride a broadcast lane dim
I0 = np.int32(0)  # index-map zero pinned to i32 (x64 would make it i64)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Pallas interpret mode, for the CPU backend only (the test suite
    runs the kernel bodies there). Any other backend that is not a TPU
    cannot run these kernels at all: a selected kernel raises instead
    of quietly computing something else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"paddle_tpu Pallas kernels are TPU (Mosaic) kernels; the default "
        f"JAX backend is {backend!r}. Run on a TPU, or on CPU "
        f"(JAX_PLATFORMS=cpu) for interpret mode.")


def compiler_params(*dimension_semantics: str,
                    vmem_limit_bytes: int | None = None) -> dict:
    """``pallas_call`` kwargs naming the grid's dimension semantics (and
    a VMEM allowance above the compiler's default) for Mosaic;
    interpret mode takes none."""
    if interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=vmem_limit_bytes)}


def mxu_dtype():
    """MXU operand dtype follows jax_default_matmul_precision: 'highest'
    keeps f32 operands (tests, debugging); the TPU default streams bf16
    through the MXU at full rate (accumulation is always f32)."""
    prec = jax.config.jax_default_matmul_precision
    if prec in ("highest", "float32"):
        return jnp.float32
    return jnp.bfloat16
