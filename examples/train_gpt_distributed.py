"""Train a GPT with the fleet strategy compiler.

Pick parallelism by flipping DistributedStrategy toggles — the compiler
maps them to mesh axes + shardings and XLA emits the collectives:

    python examples/train_gpt_distributed.py            # 1 chip
    python examples/train_gpt_distributed.py --dp 2 --tp 2 --sp 2   # hybrid

Run off-TPU with:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse

import numpy as np

import jax
import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed.fleet.compiler import compile_train_step
from paddle_tpu.distributed.fleet.strategy import DistributedStrategy
from paddle_tpu.models import GPT, gpt_tiny


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--zero", type=int, default=0, help="ZeRO stage 0-3")
    ap.add_argument("--steps", type=int, default=20)
    ns = ap.parse_args()

    paddle.seed(0)
    model = GPT(gpt_tiny())

    s = DistributedStrategy()
    s.amp = True
    if ns.tp > 1:
        s.tensor_parallel, s.hybrid_configs.mp_degree = True, ns.tp
    if ns.sp > 1:
        s.sequence_parallel, s.hybrid_configs.sep_degree = True, ns.sp
    if ns.pp > 1:
        s.pipeline, s.hybrid_configs.pp_degree = True, ns.pp
        s.pipeline_configs.accumulate_steps = 4
    if ns.zero:
        s.sharding, s.sharding_configs.stage = True, ns.zero
    s.hybrid_configs.dp_degree = ns.dp
    n_dev = ns.dp * ns.tp * ns.sp * ns.pp
    mesh = s.build_mesh(devices=jax.devices()[:n_dev])

    adam = opt.Adam(learning_rate=3e-4,
                    parameters=list(model.parameters()))
    prog = compile_train_step(model, adam, s, mesh=mesh)

    rng = np.random.default_rng(0)
    for step in range(ns.steps):
        ids = rng.integers(0, 512, (max(4, 2 * ns.dp), 32)).astype(np.int64)
        loss = prog.step(ids, ids, lr=3e-4)
        if step % 5 == 0:
            print(f"step {step}: loss {float(jax.device_get(loss)):.4f}")
    prog.save_checkpoint("/tmp/gpt_ckpt", step=ns.steps)
    print("checkpoint written to /tmp/gpt_ckpt")


if __name__ == "__main__":
    main()
