"""Long-context single-chip sweep: GPT-2
124M geometry at T in {1024, 4096, 8192, 16384}, bf16 AMP, strategy-
compiled train step. Prints one JSON line per length with tokens/s and
MFU (flops_per_token includes the quadratic attention term).

    python benchmarks/longctx.py                 # full sweep on TPU
    python benchmarks/longctx.py --seqs 4096
    PT_FLASH_FWD_BLOCKS=1024,2048 python benchmarks/longctx.py ...
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_one(T, batch, n_warm=2, n_meas=6):
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed.fleet.strategy import DistributedStrategy
    from paddle_tpu.distributed.fleet.compiler import compile_train_step
    from paddle_tpu.models import GPT, GPTConfig
    from bench import peak_flops

    cfg = GPTConfig(max_seq_len=max(T, 1024))      # GPT-2 124M geometry
    paddle.seed(0)
    model = GPT(cfg)
    model.eval()
    s = DistributedStrategy()
    s.amp = True
    adam = opt.Adam(learning_rate=1e-4, parameters=list(model.parameters()))
    prog = compile_train_step(model, adam, s, loss_method="loss")
    rng = np.random.default_rng(0)
    ids = prog._put_data(
        rng.integers(0, cfg.vocab_size, (batch, T)).astype(np.int32))

    # marginal-step estimator (bench.py): time two window sizes ending
    # in one value fetch (float()) each — the fetch's constant cost
    # cancels in the difference
    def window(n):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = prog.step(ids, ids)
        float(loss)
        return time.perf_counter() - t0

    window(n_warm)
    n_short, n_long = 2, 2 + n_meas
    dts = []
    for _ in range(2):
        t_s = window(n_short)
        t_l = window(n_long)
        dts.append((t_l - t_s) / (n_long - n_short))
    dt = min(d for d in dts if d > 0)
    tps = batch * T / dt
    mfu = tps * model.flops_per_token(T) / peak_flops()
    rec = {"seq_len": T, "batch": batch, "tokens_per_s": round(tps),
           "step_ms": round(dt * 1e3, 1), "mfu": round(mfu, 4)}
    print(json.dumps(rec), flush=True)
    return rec


BATCHES = {1024: 16, 4096: 4, 8192: 2, 16384: 1}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="*",
                    default=[1024, 4096, 8192, 16384])
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()
    for T in args.seqs:
        run_one(T, args.batch or BATCHES[T])


if __name__ == "__main__":
    main()
