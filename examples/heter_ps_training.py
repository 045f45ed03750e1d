"""Heterogeneous PS training (SURVEY §2 row 33): sparse embeddings on
the host-DRAM table server, the dense tower in one jitted accelerator
step — pull -> jit(step, rows grad as output) -> async push, with
prefetch-overlapped pulls.

    JAX_PLATFORMS=cpu python examples/heter_ps_training.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu.distributed.ps import HeterTrainer, PSClient, PSServer

EMB_DIM, VOCAB, B = 16, 1000, 64


class DenseTower(nn.Layer):
    """The accelerator tier: everything downstream of the embedding
    pool. The sparse tier (the embedding table itself) never leaves the
    server's host memory."""

    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(EMB_DIM + 4, 64)
        self.fc2 = nn.Linear(64, 2)

    def forward(self, pooled, feats):
        h = paddle.concat([pooled, feats], axis=-1)
        return self.fc2(F.relu(self.fc1(h)))


def make_batches(rng, n):
    out = []
    for _ in range(n):
        lens = rng.integers(1, 5, B)                 # ragged id bags
        keys = rng.integers(0, VOCAB, lens.sum()).astype(np.uint64)
        lod = np.zeros(B + 1, np.int64)
        np.cumsum(lens, out=lod[1:])
        feats = rng.normal(size=(B, 4)).astype(np.float32)
        labels = (keys[lod[:-1]] % 2).astype(np.int64)   # sparse-only signal
        out.append((keys, lod, feats, labels))
    return out


def main():
    paddle.seed(0)
    with PSServer() as srv:
        client = PSClient(srv.endpoint)
        model = DenseTower()
        adam = opt.Adam(learning_rate=2e-2,
                        parameters=list(model.parameters()))
        trainer = HeterTrainer(client, model, EMB_DIM, adam,
                               table=0, lr_sparse=0.5)
        batches = make_batches(np.random.default_rng(0), 20)
        for epoch in range(5):
            losses = trainer.train(batches, epochs=1)
            print(f"epoch {epoch}: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
        trainer.write_back()              # dense params back onto the layer
        client.save("/tmp/heter_tables")  # sparse tier snapshot (server-side)
        client.close()
    print("done: dense tier trained on-device, sparse tier on the PS host")


if __name__ == "__main__":
    main()
