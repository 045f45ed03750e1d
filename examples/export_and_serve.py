"""Train briefly, export the model as a StableHLO bundle, and serve it
through the inference predictor — no model class needed at load time."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.static import InputSpec


def main():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 2))
    sgd = opt.SGD(learning_rate=0.1, parameters=list(net.parameters()))
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(size=(64, 8)).astype(np.float32))
    y = paddle.to_tensor((rng.random(64) > 0.5).astype(np.int64))
    for _ in range(30):
        loss = paddle.nn.functional.cross_entropy(net(x), y)
        loss.backward()
        sgd.step()
        sgd.clear_grad()
    print("trained; final loss", float(loss.numpy()))

    paddle.jit.save(net, "/tmp/served_model",
                    input_spec=[InputSpec([None, 8], "float32")])
    print("exported /tmp/served_model.pdmodel + .pdiparams")

    pred = create_predictor(Config("/tmp/served_model"))
    probe = rng.normal(size=(3, 8)).astype(np.float32)
    out = pred.run([probe])[0]
    print("served logits shape:", out.shape)


if __name__ == "__main__":
    main()
