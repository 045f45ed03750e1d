"""Guards on the numbers a throughput-to-utilization conversion rests
on: `GPT.num_params` and `flops_per_token`."""
import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig


def test_flops_per_token_formula():
    cfg = GPTConfig(vocab_size=512, max_seq_len=64, hidden=32, layers=2,
                    heads=4)
    paddle.seed(0)
    m = GPT(cfg)
    # parameter count built up by hand
    V, T, C, L, F = 512, 64, 32, 2, 4 * 32
    per_block = (C * 3 * C + 3 * C) + (C * C + C) + (C * F + F) \
        + (F * C + C) + 4 * C          # qkv + proj + fc1 + fc2 + 2 LN
    expect_params = V * C + T * C + L * per_block + 2 * C
    assert m.num_params() == expect_params
    # 6N + attention seq terms at T=64
    attn = 12 * L * C * 64
    assert m.flops_per_token(64) == 6 * expect_params + attn


def test_flops_per_token_gpt2_magnitude():
    paddle.seed(0)
    m = GPT(GPTConfig())
    n = m.num_params()
    assert 120e6 < n < 130e6          # GPT-2 124M ballpark
    f = m.flops_per_token(1024)
    assert 6 * n < f < 7 * n          # attention adds ~15% at T=1024
