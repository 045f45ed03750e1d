"""The yardstick's arithmetic: operations, bytes, peaks."""
import pytest

from chipbench import formulas, harness, peaks

gpt = harness.load_family("gpt")

TINY = {"vocab_size": 512, "max_seq_len": 128, "hidden": 64, "layers": 2,
        "heads": 4}
GPT2 = {"vocab_size": 50304, "max_seq_len": 1024, "hidden": 768,
        "layers": 12, "heads": 12}
GPT3 = {"vocab_size": 50304, "max_seq_len": 2048, "hidden": 2048,
        "layers": 24, "heads": 16}


def test_param_count_is_the_programs():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, gpt_tiny

    paddle.seed(0)
    m = GPT(gpt_tiny())
    assert gpt.param_count(TINY) == m.num_params()
    assert gpt.train_flops_per_token(TINY, 128) == \
        m.flops_per_token(128)


@pytest.mark.parametrize("cfg,lo,hi", [(GPT2, 120e6, 130e6),
                                       (GPT3, 1.30e9, 1.33e9)])
def test_param_count_magnitudes(cfg, lo, hi):
    assert lo < gpt.param_count(cfg) < hi


def test_train_flops_per_token_gpt2():
    n = gpt.param_count(GPT2)
    f = gpt.train_flops_per_token(GPT2, 1024)
    assert f == 6 * n + 12 * 12 * 768 * 1024
    assert 855e6 < f < 865e6           # 860.1 MFLOP per token


@pytest.mark.parametrize("backward", [False, True])
def test_flash_attention_cost(backward):
    B, H, T, D = 8, 12, 1024, 64
    flops, nbytes = formulas.flash_attention_cost(
        B, H, T, D, causal=True, backward=backward)
    one_matmul = 2 * B * H * T * T * D // 2
    panel = B * H * T * D * 2
    if backward:
        assert flops == 4 * one_matmul
        assert nbytes == 8 * panel + 2 * B * H * T * 4
    else:
        assert flops == 2 * one_matmul
        assert nbytes == 4 * panel + B * H * T * 4
    full, _ = formulas.flash_attention_cost(B, H, T, D, causal=False,
                                            backward=backward)
    assert full == 2 * flops


def test_attention_flops_agree_with_the_model_formula():
    # forward + backward, not causal, all layers, per token
    B, T = 8, 1024
    fwd, _ = formulas.flash_attention_cost(B, 12, T, 64, causal=False)
    bwd, _ = formulas.flash_attention_cost(B, 12, T, 64, causal=False,
                                           backward=True)
    per_token = 12 * (fwd + bwd) / (B * T)
    assert per_token == 12 * 12 * 768 * 1024


def test_decode_step_bytes():
    w = gpt.decode_weight_bytes(GPT3)
    assert 5.2e9 < w < 5.3e9           # 5.24 GB of float32 weights
    assert gpt.kv_bytes_per_token(GPT3) == 24 * 2 * 2048 * 4
    assert gpt.decode_step_bytes(GPT3, 0) == w
    assert gpt.decode_step_bytes(GPT3, 1000) - w == \
        1000 * 24 * 2 * 2048 * 4
    # a dense model reads every weight whatever its rows are
    assert gpt.decode_step_bytes(GPT3, 1000, rows=6) == \
        gpt.decode_step_bytes(GPT3, 1000)


def test_the_family_hands_the_kernel_its_own_heads():
    assert gpt.flash_attention_costs(GPT2, 8, 1024) == [
        formulas.flash_attention_cost(8, 12, 1024, 64, causal=True,
                                      backward=b) for b in (False, True)]


def test_roofline_share_says_which_bound():
    peak = peaks.peak("TPU v5 lite")
    share, bound = formulas.roofline_share(0, 819e9, 2.0, peak)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = formulas.roofline_share(197e12, 1, 1.0, peak)
    assert bound == "flops" and share == pytest.approx(100.0)


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite") == {"flops": 197e12,
                                         "bytes_per_s": 819e9}
    for kind in ("cpu", "v5e", "TPU v9 imaginary"):
        with pytest.raises(ValueError, match="no peak recorded"):
            peaks.peak(kind)
