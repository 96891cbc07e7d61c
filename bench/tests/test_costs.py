import json

import pytest

import costs
from harness import BENCH


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_v5e_peaks_are_the_published_ones():
    pk = costs.peaks_for("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12
    assert pk["int8_ops"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in pk["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        costs.peaks_for("TPU v99 imaginary")


def test_mpgemm_cost_counts_packed_weights_activations_and_outputs():
    # 2048 x 2040 ternary at g=5 (408 groups), 4096 bf16 tokens, bf16 out
    ops, nbytes = costs.mpgemm_cost(m=2048, kg=408, g=5, n=4096,
                                    act_bytes=2, out_bytes=2)
    assert ops == 2 * 2048 * 2040 * 4096
    assert nbytes == (2048 * 408 + 2040 * 4096 * 2 + 2048 * 4096 * 2
                      + 4 * (2048 + 4096))


def test_least_time_is_the_binding_roof():
    assert costs.least_time(393e12, 1.0, 393e12, 819e9) == pytest.approx(1.0)
    assert costs.least_time(1.0, 819e9, 393e12, 819e9) == pytest.approx(1.0)


def test_ops_per_token_of_the_configurations():
    # internlm2-1.8b: 24 layers of 62.9M ternary weights, 3.0 GOP a token
    assert costs.mpgemm_ops_per_token(_cfg("internlm2-1.8b")) == pytest.approx(
        2 * 24 * (2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192))
    assert 3.0e9 < costs.mpgemm_ops_per_token(_cfg("internlm2-1.8b")) < 3.05e9
    lm = _cfg("internlm2-1.8b")
    assert costs.head_ops(lm) == 2 * 2048 * 92544
    assert costs.attention_ops(lm, 100) == 4 * 16 * 128 * 100 * 24
