"""``benchmark/flops`` against hand counts."""
import json
import os

import pytest

from benchmark.flops import gpt

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_step():
    cfg = _config("gpt2-medium")
    traffic = {"batch": 32, "seq_len": 1024}
    # 12 L D^2 + D V matmul parameters
    assert gpt.matmul_params(cfg) == 12 * 24 * 1024 ** 2 + 1024 * 50257
    # 6 x 353.5 M x 32,768 tokens + causal attention 6 L T D a token
    by_hand = 6 * 353.453056e6 * 32768 + 6 * 24 * 1024 * 1024 * 32768
    assert gpt.step_flops(cfg, traffic) == pytest.approx(by_hand, rel=1e-9)
    assert gpt.step_flops(cfg, traffic) == pytest.approx(7.44e13, rel=2e-3)
    ops, nbytes = gpt.kernel_work(cfg, traffic, "loss_head")
    assert ops == 6 * 32768 * 1024 * 50257
    ops, nbytes = gpt.kernel_work(cfg, traffic, "attention")
    assert ops == 6 * 24 * 1024 * 1024 * 32768
    assert nbytes == 12 * 24 * 32768 * 1024 * 2
    with pytest.raises(KeyError):
        gpt.kernel_work(cfg, traffic, "no such work")
