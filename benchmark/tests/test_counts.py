"""The operation counts and the table of peaks against hand numbers."""

import json
import os

import pytest

from benchmark import peaks
from benchmark.counts import lstm_imdb_h1280, resnet50_bf16

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_lstm_step_is_1_56_tflop_at_bs128():
    # per token: (128 + 1280) x 5120 projections + 2 x 1280 x 5120
    # recurrent = 20.3 M MAC; x 2 FLOP x 3 (forward + backward) x 12,800
    cfg = _cfg("lstm_imdb_h1280")
    per_sample = lstm_imdb_h1280.step_flops_per_sample(cfg, {"seq_len": 100})
    hand = 3 * 2 * ((128 + 1280 + 2 * 1280) * 5120 * 100 + 1280 * 2)
    assert per_sample == hand
    assert 128 * per_sample == pytest.approx(1.56e12, rel=2e-3)
    assert lstm_imdb_h1280.param_count(cfg) == 24_186_882


def test_lstm_recurrence_counts():
    cfg = _cfg("lstm_imdb_h1280")
    need = lstm_imdb_h1280.lstm_seq(cfg, {"seq_len": 100}, 128)
    # 2 layers x 3 passes' worth x 2 x B x H x 4H x T
    assert need["flops"] == 2 * 3 * 2 * 128 * 1280 * 5120 * 100
    # bound by FLOPs on a v5e: 5.1 ms against 2.75 ms of bytes
    least, bound = peaks.least_seconds(need["flops"], need["bytes"],
                                       peaks.load("TPU v5 lite"))
    assert bound == "flops" and least == pytest.approx(5.11e-3, rel=1e-2)


def test_resnet50_is_3_86_gmac_an_image():
    # the 50-layer column of table 1 with the stride on the first 1x1 of
    # a stage (the paper's own, 3.8e9 multiply-adds as it says; the
    # "v1.5" with the stride on the 3x3 is the 4.1 GMAC often quoted)
    cfg = _cfg("resnet50_bf16")
    macs = resnet50_bf16.forward_macs_per_sample(cfg)
    assert macs == pytest.approx(3.858e9, rel=1e-3)
    assert resnet50_bf16.step_flops_per_sample(cfg, {}) == 3 * 2 * macs
    assert resnet50_bf16.param_count(cfg) == 25_557_032


def test_unknown_device_kind_is_an_error():
    assert peaks.load("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.load("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            peaks.load(kind)
