"""perfbench/lib/flops.py against hand arithmetic for both configurations,
and the table of peaks."""
import json
import os

import pytest

from perfbench.lib import flops, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def _sizes(name):
    return json.load(open(os.path.join(HERE, "..", "configs", name + ".json")))


def test_gpt2_large_by_hand():
    s = _sizes("gpt2-large")
    # per layer 12 H^2 = 12 * 1280^2 = 19,660,800; x 36 = 707,788,800;
    # unembedding 50304 * 1280 = 64,389,120
    assert flops.matmul_params(s) == 707_788_800 + 64_389_120
    # 6 * 772,177,920 + 12 * 36 * 1280 * 1024
    assert flops.train_flops_per_token(s, 1024) == \
        6 * 772_177_920 + 566_231_040
    assert 773e6 < flops.num_params(s) < 776e6          # "774M" + padding


def test_gpt2_medium_by_hand():
    s = _sizes("gpt2-medium")
    # 12 * 1024^2 * 24 = 301,989,888; 50304 * 1024 = 51,511,296
    assert flops.matmul_params(s) == 301_989_888 + 51_511_296
    assert flops.train_flops_per_token(s, 1024) == \
        6 * 353_501_184 + 12 * 24 * 1024 * 1024
    assert 354e6 < flops.num_params(s) < 356e6


def test_adam_bytes():
    # bf16 params and grads, fp32 moments: 3 * 2 + 4 * 4 = 22 B an element
    assert flops.adam_step_bytes(1000, 2, 4) == 22_000


def test_peaks_table():
    assert peaks.chip_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.chip_peaks("TPU v9 imaginary")
