"""``work.py`` on the two configurations: the counts the rooflines rest
on, from shapes and ring width alone."""

import json
import os

import pytest

from chipbench import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_limb_pairs():
    assert work.limb_pairs(128) == 136
    assert work.limb_pairs(64) == 36


def test_dot_2048_is_1_40e13_int8_operations():
    config = _config("secure-dot-r128")
    ops = work.ring_ops(config, {"n": 2048})
    assert ops == 6 * 2048 ** 3 * 136 * 2
    assert ops == pytest.approx(1.40e13, rel=0.005)
    seconds, bound = work.least_seconds(config, {"n": 2048}, "TPU v5 lite")
    assert bound == "int8"
    assert seconds == pytest.approx(0.0357, rel=0.01)  # 36 ms at 393 TOP/s


def test_a_device_that_is_not_in_the_table_is_an_error():
    with pytest.raises(KeyError, match="peaks.json"):
        work.peaks("TPU v9 imaginary")
