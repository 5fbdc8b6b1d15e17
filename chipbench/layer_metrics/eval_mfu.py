"""The whole evaluation's share of the chip's int8 peak: the ring work
of one evaluation (``chipbench/work.py``) times the evaluations per
second of the traced window, over the published peak."""

from chipbench import work

NAME = "eval_mfu"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "whole evaluation"
MOVES = "evals_per_s"


def read(view):
    if view.trace is None or not view.trace["window_s"]:
        return None
    rate = len(view.trace["evaluations"]) / view.trace["window_s"]
    peak = work.peaks(view.device_kind)["int8_ops_per_s"]
    return 100.0 * work.ring_ops(view.config, view.size) * rate / peak
