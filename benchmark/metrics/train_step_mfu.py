"""The whole step's share of the chips' peak FLOP/s: DeepFM's forward and
backward operations per example, times examples per second of the traced
window, over chips times peak. A small fraction of 1% here (the dense net
is tiny); it stays so that a whole-step share bounds any later claim."""

from .. import counts
from ._common import peaks, traced_step_s

TIMING = True


def read(run):
    step_s = traced_step_s(run)
    if step_s is None:
        return None
    config = run["config"]
    rate = config["batch"] / step_s
    return 100.0 * counts.dense_flops_per_example(config) * rate \
        / (config["chips"] * peaks(run)["flops_per_s"])
