"""driver: host clock around each ``train_epoch()`` of the measured window,
99th percentile by nearest rank (the slowest epoch, in a window of fewer than
a hundred)."""

import math


def read(run):
    times = sorted(t1 - t0 for t0, t1, _ in run["window"]["epochs"])
    return times[math.ceil(0.99 * len(times)) - 1] * 1e3
