"""driver: host clock around each ``train_epoch()`` of the measured window,
median."""

import statistics


def read(run):
    times = [t1 - t0 for t0, t1, _ in run["window"]["epochs"]]
    return statistics.median(times) * 1e3
