"""driver: the chip's idle time between one execution of the epoch program
and the next (dispatch, loss readback and whatever else the host does per
epoch), median over the executions in the trace and over the chips. Nothing
when the trace holds fewer than two executions."""

import statistics

import xtrace


def read(run):
    devices = xtrace.traced_devices(run)
    if not devices:
        return None
    module = xtrace.main_module(devices)
    found = []
    for dev in devices:
        runs = sorted(
            (ev for ev in dev["modules"] if ev[0] == module), key=lambda ev: ev[1]
        )
        found += [b[1] - (a[1] + a[2]) for a, b in zip(runs, runs[1:])]
    return statistics.median(found) / 1e6 if found else None
