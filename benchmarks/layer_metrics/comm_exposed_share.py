"""gradsync: the share of the traced window in which an all-reduce,
reduce-scatter or all-gather ran on a chip while no compute operation did
(``trace_stats``'s interval arithmetic, per chip), on the chip where it is
largest. Nothing where no such collective ran."""

import xtrace

SYNC = ("all-reduce", "reduce-scatter", "all-gather")


def read(run):
    devices = xtrace.traced_devices(run)
    if not devices:
        return None
    worst = None
    for dev in devices:
        sync = xtrace.union(
            xtrace.spans([ev for ev in dev["leaf"] if ev[0].lower().startswith(SYNC)])
        )
        if not sync:
            continue
        exposed = xtrace.total(xtrace.subtract(sync, dev["compute"]))
        share = exposed / (dev["window"][1] - dev["window"][0])
        worst = share if worst is None else max(worst, share)
    return None if worst is None else 100.0 * worst
