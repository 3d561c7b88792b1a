"""schedules: the idle share MEASURED: over the executions of the
epoch program in the trace, one minus the time a compute operation (anything
but a collective or a relay) ran, over the executions' length; mean over the
chips. A stage that waits for its neighbour sits inside a collective-permute,
so this, not the device's idle share, is the measured bubble."""

import xtrace


def read(run):
    devices = xtrace.traced_devices(run)
    if not devices:
        return None
    module = xtrace.main_module(devices)
    shares = []
    for dev in devices:
        envelope = xtrace.union(
            xtrace.spans([ev for ev in dev["modules"] if ev[0] == module])
        )
        if not envelope:
            continue
        idle = xtrace.subtract(envelope, dev["compute"])
        shares.append(xtrace.total(idle) / xtrace.total(envelope))
    return 100.0 * sum(shares) / len(shares) if shares else None
