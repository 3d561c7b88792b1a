"""executor: device operation events per optimizer step (every
event of the ``XLA Ops`` line, loops included), mean over the chips."""

import xtrace


def read(run):
    devices = xtrace.traced_devices(run)
    if not devices:
        return None
    per_chip = [
        len(dev["ops"]) / xtrace.steps_in_window(run, dev)
        for dev in devices
    ]
    return sum(per_chip) / len(per_chip)
