"""device: one minus the union of the intervals in which any operation ran,
over the traced window, on the chip where that is largest."""

import xtrace


def read(run):
    devices = xtrace.traced_devices(run)
    if not devices:
        return None
    return 100.0 * max(
        1.0 - xtrace.total(dev["busy"]) / (dev["window"][1] - dev["window"][0])
        for dev in devices
    )
