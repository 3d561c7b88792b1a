"""executor: time inside collective-permute operations (the pipeline's
relays, including the wait for the neighbouring stage) per optimizer step, on
the chip where it is longest. Nothing where no relay ran."""

import xtrace


def read(run):
    devices = xtrace.traced_devices(run)
    if not devices:
        return None
    worst = 0.0
    for dev in devices:
        relay = sum(
            ev[2] for ev in dev["leaf"] if ev[0].lower().startswith("collective-permute")
        )
        worst = max(worst, relay / 1e6 / xtrace.steps_in_window(run, dev))
    return worst or None
