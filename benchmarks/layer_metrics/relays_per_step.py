"""executor: ``collective-permute-start`` operations (the pipeline's relays,
one per direction in which the program issued one) per optimizer step, on the
chip that ran most of them. The tick table says how many are due
(``program_stats()``: ``relays_issued_fwd + relays_issued_bwd``); this is
what the chip really issued. Nothing where no relay ran."""

import xtrace


def read(run):
    devices = xtrace.traced_devices(run)
    if not devices:
        return None
    most = 0.0
    for dev in devices:
        starts = sum(
            1 for ev in dev["leaf"] if ev[0].lower().startswith("collective-permute-start")
        )
        most = max(most, starts / xtrace.steps_in_window(run, dev))
    return most or None
