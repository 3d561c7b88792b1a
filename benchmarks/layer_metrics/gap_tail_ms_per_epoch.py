"""driver: from the end of a chip's execution of the epoch program to the end
of the ``epoch/readback`` span around it: the loss reaches the host and the
loop wakes. Median over the boundaries of the traced stretch on the chip whose
gap is largest (``gapsplit.py``). Nothing when the trace holds fewer than two
executions or no such span."""

import gapsplit


def read(run):
    found = gapsplit.read(run)
    return found and found["tail_ms"]
