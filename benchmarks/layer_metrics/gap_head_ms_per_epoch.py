"""driver: from the start of an ``epoch/dispatch`` span to the start of the
chip's next execution of the epoch program: the call reaches the chip (on a
mesh, behind the re-slicing of the training set). Median over the boundaries
of the traced stretch on the chip whose gap is largest (``gapsplit.py``).
Nothing when the trace holds fewer than two executions or no such span."""

import gapsplit


def read(run):
    found = gapsplit.read(run)
    return found and found["head_ms"]
