"""driver: from the end of one ``epoch/readback`` span to the start of the
next ``epoch/dispatch``: the session's bookkeeping between two epochs and the
caller's loop, on the host's clock alone. Median over the boundaries of the
traced stretch (``gapsplit.py``). Nothing when the trace holds fewer than two
executions or no such span."""

import gapsplit


def read(run):
    found = gapsplit.read(run)
    return found and found["between_ms"]
