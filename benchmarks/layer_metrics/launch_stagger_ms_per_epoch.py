"""driver: within one epoch, the last chip's start of the epoch program less
the first chip's: how far apart one dispatch launches the chips of a mesh.
Median over the dispatches of the traced stretch that launched every chip
(``gapsplit.py``). Nothing on one chip, or when the trace holds no dispatch
span."""

import gapsplit


def read(run):
    found = gapsplit.read(run)
    return found and found["stagger_ms"]
