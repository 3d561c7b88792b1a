"""setup: the host spans ``session/lower`` (a mesh's device grid and its tick
program) plus ``session/program`` (the epoch function, the cost model, the
audit's contract) of the program's span log (``hostlog.py``). Nothing where
the program keeps no span log."""

import hostlog


def read(run):
    found = hostlog.init_split(run)
    return found and found["session/lower"] + found["session/program"]
