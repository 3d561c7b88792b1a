"""setup: the host span ``session/data`` of the program's span log
(``hostlog.py``): reading the training set back from disk (``data.Dataset``),
its reshaping, its placement on the device and, on the sequential path, its
re-orientation there; summed over the phase's blocks. Nothing where the
program keeps no span log."""

import hostlog


def read(run):
    hostlog.report(run)
    found = hostlog.init_split(run)
    return found and found["session/data"]
