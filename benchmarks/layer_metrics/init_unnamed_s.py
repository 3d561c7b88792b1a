"""setup: the root span ``session/init`` less its phases (``session/data``,
``weights``, ``lower``, ``program``, ``resume``): what of
``TrainingSession.__init__`` no phase names (``hostlog.py``). Nothing where
the program keeps no span log."""

import hostlog


def read(run):
    found = hostlog.init_split(run)
    return found and found["unnamed"]
