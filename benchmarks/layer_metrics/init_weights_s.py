"""setup: the host span ``session/weights`` of the program's span log
(``hostlog.py``): the host's normals (``init.linear_init`` /
``init.token_leaf_init``), their stacking and placement, the optimizer's
state. Nothing where the program keeps no span log."""

import hostlog


def read(run):
    found = hostlog.init_split(run)
    return found and found["session/weights"]
