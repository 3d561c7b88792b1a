"""setup: wall time before the window inside ``compile/backend`` events (XLA
and Mosaic compiling, or the persistent cache answering) less the
``compile/cache_load`` events inside them: what the backend really compiled
(``hostlog.py``). Nothing where the program keeps no span log."""

import hostlog


def read(run):
    found = hostlog.compile_split(run)
    return found and found["backend"]
