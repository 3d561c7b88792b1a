"""setup: wall time before the window inside ``compile/cache_load`` events:
executables read back from JAX's persistent cache (``hostlog.py``). Nothing
where the program keeps no span log."""

import hostlog


def read(run):
    found = hostlog.compile_split(run)
    return found and found["cache_load"]
