"""setup: wall time before the window inside ``compile/trace`` and
``compile/lower`` events of the program's span log (Python tracing a function
to a jaxpr, the jaxpr to an MLIR module), as the length of their union
(``hostlog.py``). Nothing where the program keeps no span log."""

import hostlog


def read(run):
    found = hostlog.compile_split(run)
    return found and found["trace_lower"]
