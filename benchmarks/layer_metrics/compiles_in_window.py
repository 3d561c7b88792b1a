"""device: programs traced, lowered, compiled or loaded from the cache inside
the measured window, from JAX's ``/jax/core/compile/*`` events. Expected 0;
anything else also makes the record ``correct: false``."""


def read(run):
    return run["compiles_in_window"]
