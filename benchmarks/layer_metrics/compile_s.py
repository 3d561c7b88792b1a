"""setup: wall time inside JAX's compile spans before the window (tracing,
lowering, XLA compilation or the load from the persistent cache), as a union
of the spans."""


def read(run):
    return run["setup"]["compile_s"]
