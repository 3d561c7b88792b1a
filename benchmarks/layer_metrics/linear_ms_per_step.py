"""kernels: device time per optimizer step in class ``linear`` of the class
table (``optable.table``): the scopes ``linear/fwd``, ``linear/dgrad`` and
``linear/wgrad`` of ``ops.py``: the matmul fusions by the program's own name,
the second source beside ``matmul_roofline``'s ``kind=kOutput``. On the chip where it
is largest; nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "linear")
