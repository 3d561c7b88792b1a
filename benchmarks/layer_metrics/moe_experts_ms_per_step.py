"""kernels: device time per optimizer step in class ``moe_experts`` of the
class table (``optable.table``): the scope ``moe/experts`` of ``ops.py``: the
grouped products of the routed experts this chip holds (three a tile forward,
the forward again, and the backward's), with the rounding of their operands.
On the chip where it is largest; nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "moe_experts")
