"""executor: device time per optimizer step in class ``moe_route`` of the
class table (``optable.table``): the scope ``moe/route`` of ``ops.py``: the
router's scores over every published expert, the top-k, the weights, the sort
of the (token, slot) pairs by expert, each tile's gather and weighted scatter,
and their pull-backs: everything around the experts' products that does not
shrink with the share of the experts held. On the chip where it is largest;
nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "moe_route")
