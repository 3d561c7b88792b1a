"""executor: device time per optimizer step in class ``update`` of the class
table (``optable.table``): the scope ``update`` (``optimizer.py``: apply, clip,
norms) and the copies of parameters and optimizer state around the loops. On the chip where it
is largest; nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "update")
