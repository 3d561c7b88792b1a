"""executor: device time per optimizer step in class ``grad_acc`` of the class
table (``optable.table``): the scope ``acc``: adding a microbatch's weight
gradients to the step's accumulators, and the copies of those accumulators. On the chip where it
is largest; nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "grad_acc")
