"""executor: device operations per optimizer step in class ``control`` of the
class table (``optable.table``): scalar integer and predicate results and
tick-table lookups that no scope names, i.e. loop and branch bookkeeping. On
the chip where it is largest; nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "control", "ops_per_step")
