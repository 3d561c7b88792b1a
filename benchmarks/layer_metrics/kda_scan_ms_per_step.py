"""kernels: device time per optimizer step in class ``kda_scan`` of the class
table (``optable.table``): the scope ``kda/scan`` of ``ops.py``: the chunked
delta rule with a decay per key channel, forward, the forward run again and
backward. On the chip where it is largest; nothing where there is no class
table."""

import optable


def read(run):
    return optable.class_value(run, "kda_scan")
