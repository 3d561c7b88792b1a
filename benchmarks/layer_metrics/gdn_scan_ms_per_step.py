"""kernels: device time per optimizer step in class ``gdn_scan`` of the class
table (``optable.table``): the scope ``gdn/scan`` of ``ops.py``: the chunked gated delta rule, forward, the forward run again and backward. On the chip where it is largest; nothing
where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "gdn_scan")
