"""kernels: device time per optimizer step in class ``attn`` of the class
table (``optable.table``): the scope ``attn/core`` of ``ops.py``: blocked attention under the causal, same-document mask, forward, the forward run again and backward. On the chip where it is largest; nothing
where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "attn")
