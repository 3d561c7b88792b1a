"""executor: device time per optimizer step in class ``head`` of the class
table (``optable.table``): the scopes ``embed`` and ``head/xent`` of ``ops.py``: the embedding's lookup and scatter-add, the cross-entropy over the vocabulary slice. On the chip where it is largest; nothing
where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "head")
