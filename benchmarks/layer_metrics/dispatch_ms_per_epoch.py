"""driver: the median duration of the host span ``epoch/dispatch``
(``api.py``: the call into the epoch program; on a mesh it holds the
re-slicing of the training set from chip 0) in the trace. Nothing where the
program writes no such span."""

import optable


def read(run):
    return optable.host_span_ms(run, "epoch/dispatch")
