"""executor: device time per optimizer step in class ``stash`` of the class
table (``optable.table``): the scopes ``stash`` and ``unstash`` of the executor's
tick branches, and the copies and slices the compiler inserts around the
stash buffers of the tick loop's carry. On the chip where it
is largest; nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "stash")
