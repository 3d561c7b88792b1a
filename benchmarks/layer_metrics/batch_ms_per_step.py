"""driver: device time per optimizer step in class ``batch`` of the class
table (``optable.table``): the scope ``batch`` and whatever moves the arguments
``X``/``Y``: cutting a step's batch out of the resident set, padding and
reshaping it, picking a microbatch's rows. On the chip where it
is largest; nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "batch")
