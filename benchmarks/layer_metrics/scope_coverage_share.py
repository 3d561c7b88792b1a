"""device: the share of the device time of the epoch program's operations
(containers dropped: ``optable.table``) that falls in a class the program
named, i.e. any class but ``unattributed`` and ``unresolved``; on the chip
where it is smallest. Nothing where there is no class table."""

import optable


def read(run):
    found = optable.table(run)
    if found is None:
        return None
    return min(chip["coverage"] for chip in found["chips"])
