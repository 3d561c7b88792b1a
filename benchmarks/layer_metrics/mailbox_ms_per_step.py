"""executor: device time per optimizer step in class ``mailbox`` of the class
table (``optable.table``): the scope ``mail`` (the reads of the relay mailboxes,
the payload selects, the writes after the relays) and the copies that stage a
payload for a relay or take one from it; the collective-permute itself is
``relay_ms_per_step``. On the chip where it
is largest; nothing where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "mailbox")
