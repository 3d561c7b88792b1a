"""driver: the width of the interval in which the offset between the device's
clock and the host's may lie, from causality over every epoch of the traced
stretch (an execution cannot begin before its dispatch, a readback cannot end
before its execution): smallest head plus smallest tail (``gapsplit.py``). No
tail or head of the gap readers is off by more than this. Nothing when the
trace holds fewer than two executions or no such span."""

import gapsplit


def read(run):
    found = gapsplit.read(run)
    return found and found["clock_slack_ms"]
