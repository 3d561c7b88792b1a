"""schedules: the idle share the tick table PLANS, FLOP-weighted
(``program_stats(...)["weighted_bubble_fraction"]`` of the schedule lowered
for the mix's stages and microbatches). A count: it repeats exactly and needs
no chip. Nothing on a layout without pipeline stages."""

from shallowspeed_tpu import schedules
from shallowspeed_tpu.parallel import lower_schedule
from shallowspeed_tpu.parallel.lowering import program_stats


def read(run):
    kw = run["cell"]["session"]
    if kw.get("pp", 1) < 2:
        return None
    prog = lower_schedule(
        schedules.SCHEDULES[kw["schedule"]],
        kw["mubatches"],
        kw["pp"],
        virtual=kw.get("virtual_stages", 1),
        backward_split=kw.get("backward_split", False),
        recompute=kw.get("recompute", False),
    )
    return 100.0 * program_stats(prog)["weighted_bubble_fraction"]
