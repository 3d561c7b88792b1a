#!/usr/bin/env python3
"""One rehearsed run of a cell with the program broken underneath:

    python3 benchmarks/tests/faulty_run.py <fault> --workload <cell> --seed <n> ...

The arguments after the fault are ``run.py``'s; ``--rehearse`` is added, so
the look for a chip is skipped and the rest of a run is driven as it is on
the chip. ``test_faults.py`` sees ``correct`` come out false for each fault.
With ``--at-size`` among them nothing is added: on the chip that reads the
fault at the cell's own size (``PERF.md`` section 4 has those readings).

``state_unchanged``  every step returns its state as it was: ``params()``
                     hands out the parameters of its first call for good;
``half_the_batch``   the second half of every batch's rows is left out and
                     the mean taken over the rest (the first half fed twice);
``no_exchange``      the gradient exchange between the replicas is left out
                     (``psum`` over ``dp`` returns what it was given).
"""

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH)]


def state_unchanged():
    from shallowspeed_tpu.api import TrainingSession

    params, first = TrainingSession.params, []

    def frozen(self):
        if not first:
            first.append(params(self))
        return copy.deepcopy(first[0])

    TrainingSession.params = frozen


def half_the_batch():
    from shallowspeed_tpu.data import Dataset

    load = Dataset.load

    def halved(self, *args, **kwargs):
        load(self, *args, **kwargs)
        rows = self.local_batch_size
        for a in (self.input_X, self.target_y):
            batches = a[: len(a) // rows * rows].reshape(-1, rows, a.shape[-1])
            batches[:, rows // 2 :] = batches[:, : rows // 2]

    Dataset.load = halved


def no_exchange():
    from jax import lax

    psum = lax.psum
    lax.psum = lambda x, axis_name, **kw: (
        x if axis_name == "dp" else psum(x, axis_name, **kw)
    )


FAULTS = {f.__name__: f for f in (state_unchanged, half_the_batch, no_exchange)}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    import run

    rest = sys.argv[2:]
    at_size = "--at-size" in rest
    rest = [a for a in rest if a != "--at-size"]
    sys.exit(run.main(rest if at_size else [*rest, "--rehearse"]))
