#!/usr/bin/env python3
"""How the tolerance in a configuration's ``check`` was set, as a command:

    python3 benchmarks/tests/tolerance_probe.py <workload> <lower-policy> [seed]

Runs the configuration's plain reference twice on the cell's own checked
prefix (same data, same start): once under the configuration's matmul policy
and once under ``<lower-policy>`` (for ``highest``: ``default``; for
``default``: ``bfloat16``), and prints the gap between the two in units of
the configuration's tolerance (``check.compare``'s ``worst``). A tolerance is
tight enough when that number is well above 1 while the benchmark's own
``reference check`` line for the same cell stays well below it. On the chip
the numbers are the real ones; on a CPU the command only shows that it runs
(a CPU has one matmul precision).
"""

import copy
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(HERE.parent), str(HERE)]

import cells  # noqa: E402
import check  # noqa: E402


def main(workload, lower, seed=1, rehearse=False):
    from shallowspeed_tpu import model as Mo

    cell = cells.load_cell(workload, rehearse=rehearse)
    config, kw = cell["config"], cell["session"]
    batch, mub, steps = kw["global_batch_size"], kw["mubatches"], config["check"]["steps"]
    data_dir = HERE.parent / "data" / "bench" / "tolerance_probe"
    arrays = cells.make_dataset(cell, seed, steps * batch, data_dir)
    prefix = check.prefix(arrays, steps, batch, mub)
    del arrays
    shutil.rmtree(data_dir, ignore_errors=True)
    start = check.layers(Mo.init_model(Mo.make_model_spec(kw["sizes"], 1, batch)))
    reference = cells.load_module(HERE / "references" / f"{config['reference']}.py")
    lowered = copy.deepcopy(config)
    lowered["session"]["precision"] = lower
    stated, losses = reference.make_reference(config)(start, *prefix)
    lowered_out, lowered_losses = reference.make_reference(lowered)(start, *prefix)
    report = check.compare(
        lowered_out, stated, start, config["check"],
        loss=sum(lowered_losses) / steps, ref_loss=sum(losses) / steps,
    )
    report["loss_gap_over_allowed"] = report["loss_gap"] / (
        config["check"]["loss_rtol"] * abs(report["ref_loss"])
    )
    print(
        f"{workload}: {config['session']['precision']} against {lower}: "
        + json.dumps(report),
        flush=True,
    )
    return report


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    main(args[0], args[1], int(args[2]) if len(args) > 2 else 1,
         rehearse="--rehearse" in sys.argv)
