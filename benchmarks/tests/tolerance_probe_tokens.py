#!/usr/bin/env python3
"""``tolerance_probe.py`` for a configuration whose model is a function of
tokens (its start is ``model.init_token_model``'s, not a stack of Linears'):

    python3 benchmarks/tests/tolerance_probe_tokens.py <workload> <lower-policy> [seed]

Runs the configuration's plain reference twice on the cell's own checked
prefix (same rows, same start): under the configuration's matmul policy and
under ``<lower-policy>`` (for ``default``: ``bfloat16``), and prints the gap
between the two in units of the configuration's tolerance
(``check.compare``'s ``worst``) and of its ``loss_rtol``. The control has to
read over 1 by one of them while the benchmark's own ``reference check`` line
for the cell stays well under 1. On the chip the numbers are the real ones;
on a CPU ``default`` is float32 and the command shows that the control fails.
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
    data_dir = HERE.parent / "data" / "bench" / "tolerance_probe_tokens"
    arrays = cells.make_dataset(cell, seed, steps * batch, data_dir)
    prefix = check.prefix(arrays, steps, batch, mub)
    del arrays
    shutil.rmtree(data_dir, ignore_errors=True)
    spec = Mo.make_token_spec(Mo.token_model_config(kw["model"]), kw["seq_len"], batch)
    start = check.layers(Mo.init_token_model(spec))
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
