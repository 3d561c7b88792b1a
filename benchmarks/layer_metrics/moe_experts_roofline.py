"""kernels: how close the held experts' grouped products run to the chip's
roofline: the least time the chip could take for one step's (the larger of
their FLOPs over the peak FLOP/s and their HBM bytes over the peak bytes/s,
both from the configuration's reference module: 6 FLOPs a weight over the
rows the router actually sent, by the session's counter; a row's input,
output and gradients, and each held expert's weights once a microbatch and
pass) over the device time of class ``moe_experts`` per step, the recomputed
forward and the padding of each expert's last tile included. Nothing where
there is no class table, no counter or no such class."""

import cells
import optable


def read(run):
    found = cells.load_module(
        cells.HERE / "layer_metrics" / "moe_rows_per_token.py"
    ).counts(run)
    ms = optable.class_value(run, "moe_experts")
    if not found or not ms or not run["peaks"]:
        return None
    model = run["model"]
    m = model.model_config(run["cell"]["config"])
    rows = found["moe_rows_held"] / run["session"]["steps_per_epoch"]
    visits = (
        found["moe_layers"] * found["moe_experts_held"]
        * run["cell"]["session"]["mubatches"]
    )
    least_s = max(
        model.moe_train_flops(m, rows) / run["peaks"]["flops_per_s"],
        model.moe_train_bytes(m, rows, visits) / run["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (ms / 1e3)
