"""driver: how unevenly the router loads the experts held here: the most
(token, slot) pairs any one held expert of any layer took in one step
(``moe_load_max``) over the mean a held expert took a step (``moe_rows_held``
of the epoch over steps x ``moe_layers`` x ``moe_experts_held``), both from the
counter the session left with ``observability.scopes``. 1 is even. Nothing
where the program keeps no such counter."""

import cells


def read(run):
    found = cells.load_module(
        cells.HERE / "layer_metrics" / "moe_rows_per_token.py"
    ).counts(run)
    if not found:
        return None
    visits = run["session"]["steps_per_epoch"] * found["moe_layers"] * found["moe_experts_held"]
    return found["moe_load_max"] / (found["moe_rows_held"] / visits)
