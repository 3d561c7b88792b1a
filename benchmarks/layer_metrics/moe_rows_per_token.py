"""driver: the (token, slot) pairs routed to the experts held here, per token
and routed layer, from the counter the session left with
``observability.scopes`` (``program_counts``: ``moe_rows_held`` of the last
epoch over ``tokens`` x ``moe_layers``): the whole model reads
``num_experts_per_tok``, a chip that holds 8 of 320 experts reads 0.2 under
even routing. Nothing where the program keeps no such counter (a program
older than it, a model without routed layers)."""

import cells


def counts(run):
    """The resident set's counts where the program routes, or ``None``."""
    found = cells.load_module(
        cells.HERE / "layer_metrics" / "attn_pairs_per_token.py"
    ).counts(run)
    if not found or not found.get("moe_rows_held") or not found.get("tokens"):
        return None
    return found


def read(run):
    found = counts(run)
    if not found:
        return None
    return found["moe_rows_held"] / (found["tokens"] * found["moe_layers"])
