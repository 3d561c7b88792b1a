"""driver: the (query, key) pairs the causal, same-document mask admits per
token of the resident set, from the counter the session left with
``observability.scopes`` (``program_counts``: tokens, documents, pairs per
epoch): what packing left of the causal mask's half a row. Nothing where the
program keeps no such counter (a program older than it, a model without
tokens)."""

import optable


def counts(run):
    """The resident set's counts, or ``None``."""
    found = optable.table(run)
    if found is None:
        return None
    try:
        from shallowspeed_tpu.observability.scopes import program_counts
    except ImportError:  # a program from before the counter
        return None
    return program_counts(found["module"])


def read(run):
    found = counts(run)
    if not found or not found.get("tokens"):
        return None
    return found["pairs"] / found["tokens"]
