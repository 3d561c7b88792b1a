"""kernels: how close the per-channel delta rule runs to the chip's roofline:
the least time the chip could take for one step's scans (the larger of the
recurrence's FLOPs over the peak FLOP/s and its HBM bytes over the peak
bytes/s, both from the configuration's reference module: the rule in its
recurrence form, and each of ``q, k, v, g, beta, o`` and their gradients
through HBM once a pass) over the device time of class ``kda_scan`` per step,
the chunked form's extra products and the recomputed forward included.
Nothing where there is no class table, no such class, or a reference without
``kda`` layers."""

import optable


def read(run):
    ms = optable.class_value(run, "kda_scan")
    seq_len = run["cell"]["session"].get("seq_len")
    if not ms or not run["peaks"] or seq_len is None:
        return None
    model = run["model"]
    m = model.model_config(run["cell"]["config"])
    layers = sum(kind == "kda" for kind in m.get("layer_types", ()))
    if not layers:
        return None
    tokens = run["session"]["batch"] * seq_len
    least_s = layers * max(
        model.scan_train_flops(m, tokens) / run["peaks"]["flops_per_s"],
        model.scan_train_bytes(m, tokens) / run["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (ms / 1e3)
