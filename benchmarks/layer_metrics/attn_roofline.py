"""kernels: how close the attention core runs to the chip's roofline: the
least time the chip could take for one step's attention (the larger of its
FLOPs over the peak FLOP/s and its HBM bytes over the peak bytes/s, both from
the configuration's reference module: two products forward and four backward
over the pairs the mask ADMITS, by the session's counter, and ``q, k, v, o``
and their gradients through HBM once a pass) over the device time of class
``attn`` per step, recomputed forward and visited-but-masked pairs included.
Nothing where there is no class table, no counter or no such class."""

import cells
import optable

HERE = cells.HERE


def read(run):
    counts = cells.load_module(HERE / "layer_metrics" / "attn_pairs_per_token.py").counts(run)
    ms = optable.class_value(run, "attn")
    if not counts or not ms or not run["peaks"]:
        return None
    model = run["model"]
    m = model.model_config(run["cell"]["config"])
    layers = sum(kind == "full_attention" for kind in m["layer_types"])
    steps = run["session"]["steps_per_epoch"]
    flops = layers * model.attention_train_flops(m, counts["pairs"] / steps)
    bytes_ = layers * model.attention_train_bytes(m, counts["tokens"] / steps)
    least_s = max(
        flops / run["peaks"]["flops_per_s"], bytes_ / run["peaks"]["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (ms / 1e3)
