"""The comparison that decides ``correct``: the system's weights after the
checked prefix against the plain reference's, from the same start, on the
same data.

A weight is judged by how far it MOVED, not by how large it is: over a few
steps at the configuration's own learning rate an update is thousands of
times smaller than the weight it is applied to, so a tolerance relative to the
weight would pass a run whose every gradient was wrong by half. For each
tensor the gap ``||system - reference||`` (Euclidean norm) may be at most

    update_rtol * ||reference - start||  +  weight_ulps * ||ulp(reference)||

The norm and not the largest element: under a bfloat16 policy two correct runs
differ where one operand rounded the other way, which moves single elements
by a last bfloat16 place (0.4%) and the tensor's norm hardly at all, while a
precision dropped everywhere moves every element. The second term is what
float32 storage itself costs: an update far below the weight's last bit is
rounded onto the weight's grid in both runs, and two correct runs that summed
in another order can land one grid point apart. The configuration's file
states both numbers and the reason for them.

Every tensor of every layer is compared, whatever it is called: a layer is a
dictionary of arrays, or of further dictionaries of them (a norm's scale, a
router, a convolution's taps), and the three runs have to hold the same ones.
"""

import numpy as np


def layers(params):
    """``session.params()`` (a list of stages, each a list of layers) -> the
    layers in model order."""
    return [layer for stage in params for layer in stage]


def prefix(arrays, steps, batch, mubatches):
    """The first ``steps`` batches of each array of the training set, as the
    reference takes them: ``(steps, mubatches, rows, ...)``, copied out of
    the memory maps."""
    return [
        np.array(a[: steps * batch]).reshape(
            steps, mubatches, batch // mubatches, *a.shape[1:]
        )
        for a in arrays
    ]


def tensors(layer, path=()):
    """``{"key/path": array}`` of one layer's array leaves, keys sorted at
    every level (so ``W`` comes before ``b``)."""
    if not isinstance(layer, dict):
        return {"/".join(path): layer}
    found = {}
    for key in sorted(layer):
        found.update(tensors(layer[key], (*path, str(key))))
    return found


def compare(system, reference, start, tolerance, loss=None, ref_loss=None):
    """-> ``{"ok", "worst", "where", "loss_gap", ...}``; ``worst`` is the
    largest gap in units of what is allowed (over 1 fails)."""
    if not len(system) == len(reference) == len(start):
        raise ValueError(
            f"{len(system)} layers against the reference's {len(reference)} "
            f"from a start of {len(start)}"
        )
    worst, where, worst_max, ratios = 0.0, None, 0.0, []
    for index, trees in enumerate(zip(system, reference, start)):
        s, r, s0 = map(tensors, trees)
        if not set(s) == set(r) == set(s0):
            raise ValueError(
                f"layer {index}: the system holds {sorted(s)}, the reference "
                f"{sorted(r)}, the start {sorted(s0)}"
            )
        for key in s:
            sys_w = np.asarray(s[key], np.float64).reshape(-1)
            ref_w = np.asarray(r[key], np.float64).reshape(-1)
            moved_w = ref_w - np.asarray(s0[key], np.float64).reshape(-1)
            gap = float(np.linalg.norm(sys_w - ref_w))
            moved = float(np.linalg.norm(moved_w))
            grid = float(np.linalg.norm(np.spacing(np.abs(ref_w).astype(np.float32))))
            allowed = (
                tolerance["update_rtol"] * moved + tolerance["weight_ulps"] * grid
            )
            ratio = gap / allowed if allowed > 0 else (0.0 if gap == 0 else np.inf)
            ratios.append(round(ratio, 3))
            gap_max = float(np.max(np.abs(sys_w - ref_w)))
            moved_max = float(np.max(np.abs(moved_w)))
            if moved_max > 0:
                worst_max = max(worst_max, gap_max / moved_max)
            if not np.isfinite(gap) or ratio > worst:
                worst = ratio if np.isfinite(gap) else np.inf
                where = {
                    "layer": index, "tensor": key, "gap": gap, "moved": moved,
                    "grid": grid,
                }
    report = {"ok": bool(worst <= 1.0), "worst": worst, "where": where,
              "max_gap_over_max_moved": worst_max, "per_tensor": ratios}
    if loss is not None:
        # only where the checked prefix is a whole epoch does the program
        # hand out the prefix's own mean loss
        gap = abs(loss - ref_loss)
        report["loss"], report["ref_loss"], report["loss_gap"] = loss, ref_loss, gap
        report["ok"] = bool(
            report["ok"] and gap <= tolerance["loss_rtol"] * abs(ref_loss)
        )
    return report
