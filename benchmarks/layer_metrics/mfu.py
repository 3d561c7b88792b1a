"""kernels: model FLOP/s utilization of the measured window: samples
per second times the model's training FLOPs per sample over the chips' bf16
peak (``yardstick.mfu``). It is ``samples_per_s`` times a constant of the
cell; a ``highest`` cell reads low by construction."""

import yardstick


def read(run):
    if not run["peaks"]:
        return None
    return 100.0 * yardstick.mfu(
        run["window"]["samples_per_s"],
        run["flops_per_sample"],
        run["cell"]["chips"],
        run["peaks"],
    )
