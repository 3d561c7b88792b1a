"""kernels: how close the matmul fusions run to the chip's roofline.
The least time the chips could take for the samples trained in the traced
window (the larger of the model's matmul FLOPs over the peak FLOP/s and the
matmuls' HBM bytes over the peak bytes/s, both from the configuration's
reference module) over the device time of the matmul fusions (XLA's
``kind=kOutput`` fusions and bare convolutions/dots), mean over the chips.
Model FLOPs against the bf16 peak: a cell that multiplies in six bf16 passes
(``highest``) reads low by construction."""

import xtrace

MATMUL_OPS = ("convolution", "dot")


def is_matmul(ev):
    return ev[3] == "kOutput" or xtrace.op_family(ev[0]).startswith(MATMUL_OPS)


def bound(run, samples):
    """-> (seconds, which) for ``samples`` on the cell's chips."""
    kw = run["cell"]["session"]
    rows = kw["global_batch_size"] // kw.get("dp", 1) // kw["mubatches"]
    chips, peaks = run["cell"]["chips"], run["peaks"]
    by_flops = samples * run["flops_per_sample"] / (chips * peaks["flops_per_s"])
    bytes_ = samples * run["model"].matmul_bytes_per_sample(run["cell"]["config"], rows)
    by_bytes = bytes_ / (chips * peaks["hbm_bytes_per_s"])
    return max((by_flops, "flops"), (by_bytes, "bytes"))


def read(run):
    devices = xtrace.traced_devices(run)
    if not devices or not run["peaks"]:
        return None
    shares = []
    for dev in devices:
        matmul_s = sum(ev[2] for ev in dev["leaf"] if is_matmul(ev)) / 1e9
        if not matmul_s:
            continue
        samples = xtrace.steps_in_window(run, dev) * run["session"]["batch"]
        shares.append(bound(run, samples)[0] / matmul_s)
    return 100.0 * sum(shares) / len(shares) if shares else None
