"""setup: host clock around ``TrainingSession(...)``: reading the training
set back from disk, ``linear_init`` on the host, the transfers to the device,
schedule lowering."""


def read(run):
    return run["setup"]["init_s"]
