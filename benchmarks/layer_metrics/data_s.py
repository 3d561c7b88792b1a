"""setup: host clock around drawing the training set from the seed and
writing it where ``data.Dataset`` reads it."""


def read(run):
    return run["setup"]["data_s"]
