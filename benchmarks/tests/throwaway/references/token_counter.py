"""Throwaway reference of the throwaway configuration: the three functions a
reference has, of which only the cost functions do anything. They read the
sequence length, which the configuration does not state and the mix does: the
harness hands them the configuration with the job's merged ``session``."""


def train_flops_per_sample(config):
    """A sample is one row of ``seq_len`` tokens."""
    return 6 * config["parameters_per_token"] * config["session"]["seq_len"]


def matmul_bytes_per_sample(config, rows):
    return 4 * config["parameters_per_token"] / rows + 8 * config["session"]["seq_len"]


def make_reference(config):
    raise NotImplementedError("the throwaway configuration has no model")
