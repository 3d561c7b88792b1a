"""driver: tokens trained per second in the measured window: ``samples_per_s``
times the job's ``seq_len`` (a token model's sample is one row). Nothing
where the job has no sequence length."""


def read(run):
    seq_len = run["cell"]["session"].get("seq_len")
    if seq_len is None:
        return None
    return run["window"]["samples_per_s"] * seq_len
