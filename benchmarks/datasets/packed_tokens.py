"""Generator ``packed_tokens``: a language model's training job (the
``model-configs`` guide's ``workloads.md``, "Training: what a real job is
like"), drawn from ``--seed``: documents of heavy-tailed length, concatenated
and cut into rows of ``seq_len + 1`` token ids with no padding.

Document lengths are lognormal (median 1,024, sigma 1.2), clipped to
16 ... ``seq_len``. Token ids are Zipf (exponent 1.1) over ``data.vocab_size``
ranks, under a permutation drawn from the seed, so that the frequent ids are
not the small ones. Documents are packed greedily in the order drawn: a
document that reaches past the end of a row is cut there and goes on in the
next row as that row's first segment, as in a job that concatenates and
chunks; the last document of a chunk of rows is cut for good. A row's
inputs are ``[:, :-1]`` and its targets ``[:, 1:]``; ``segments`` numbers the
documents of a row from 0 and says where attention and recurrent state stop.

Drawn in fixed chunks of rows, each from its own child of the seed, as
``gaussian_clusters`` is, so the set is the same whatever the number of
threads. ``seq_len`` is the job's (the merged ``session``), ``vocab_size``
the configuration's (its ``data`` block).
"""

import os
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

CHUNK_ROWS = 16
LENGTH_MEDIAN = 1024
LENGTH_SIGMA = 1.2
LENGTH_MIN = 16
ZIPF_EXPONENT = 1.1


def _threads():
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def document_lengths(rng, tokens, seq_len):
    """Lengths of documents drawn until they hold ``tokens`` tokens or more."""
    found, have = [], 0
    mean = LENGTH_MEDIAN * np.exp(LENGTH_SIGMA**2 / 2)
    while have < tokens:
        n = int((tokens - have) / min(mean, seq_len)) + 8
        drawn = rng.lognormal(np.log(LENGTH_MEDIAN), LENGTH_SIGMA, n)
        drawn = np.clip(np.rint(drawn), min(LENGTH_MIN, seq_len), seq_len)
        found.append(drawn.astype(np.int64))
        have += int(found[-1].sum())
    return np.concatenate(found)


def make_dataset(seed, rows, session, data, data_dir):
    """Write ``tokens_train.npy`` and ``segments_train.npy``, both ``int32
    (rows, seq_len + 1)``, and return them as the memory maps they were drawn
    into."""
    width, vocab = session["seq_len"] + 1, data["vocab_size"]
    data_dir = Path(data_dir)
    data_dir.mkdir(parents=True, exist_ok=True)
    n_chunks = -(-rows // CHUNK_ROWS)
    head, *children = np.random.SeedSequence(seed).spawn(1 + n_chunks)
    ids = np.random.Generator(np.random.PCG64(head)).permutation(vocab).astype(np.int32)
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -ZIPF_EXPONENT)
    cdf /= cdf[-1]
    tokens, segments = (
        np.lib.format.open_memmap(
            data_dir / f"{name}_train.npy", mode="w+", dtype=np.int32, shape=(rows, width)
        )
        for name in ("tokens", "segments")
    )

    def draw(i):
        a, b = i * CHUNK_ROWS, min(rows, (i + 1) * CHUNK_ROWS)
        rng = np.random.Generator(np.random.PCG64(children[i]))
        ends = np.cumsum(document_lengths(rng, (b - a) * width, width - 1))
        at = np.arange((b - a) * width)
        document = np.searchsorted(ends, at, side="right").reshape(b - a, width)
        segments[a:b] = document - document[:, :1]
        # the clip guards the last rank against a draw that rounds up to 1.0
        ranks = np.searchsorted(cdf, rng.random(at.size)).clip(max=vocab - 1)
        tokens[a:b] = ids[ranks].reshape(b - a, width)

    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(draw, range(n_chunks)))
    return tokens, segments
