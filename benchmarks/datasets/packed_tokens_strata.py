"""Generator ``packed_tokens_strata``: ``packed_tokens``' training job with
the two things that made a small set's WORK depend on the seed taken out of
the seed's hands. A step of a few rows stands for a job's batch of millions
of tokens, whose mix of document lengths and whose frequent ids do not change
from step to step; drawn from the seed, a set of four rows holds ten or so
documents, and the pairs its attention visits and the load of the few experts
a chip holds swing by tens of per cent from seed to seed.

What the seed no longer draws:

1. The document lengths. They are the mid-quantiles ``(i + 1/2) / n`` of
   ``packed_tokens``' clipped lognormal (median 1,024, sigma 1.2, 16 ...
   ``seq_len``) for the least ``n`` whose lengths hold the set's tokens: the
   distribution's strata, one document each, in an order drawn from the
   constant 0. Packed as ``packed_tokens`` packs (greedily, in that order, a
   document that reaches past a row's end goes on in the next row), so
   ``segments`` is the same array for every seed.
2. The permutation that says which ids are the frequent ones: drawn from the
   constant 0. (The model's weights do not follow the seed, so under a
   permutation from the seed whether the top ids' experts are among the few
   held here changed from seed to seed.)

What the seed still draws: the Zipf-1.1 ranks themselves, in ``packed_tokens``'
fixed chunks of rows, each from its own child of the seed. Every seed trains
on other tokens; the attention pairs are equal and the experts' rows differ
by sampling alone.
"""

import importlib.util
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from statistics import NormalDist

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_packed_tokens", Path(__file__).resolve().parent / "packed_tokens.py"
)
packed_tokens = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(packed_tokens)

CONSTANT = 0  # what draws the order of the documents and the frequent ids


def stratum_lengths(n, seq_len):
    """The ``n`` mid-quantiles of the clipped lognormal, ascending."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.rint(packed_tokens.LENGTH_MEDIAN * np.exp(packed_tokens.LENGTH_SIGMA * z))
    return np.clip(lengths, min(packed_tokens.LENGTH_MIN, seq_len), seq_len).astype(np.int64)


def document_lengths(tokens, seq_len):
    """One document a stratum, for the least number of strata that hold
    ``tokens`` tokens, in the constant's order."""
    n = 1
    while stratum_lengths(n, seq_len).sum() < tokens:
        n += 1
    order = np.random.Generator(np.random.PCG64(CONSTANT)).permutation(n)
    return stratum_lengths(n, seq_len)[order]


def make_dataset(seed, rows, session, data, data_dir):
    """Write ``tokens_train.npy`` and ``segments_train.npy``, both ``int32
    (rows, seq_len + 1)``, and return them as the memory maps they were drawn
    into."""
    width, vocab = session["seq_len"] + 1, data["vocab_size"]
    data_dir = Path(data_dir)
    data_dir.mkdir(parents=True, exist_ok=True)
    chunk_rows = packed_tokens.CHUNK_ROWS
    n_chunks = -(-rows // chunk_rows)
    children = np.random.SeedSequence(seed).spawn(n_chunks)
    ids = (
        np.random.Generator(np.random.PCG64(CONSTANT)).permutation(vocab).astype(np.int32)
    )
    cdf = np.cumsum(
        np.arange(1, vocab + 1, dtype=np.float64) ** -packed_tokens.ZIPF_EXPONENT
    )
    cdf /= cdf[-1]
    tokens, segments = (
        np.lib.format.open_memmap(
            data_dir / f"{name}_train.npy", mode="w+", dtype=np.int32, shape=(rows, width)
        )
        for name in ("tokens", "segments")
    )
    ends = np.cumsum(document_lengths(rows * width, width - 1))
    document = np.searchsorted(ends, np.arange(rows * width), side="right").reshape(rows, width)
    segments[:] = document - document[:, :1]

    def draw(i):
        a, b = i * chunk_rows, min(rows, (i + 1) * chunk_rows)
        rng = np.random.Generator(np.random.PCG64(children[i]))
        # the clip guards the last rank against a draw that rounds up to 1.0
        ranks = np.searchsorted(cdf, rng.random((b - a) * width)).clip(max=vocab - 1)
        tokens[a:b] = ids[ranks].reshape(b - a, width)

    with ThreadPoolExecutor(packed_tokens._threads()) as pool:
        list(pool.map(draw, range(n_chunks)))
    return tokens, segments
