"""Generator ``gaussian_clusters``: a training set of float rows with one-hot
targets, drawn from ``--seed``. The width of a row and the number of classes
are the first and the last of the session's ``sizes``.

The arithmetic is ``prepare_data._load_synthetic`` followed by
``prepare_data.prepare``'s centring (Gaussian class clusters, noise of
standard deviation 2, scaled into [0, 1] by the set's own range, then
mean-centred; one-hot targets), written as the ``x_train.npy`` /
``y_train.npy`` pair ``shallowspeed_tpu.data.Dataset`` reads. What differs is
how it is drawn: in fixed chunks, each from its own child of the seed, so the
set is the same whatever the number of threads, and quickly, because every
run of every cell pays for it as set-up.
"""

import os
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

CHUNK_ROWS = 2048  # a chunk stays in cache between its passes
NOISE_STD = 2.0


def _threads():
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def make_dataset(seed, rows, session, data, data_dir):
    """Write ``x_train.npy`` (rows, dim) and ``y_train.npy`` (rows, classes),
    float32, the pair ``data.Dataset`` loads without pandas, and return them
    as the memory maps they were drawn into: a set of several gigabytes is
    written once, by the threads that draw it, and never copied. ``data``
    (the configuration's block) holds nothing this generator reads."""
    dim, classes = session["sizes"][0], session["sizes"][-1]
    data_dir = Path(data_dir)
    data_dir.mkdir(parents=True, exist_ok=True)
    n_chunks = -(-rows // CHUNK_ROWS)
    head, *children = np.random.SeedSequence(seed).spawn(1 + n_chunks)
    centers = (
        np.random.Generator(np.random.PCG64(head))
        .standard_normal((classes, dim))
        .astype(np.float32)
    )
    X = np.lib.format.open_memmap(
        data_dir / "x_train.npy", mode="w+", dtype=np.float32, shape=(rows, dim)
    )
    labels = np.empty(rows, np.int64)

    def draw(i):
        a, b = i * CHUNK_ROWS, min(rows, (i + 1) * CHUNK_ROWS)
        rng = np.random.Generator(np.random.PCG64(children[i]))
        labels[a:b] = rng.integers(0, classes, b - a)
        x = X[a:b]
        rng.standard_normal(out=x, dtype=np.float32)
        x *= NOISE_STD
        x += centers[labels[a:b]]
        return float(x.min()), float(x.max()), float(x.sum(dtype=np.float64))

    with ThreadPoolExecutor(_threads()) as pool:
        stats = list(pool.map(draw, range(n_chunks)))
        lo = min(s[0] for s in stats)
        hi = max(s[1] for s in stats)
        mean = sum(s[2] for s in stats) / (rows * dim)
        # ((x - lo) / (hi - lo)) - mean of that  ==  (x - mean) / (hi - lo)
        scale = np.float32(1.0 / (hi - lo))
        shift = np.float32(mean)

        def normalise(i):
            x = X[i * CHUNK_ROWS : (i + 1) * CHUNK_ROWS]
            x -= shift
            x *= scale

        list(pool.map(normalise, range(n_chunks)))
    Y = np.lib.format.open_memmap(
        data_dir / "y_train.npy", mode="w+", dtype=np.float32, shape=(rows, classes)
    )
    Y[np.arange(rows), labels] = 1.0
    return X, Y
