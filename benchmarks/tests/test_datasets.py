"""The generators under ``datasets/``: each training set depends on the seed
alone, ``gaussian_clusters`` draws the bytes ``datagen.make_dataset`` drew
until PR 26, and ``packed_tokens`` is the job its docstring states."""

import hashlib
from pathlib import Path

import numpy as np
import pytest

import cells

DATASETS = Path(__file__).resolve().parents[1] / "datasets"


@pytest.fixture(scope="module")
def clusters():
    return cells.load_module(DATASETS / "gaussian_clusters.py")


@pytest.fixture(scope="module")
def packed():
    return cells.load_module(DATASETS / "packed_tokens.py")


def sha(array):
    return hashlib.sha256(np.ascontiguousarray(array).tobytes()).hexdigest()


# sha256 of the parent's ``datagen.make_dataset(seed, rows, dim, classes, dir)``
# (commit de813f1, the tree before the generator moved), X then Y
PARENT = {
    (5, 5000, 784, 10): (
        "f15893af17d57f22020d69581e20880e70b6c11984dde954275fa27dcf5cb93a",
        "52bfe8d85e84888a6e7ff8360560cb893b7f777ca4edf1212466e5457fa9b5dd",
    ),
    (2147483659, 4099, 20, 7): (
        "0370147ab77607896f6b7f03b3b22218514b620f7240192c366e02cd6910feb3",
        "3d5965dfbfab31073d755b6bb00add8cbefc1f3ef67aaee64f48a977794f87f4",
    ),
}


@pytest.mark.parametrize("case", PARENT, ids=lambda case: f"seed{case[0]}")
def test_gaussian_clusters_draws_the_parents_bytes(clusters, tmp_path, case):
    seed, rows, dim, classes = case
    session = {"sizes": [dim, 33, classes], "lr": 0.006}
    X, Y = clusters.make_dataset(seed, rows, session, {"generator": "x"}, tmp_path)
    assert X.dtype == Y.dtype == np.float32
    assert X.shape == (rows, dim) and Y.shape == (rows, classes)
    assert (sha(X), sha(Y)) == PARENT[case]


def test_gaussian_clusters_depends_on_the_seed_alone(clusters, tmp_path, monkeypatch):
    session = {"sizes": [784, 10]}
    a, ya = clusters.make_dataset(5, 5000, session, {}, tmp_path / "a")
    monkeypatch.setattr(clusters, "_threads", lambda: 1)
    b, yb = clusters.make_dataset(5, 5000, session, {}, tmp_path / "b")
    c, _ = clusters.make_dataset(6, 5000, session, {}, tmp_path / "c")
    assert np.array_equal(a, b) and np.array_equal(ya, yb)
    assert not np.array_equal(a, c)
    # prepare_data's shape: mean-centred, a range of exactly one, one-hot rows
    assert abs(float(np.mean(a, dtype=np.float64))) < 1e-6
    assert float(a.max() - a.min()) == pytest.approx(1.0, abs=1e-6)
    assert np.array_equal(ya.sum(axis=1), np.ones(5000, np.float32))
    # and it is what data.Dataset reads back
    from shallowspeed_tpu.data import Dataset

    ds = Dataset(tmp_path / "a", 1000, 250)
    ds.load(0, 1)
    assert np.array_equal(ds.input_X, np.array(a))


ROWS, SEQ_LEN, VOCAB = 100, 2048, 5000


@pytest.fixture(scope="module")
def token_set(packed, tmp_path_factory):
    where = tmp_path_factory.mktemp("tokens")
    tokens, segments = packed.make_dataset(
        2147483659, ROWS, {"seq_len": SEQ_LEN}, {"vocab_size": VOCAB}, where
    )
    return where, tokens, segments


def test_packed_tokens_writes_what_it_returns(token_set):
    where, tokens, segments = token_set
    for name, array in (("tokens", tokens), ("segments", segments)):
        assert array.dtype == np.int32 and array.shape == (ROWS, SEQ_LEN + 1)
        assert np.array_equal(np.load(where / f"{name}_train.npy"), array)


def test_packed_tokens_is_the_same_set_for_1_and_12_threads(
    packed, token_set, tmp_path, monkeypatch
):
    _, tokens, segments = token_set
    for threads in (1, 12):
        monkeypatch.setattr(packed, "_threads", lambda: threads)
        again = packed.make_dataset(
            2147483659, ROWS, {"seq_len": SEQ_LEN}, {"vocab_size": VOCAB},
            tmp_path / str(threads),
        )
        assert np.array_equal(again[0], tokens) and np.array_equal(again[1], segments)
    other = packed.make_dataset(
        7, ROWS, {"seq_len": SEQ_LEN}, {"vocab_size": VOCAB}, tmp_path / "other"
    )
    assert not np.array_equal(other[0], tokens)


def test_packed_tokens_ids_are_zipf_inside_the_vocabulary(token_set):
    _, tokens, _ = token_set
    assert tokens.min() >= 0 and tokens.max() < VOCAB
    counts = np.sort(np.bincount(tokens.reshape(-1), minlength=VOCAB))[::-1]
    # rank 1 against rank 10 of a Zipf law of exponent 1.1: 10 ** 1.1 = 12.6
    assert counts[0] / counts[9] == pytest.approx(10**1.1, rel=0.15)
    # under the seeded permutation the frequent ids are not the small ones
    assert np.argmax(np.bincount(tokens.reshape(-1))) != 0


def test_packed_tokens_segments_restart_per_row_and_never_fall(token_set):
    _, tokens, segments = token_set
    assert np.all(segments[:, 0] == 0)
    steps = np.diff(segments, axis=1)
    assert set(np.unique(steps)) == {0, 1}
    # no padding: every position of every row holds a token of a document,
    # and a row of 2,049 ids holds a few documents, not one and not hundreds
    per_row = segments[:, -1] + 1
    assert 1 <= per_row.min() and 1.5 < per_row.mean() < 6


def test_packed_tokens_length_histogram(packed):
    rng = np.random.Generator(np.random.PCG64(3))
    lengths = packed.document_lengths(rng, 40_000_000, 8192)
    assert lengths.sum() >= 40_000_000
    assert lengths.min() >= 16 and lengths.max() == 8192
    assert np.median(lengths) == pytest.approx(1024, rel=0.10)
    # rows shorter than the median document: every length clipped to the row
    short = packed.document_lengths(rng, 100_000, 64)
    assert short.min() >= 16 and short.max() == 64
