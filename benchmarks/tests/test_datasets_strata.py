"""``datasets/packed_tokens_strata.py``: the job ``packed_tokens`` draws, with
the document lengths and the frequent ids out of the seed's hands."""

from pathlib import Path

import numpy as np
import pytest

import cells

DATASETS = Path(__file__).resolve().parents[1] / "datasets"
ROWS, SEQ_LEN, VOCAB = 8, 2048, 24576


@pytest.fixture(scope="module")
def strata():
    return cells.load_module(DATASETS / "packed_tokens_strata.py")


@pytest.fixture(scope="module")
def sets(strata, tmp_path_factory):
    where = tmp_path_factory.mktemp("strata")
    make = lambda seed: strata.make_dataset(  # noqa: E731
        seed, ROWS, {"seq_len": SEQ_LEN}, {"vocab_size": VOCAB}, where / str(seed)
    )
    return where, {seed: make(seed) for seed in (5, 2147483659)}


def test_segments_are_equal_for_two_seeds_and_tokens_are_not(sets):
    _, drawn = sets
    (a_tok, a_seg), (b_tok, b_seg) = drawn[5], drawn[2147483659]
    assert np.array_equal(a_seg, b_seg)
    assert not np.array_equal(a_tok, b_tok)
    assert np.mean(a_tok == b_tok) < 0.2  # other tokens, not a few


def test_it_writes_what_it_returns(sets):
    where, drawn = sets
    for name, array in zip(("tokens", "segments"), drawn[5]):
        assert array.dtype == np.int32 and array.shape == (ROWS, SEQ_LEN + 1)
        assert np.array_equal(np.load(where / "5" / f"{name}_train.npy"), array)


def test_the_set_is_the_same_for_1_and_12_threads(strata, sets, tmp_path, monkeypatch):
    _, drawn = sets
    for threads in (1, 12):
        monkeypatch.setattr(strata.packed_tokens, "_threads", lambda: threads)
        again = strata.make_dataset(
            5, ROWS, {"seq_len": SEQ_LEN}, {"vocab_size": VOCAB}, tmp_path / str(threads)
        )
        assert np.array_equal(again[0], drawn[5][0]) and np.array_equal(again[1], drawn[5][1])


def test_lengths_are_the_strata_of_the_clipped_lognormal(strata):
    lengths = strata.document_lengths(ROWS * (SEQ_LEN + 1), SEQ_LEN)
    assert lengths.sum() >= ROWS * (SEQ_LEN + 1)
    assert strata.stratum_lengths(len(lengths) - 1, SEQ_LEN).sum() < ROWS * (SEQ_LEN + 1)
    assert lengths.min() >= 16 and lengths.max() <= SEQ_LEN
    assert np.array_equal(np.sort(lengths), strata.stratum_lengths(len(lengths), SEQ_LEN))
    assert not np.array_equal(lengths, np.sort(lengths))  # in the constant's order
    # the strata of a long set have the distribution's median
    many = strata.stratum_lengths(1001, 8192)
    assert many[500] == 1024 and many.max() == 8192 and many.min() >= 16


def test_ids_are_zipf_and_the_frequent_ids_are_the_same_for_every_seed(sets):
    _, drawn = sets
    tops = []
    for tokens, _ in drawn.values():
        assert tokens.min() >= 0 and tokens.max() < VOCAB
        counts = np.bincount(tokens.reshape(-1), minlength=VOCAB)
        ranked = np.sort(counts)[::-1]
        assert ranked[0] / ranked[9] == pytest.approx(10**1.1, rel=0.35)
        tops.append(np.argsort(counts)[-3:])
    assert np.array_equal(tops[0], tops[1]) and tops[0][-1] != 0


def test_segments_restart_per_row_and_never_fall(sets):
    _, drawn = sets
    segments = drawn[5][1]
    assert np.all(segments[:, 0] == 0)
    assert set(np.unique(np.diff(segments, axis=1))) == {0, 1}


def test_the_session_reads_it_back(sets):
    from shallowspeed_tpu.data import Dataset, packed_counts

    where, drawn = sets
    ds = Dataset(where / "5", ROWS, 1, tokens=True)
    ds.load(0, 1)
    assert np.array_equal(ds.input_X, np.array(drawn[5][0]))
    counts = packed_counts(np.array(drawn[5][1]))
    assert counts["tokens"] == ROWS * SEQ_LEN and counts["pairs"] > counts["tokens"]
