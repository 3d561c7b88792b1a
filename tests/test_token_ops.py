"""The token ops of ``ops.py`` (embedding, RMSNorm, SwiGLU, cross-entropy
over a vocabulary slice, blocked attention under the document mask, the short
convolution with resets, the chunked gated delta rule), forward and
gradients, against the expressions of the plain reference the benchmark
keeps, ``benchmarks/references/olmo_hybrid.py``, and ``jax.grad`` of them.
tests/test_token_model.py holds the whole model and the session."""

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from shallowspeed_tpu import ops

ROOT = Path(__file__).resolve().parents[1]
HIGHEST = lax.Precision.HIGHEST
SEQ, VOCAB = 48, 96


def _reference():
    path = ROOT / "benchmarks" / "references" / "olmo_hybrid.py"
    spec = importlib.util.spec_from_file_location("ref_olmo_hybrid_ops", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def _segments(rows, seed=0, rate=0.12, width=SEQ + 1):
    """Documents of random length, numbered from 0 in each row: starts fall
    inside chunks and blocks, and some documents span several."""
    starts = np.random.default_rng(seed).random((rows, width)) < rate
    starts[:, 0] = False
    return np.cumsum(starts, axis=1).astype(np.int32)


def _tokens(rows, seed=1, width=SEQ + 1):
    return np.random.default_rng(seed).integers(0, VOCAB, (rows, width)).astype(np.int32)


def _close(got, want, rtol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    scale = np.linalg.norm(want) + 1e-30
    assert np.linalg.norm(got - want) <= rtol * scale, (
        np.linalg.norm(got - want) / scale
    )


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# -- each op: forward and gradients against the reference's expression -------


def test_embedding_and_its_scatter_add():
    rng = np.random.default_rng(0)
    table, tokens, d = _rand(rng, 96, 8), _tokens(2)[:, :-1], _rand(rng, 2, SEQ, 8)
    x, back = ops.embed(table, tokens)
    _close(x, table[tokens])
    want = jax.grad(lambda t: jnp.sum(t[tokens] * d))(table)
    _close(back(d), want)


def test_rms_norm():
    rng = np.random.default_rng(1)
    x, w, d = _rand(rng, 2, SEQ, 32), _rand(rng, 32), _rand(rng, 2, SEQ, 32)
    y, back = ops.rms_norm(x, w, 1e-6)
    _close(y, ref.rms_norm(x, w, 1e-6))
    want = jax.grad(lambda x, w: jnp.sum(ref.rms_norm(x, w, 1e-6) * d), (0, 1))(x, w)
    for got, expected in zip(back(d), want):
        _close(got, expected)


def test_swiglu():
    rng = np.random.default_rng(2)
    g, u, d = _rand(rng, SEQ, 48), _rand(rng, SEQ, 48), _rand(rng, SEQ, 48)
    y, back = ops.swiglu(g, u)
    _close(y, ref.silu(g) * u)
    want = jax.grad(lambda g, u: jnp.sum(ref.silu(g) * u * d), (0, 1))(g, u)
    for got, expected in zip(back(d), want):
        _close(got, expected)


def test_cross_entropy_over_a_vocabulary_slice():
    rng = np.random.default_rng(3)
    logits, targets = _rand(rng, 2, SEQ, 96) * 3, jnp.asarray(_tokens(2)[:, 1:])

    def want(z):
        picked = jnp.take_along_axis(z, targets[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(z, -1) - picked) / 200.0

    loss, back = ops.cross_entropy(logits, targets, 200.0)
    _close(loss, want(logits))
    _close(back(jnp.ones(()))[0], jax.grad(want)(logits))


def test_conv4_reads_zero_across_a_document_start():
    rng = np.random.default_rng(4)
    x, taps, d = _rand(rng, 2, SEQ, 24), _rand(rng, 24, 4), _rand(rng, 2, SEQ, 24)
    seg = jnp.asarray(_segments(2)[:, :-1])

    def want(x, taps):
        return jnp.stack([ref.silu(ref.conv4(x[r], taps, seg[r])) for r in range(2)])

    y, back = ops.conv_silu(x, taps, seg)
    _close(y, want(x, taps))
    for got, expected in zip(back(d), jax.grad(lambda x, t: jnp.sum(want(x, t) * d), (0, 1))(x, taps)):
        _close(got, expected)
    # the first token of a document sees its own tap only
    first = np.flatnonzero(np.diff(np.asarray(seg[0])) != 0)[0] + 1
    _close(y[0, first], ref.silu(x[0, first] * taps[:, 0]))


@pytest.mark.parametrize("block", [8, 16, 48])
def test_blocked_attention_under_the_document_mask(block):
    rng = np.random.default_rng(5)
    q, k, v, d = (_rand(rng, 2, 4, SEQ, 8) for _ in range(4))
    seg = jnp.asarray(_segments(2, seed=block)[:, :-1])
    at = jnp.arange(SEQ)

    def want(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) * 8**-0.5
        mask = (seg[:, :, None] == seg[:, None, :]) & (at[:, None] >= at[None, :])
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HIGHEST)

    o, back = ops.attention(q, k, v, seg, HIGHEST, block)
    _close(o, want(q, k, v))
    for got, expected in zip(back(d), jax.grad(lambda *a: jnp.sum(want(*a) * d), (0, 1, 2))(q, k, v)):
        _close(got, expected)


def test_attention_skips_the_key_blocks_of_earlier_documents():
    seg = np.repeat(np.arange(6), 8)[None].astype(np.int32)  # six documents of 8
    first = ops._first_key_block(jnp.asarray(seg.reshape(1, 6, 8)))
    assert list(np.asarray(first)) == [0, 1, 2, 3, 4, 5]
    one = np.zeros((1, 48), np.int32)  # one document: every block from 0
    assert list(np.asarray(ops._first_key_block(jnp.asarray(one.reshape(1, 6, 8))))) == [0] * 6


@pytest.mark.parametrize("neg_eigval", [True, False])
@pytest.mark.parametrize("chunk,block", [(8, 2), (16, 1), (48, 1), (4, 3)])
def test_chunked_scan_is_the_token_by_token_rule(chunk, block, neg_eigval):
    """Documents start inside chunks and inside blocks of chunks."""
    rng = np.random.default_rng(6)
    q, k = _rand(rng, 2, SEQ, 3, 6), _rand(rng, 2, SEQ, 3, 6)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v, d = _rand(rng, 2, SEQ, 3, 12), _rand(rng, 2, SEQ, 3, 12)
    beta = jax.nn.sigmoid(_rand(rng, 2, SEQ, 3)) * (2.0 if neg_eigval else 1.0)
    log_decay = -jax.nn.softplus(_rand(rng, 2, SEQ, 3))
    seg = jnp.asarray(_segments(2, seed=chunk)[:, :-1])
    first = jnp.concatenate([jnp.ones((2, 1), bool), seg[:, 1:] != seg[:, :-1]], 1)

    def want(q, k, v, beta, log_decay):
        return jnp.stack([
            ref.delta_rule(q[r], k[r], v[r], beta[r], jnp.exp(log_decay[r]), first[r])
            for r in range(2)
        ])

    o, back = ops.gated_delta_scan(q, k, v, beta, log_decay, seg, HIGHEST, chunk, block)
    _close(o, want(q, k, v, beta, log_decay))
    grads = jax.grad(lambda *a: jnp.sum(want(*a) * d), (0, 1, 2, 3, 4))(q, k, v, beta, log_decay)
    for got, expected in zip(back(d), grads):
        # a document's first token takes no decay in either form
        _close(got, expected)


# -- the scan's kernel form (pallas_ops.gdn_scan_fwd / _bwd), interpreted ----

KERNEL_CASES = {
    # name: (rows, seq, neg_eigval, document starts by row)
    "two-rows-two-chunks-neg": (
        2, 256, True,
        # inside a chunk; at a chunk's last token, at the next chunk's first
        # (one document of one token across the boundary); then none at all
        [[40, 127, 128, 200], []],
    ),
    "one-row-one-chunk": (1, 128, False, [[1, 77, 127]]),
    # the state crosses two boundaries and is cut at a third; a start at a
    # chunk's first token and one at the row's last
    "one-row-three-chunks": (1, 384, False, [[256, 300, 383]]),
}
KERNEL_OUTPUTS = ("o", "dq", "dk", "dv", "dbeta", "dlog_decay")


@functools.lru_cache(maxsize=None)
def _kernel_run(case):
    """``{form: {output: array}}`` for the kernels, the XLA form and the
    token-by-token rule on one case's inputs. One run per case."""
    rows, seq, neg_eigval, starts = KERNEL_CASES[case]
    heads, dk, dv = 2, 8, 16
    rng = np.random.default_rng(7)
    q, k = _rand(rng, rows, seq, heads, dk), _rand(rng, rows, seq, heads, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v, d = _rand(rng, rows, seq, heads, dv), _rand(rng, rows, seq, heads, dv)
    beta = jax.nn.sigmoid(_rand(rng, rows, seq, heads)) * (2.0 if neg_eigval else 1.0)
    log_decay = -jax.nn.softplus(_rand(rng, rows, seq, heads))
    first = np.zeros((rows, seq), bool)
    for r, at in enumerate(starts):
        first[r, at] = True
    seg = jnp.asarray(np.cumsum(first, axis=1).astype(np.int32))
    first[:, 0] = True  # a row's first token starts a document
    assert ops.scan_path(seq, ops.SCAN_CHUNK, dk, dv, q.dtype) == "pallas"

    def rule(q, k, v, beta, log_decay):
        return jnp.stack([
            ref.delta_rule(q[r], k[r], v[r], beta[r], jnp.exp(log_decay[r]), jnp.asarray(first[r]))
            for r in range(rows)
        ])

    args = (q, k, v, beta, log_decay)
    runs = {}
    for form, fn in (
        ("kernels", lambda *a: ops.gated_delta_scan(*a, seg)),
        ("xla", lambda *a: ops._gated_delta_scan_xla(
            *a, seg, ops.SCAN_PRECISION, ops.SCAN_CHUNK, ops.SCAN_BLOCK)),
    ):
        o, back = fn(*args)
        runs[form] = dict(zip(KERNEL_OUTPUTS, (o, *back(d))))
    grads = jax.grad(lambda *a: jnp.sum(rule(*a) * d), (0, 1, 2, 3, 4))(*args)
    runs["rule"] = dict(zip(KERNEL_OUTPUTS, (rule(*args), *grads)))
    return runs


@pytest.mark.parametrize("output", KERNEL_OUTPUTS)
@pytest.mark.parametrize("oracle", ["rule", "xla"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_scan_kernels_are_the_rule_and_the_xla_form(case, oracle, output):
    """Documents start inside a chunk, at a chunk's last and first token and
    at a row's first; a row without a start carries its state across the
    chunk boundary."""
    runs = _kernel_run(case)
    _close(runs["kernels"][output], runs[oracle][output])


@pytest.mark.parametrize(
    "seq,chunk,dk,dv,dtype,want",
    [
        (8192, 128, 96, 192, jnp.float32, "pallas"),  # the cell
        (256, 128, 8, 16, jnp.float32, "pallas"),  # the kernels' tests
        (128, 128, 256, 256, jnp.float32, "pallas"),
        (64, 128, 6, 12, jnp.float32, "xla"),  # the rehearsal: one chunk of 64
        (48, 8, 6, 12, jnp.float32, "xla"),  # this file's chunkings
        (48, 16, 6, 12, jnp.float32, "xla"),
        (8192, 256, 96, 192, jnp.float32, "xla"),  # a chunk of 256
        (8192, 64, 96, 192, jnp.float32, "xla"),
        (8192, 128, 100, 192, jnp.float32, "xla"),  # d_k off the sublane tile
        (8192, 128, 96, 264, jnp.float32, "xla"),  # d_v beyond what was measured
        (8192, 128, 96, 192, jnp.bfloat16, "xla"),
        (8200, 128, 96, 192, jnp.float32, "xla"),  # 8200 = 82 chunks of 100
    ],
)
def test_the_kernels_engage_by_shape_alone(seq, chunk, dk, dv, dtype, want):
    assert ops.scan_path(seq, chunk, dk, dv, dtype) == want


@pytest.mark.parametrize(
    "precision,want",
    [(lax.Precision.HIGHEST, "pallas"), (lax.Precision.DEFAULT, "pallas"),
     (lax.Precision.HIGH, "xla")],  # Mosaic lowers no three-pass product
)
def test_the_kernels_take_the_precisions_mosaic_lowers(precision, want):
    assert ops.scan_path(8192, 128, 96, 192, jnp.float32, precision) == want


def test_nothing_but_the_shapes_selects_the_kernels(monkeypatch):
    """No environment variable and no flag: the MLP kernels' switch leaves
    the scan's rule alone."""
    monkeypatch.setenv("SHALLOWSPEED_PALLAS", "0")
    monkeypatch.setattr(ops, "_PALLAS", False)
    assert ops.scan_path(8192, 128, 96, 192, jnp.float32) == "pallas"
    monkeypatch.setattr(ops, "_PALLAS", True)
    assert ops.scan_path(48, 8, 6, 12, jnp.float32) == "xla"
