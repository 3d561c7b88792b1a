"""The second token family's ops (``ops.kda_scan``, grouped-query
``ops.attention``, ``ops.route`` and ``ops.experts``) against what they state
they compute, written plainly: the per-channel delta rule token by token,
attention with the key/value heads repeated, the routed mixture as a dense
loop over experts. Tiny sizes, a CPU, float32 passes."""

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


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(ROOT / "benchmarks" / "references" / "solar_open2.py", "ref_solar_open2")


def _close(got, want, rtol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    scale = np.linalg.norm(want) + 1e-30
    assert np.linalg.norm(got - want) <= rtol * scale, np.linalg.norm(got - want) / scale


def _segments(rows, seq, seed=0, rate=0.1):
    starts = np.random.default_rng(seed).random((rows, seq)) < rate
    starts[:, 0] = False
    return np.cumsum(starts, axis=1).astype(np.int32)


# -- the per-channel delta rule -----------------------------------------------

ROWS, SEQ, H, DK, DV = 2, 48, 3, 8, 12


@pytest.fixture(scope="module")
def rule():
    rng = np.random.default_rng(0)
    f32 = lambda a: a.astype(np.float32)  # noqa: E731
    k = rng.standard_normal((ROWS, SEQ, H, DK))
    return dict(
        q=f32(rng.standard_normal((ROWS, SEQ, H, DK))),
        k=f32(k / np.linalg.norm(k, axis=-1, keepdims=True)),
        v=f32(rng.standard_normal((ROWS, SEQ, H, DV))),
        # beta up to 2: eigenvalues of I - beta k k^T down to -1
        beta=f32(2 * rng.random((ROWS, SEQ, H))),
        # strong decays too: exp(-3 x 48) underflows a product of exponentials
        g=f32(-3 * rng.random((ROWS, SEQ, H, DK))),
        seg=_segments(ROWS, SEQ),
        do=f32(rng.standard_normal((ROWS, SEQ, H, DV))),
    )


def _recurrence(q, k, v, beta, g, seg):
    def row(q, k, v, beta, g, seg):
        first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
        return ref.channel_delta_rule(q, k, v, beta, g, first)

    return jax.vmap(row)(q, k, v, beta, g, seg)


@pytest.mark.parametrize("chunk,sub", [(8, 4), (16, 4), (8, 8), (48, 16), (12, 3)])
def test_kda_scan_is_the_recurrence(rule, chunk, sub):
    r = rule
    assert r["beta"].max() > 1.5 and r["seg"].max() >= 2
    want = _recurrence(r["q"], r["k"], r["v"], r["beta"], r["g"], r["seg"])
    got, _ = ops.kda_scan(
        r["q"], r["k"], r["v"], r["beta"], r["g"], r["seg"], chunk=chunk, block=2, sub=sub
    )
    _close(got, want, rtol=1e-5)


@pytest.mark.parametrize("chunk,sub,block", [(8, 4, 2), (16, 8, 1), (24, 8, 2)])
def test_kda_scan_gradients_are_the_recurrences(rule, chunk, sub, block):
    r = rule
    want = jax.grad(
        lambda *a: jnp.sum(_recurrence(*a, r["seg"]) * r["do"]), argnums=(0, 1, 2, 3, 4)
    )(r["q"], r["k"], r["v"], r["beta"], r["g"])
    _, back = ops.kda_scan(
        r["q"], r["k"], r["v"], r["beta"], r["g"], r["seg"], chunk=chunk, block=block, sub=sub
    )
    for got, w in zip(back(r["do"]), want):
        _close(got, w, rtol=2e-5)


def test_a_decay_constant_over_the_channels_is_the_gated_delta_rule(rule):
    """``ops.gated_delta_scan`` is the special case: forward and gradients."""
    r = rule
    g_head = r["g"][..., 0]
    g_all = np.broadcast_to(g_head[..., None], r["g"].shape)
    got, got_back = ops.kda_scan(
        r["q"], r["k"], r["v"], r["beta"], g_all, r["seg"], chunk=8, block=2, sub=4
    )
    want, want_back = ops.gated_delta_scan(
        r["q"], r["k"], r["v"], r["beta"], g_head, r["seg"], chunk=8, block=2
    )
    _close(got, want, rtol=1e-5)
    dq, dk, dv, dbeta, dg = got_back(r["do"])
    for a, b in zip((dq, dk, dv, dbeta, dg.sum(-1)), want_back(r["do"])):
        _close(a, b, rtol=2e-5)


def test_kda_scan_state_stops_at_a_document_boundary(rule):
    """Tokens before a boundary do not reach the outputs after it."""
    r = rule
    seg = np.zeros((ROWS, SEQ), np.int32)
    seg[:, 20:] = 1
    base, _ = ops.kda_scan(r["q"], r["k"], r["v"], r["beta"], r["g"], seg, chunk=8, sub=4)
    v2 = r["v"].copy()
    v2[:, :20] += 1.0
    moved, _ = ops.kda_scan(r["q"], r["k"], v2, r["beta"], r["g"], seg, chunk=8, sub=4)
    assert np.array_equal(np.asarray(base[:, 20:]), np.asarray(moved[:, 20:]))
    assert not np.allclose(np.asarray(base[:, :20]), np.asarray(moved[:, :20]))


def test_decayed_pairs_do_not_overflow_where_the_decay_is_strong():
    """exp(g_i) exp(-g_j) overflows float32 past 88; the sub-blocks' reference
    points keep every exponent at or below zero."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((1, 32, 4)).astype(np.float32)
    g = np.cumsum(-20 * rng.random((1, 32, 4)), axis=-2).astype(np.float32)
    assert g.min() < -200
    got = ops._decayed_pairs(x, x, g, 8, HIGHEST)
    assert np.all(np.isfinite(np.asarray(got)))
    want = np.einsum(
        "id,jd,ijd->ij", x[0].astype(np.float64), x[0].astype(np.float64),
        np.exp(np.minimum(g[0][:, None, :].astype(np.float64) - g[0][None, :, :], 0.0)),
    )
    lower = np.tril(np.ones((32, 32), bool))
    _close(np.where(lower, np.asarray(got[0]), 0.0), np.where(lower, want, 0.0), rtol=1e-5)


# -- the per-channel rule's kernels (interpreted on a CPU) ---------------------

KERNEL_OUTPUTS = ("o", "dq", "dk", "dv", "dbeta", "dlog_decay")
# name: (rows, seq, chunk, heads, d_k, d_v, decay scale, a document's starts a row)
KERNEL_CASES = {
    # a document starts inside a chunk, and a row without a start carries its
    # state across the chunk boundaries
    "inside": (2, 64, 16, 2, 8, 16, 3.0, ((21,), ())),
    # at a chunk's first token and at a chunk's last
    "edges": (2, 64, 16, 2, 8, 16, 3.0, ((32,), (15, 47))),
    "one_chunk": (1, 32, 32, 2, 8, 16, 3.0, ((11,),)),  # the row is one chunk
    "wide_keys": (1, 32, 8, 3, 16, 8, 3.0, ((9, 24),)),  # d_k > d_v, heads side by side 1
    "four_heads": (1, 64, 64, 4, 8, 24, 1.0, ((40,),)),  # the cell's chunk, 4 heads side by side
    # test_decayed_pairs_do_not_overflow_where_the_decay_is_strong's decays:
    # exp(g_i) exp(-g_j) overflows float32 inside one chunk
    "strong": (1, 64, 32, 2, 4, 8, 20.0, ((21,),)),
}


@functools.lru_cache(maxsize=None)
def _kernel_run(case):
    """``{form: {output: array}}`` for the kernels, the XLA form and the
    token-by-token rule on one case's inputs. One run per case."""
    rows, seq, chunk, heads, dk, dv, decay, starts = KERNEL_CASES[case]
    rng = np.random.default_rng(7)
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    q, k, v, do = (f32(rows, seq, heads, d) for d in (dk, dk, dv, dv))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    beta = (2 * rng.random((rows, seq, heads))).astype(np.float32)
    log_decay = (-decay * rng.random((rows, seq, heads, dk))).astype(np.float32)
    first = np.zeros((rows, seq), bool)
    for r, at in enumerate(starts):
        first[r, list(at)] = True
    seg = jnp.asarray(np.cumsum(first, axis=1).astype(np.int32))
    if decay > 10:
        assert np.cumsum(log_decay, axis=1)[:, chunk - 1].min() < -200
    args = (q, k, v, beta, log_decay)
    runs = {}
    for form, fn in (
        ("kernels", lambda *a: ops._kda_scan_pallas(*a, seg, HIGHEST, chunk=chunk)),
        ("xla", lambda *a: ops._kda_scan_xla(*a, seg, HIGHEST, chunk, 2, 4)),
    ):
        o, back = fn(*args)
        runs[form] = dict(zip(KERNEL_OUTPUTS, (o, *back(do))))
    grads = jax.grad(lambda *a: jnp.sum(_recurrence(*a, seg) * do), (0, 1, 2, 3, 4))(*args)
    runs["rule"] = dict(zip(KERNEL_OUTPUTS, (_recurrence(*args, seg), *grads)))
    return runs


@pytest.mark.parametrize("output", KERNEL_OUTPUTS)
@pytest.mark.parametrize("oracle", ["rule", "xla"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_kda_kernels_are_the_rule_and_the_xla_form(case, oracle, output):
    runs = _kernel_run(case)
    got = np.asarray(runs["kernels"][output])
    assert np.all(np.isfinite(got))
    _close(got, runs[oracle][output], rtol=2e-5)


@functools.lru_cache(maxsize=None)
def _constant_decay_run():
    """The per-channel kernels fed a decay constant over the channels, and
    the scalar rule's kernels on the same inputs (chunks of 128 both)."""
    rows, seq, heads, dk, dv = 1, 256, 2, 8, 16
    rng = np.random.default_rng(9)
    f32 = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    q, k, v, do = (f32(rows, seq, heads, d) for d in (dk, dk, dv, dv))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    beta = (2 * rng.random((rows, seq, heads))).astype(np.float32)
    g_head = (-rng.random((rows, seq, heads))).astype(np.float32)
    seg = jnp.asarray(np.cumsum(np.arange(seq) == 150)[None].astype(np.int32))
    g_all = np.broadcast_to(g_head[..., None], (rows, seq, heads, dk))
    got, got_back = ops._kda_scan_pallas(q, k, v, beta, g_all, seg, HIGHEST, chunk=128)
    want, want_back = ops._gated_delta_scan_pallas(q, k, v, beta, g_head, seg, HIGHEST)
    dq, dk_, dv_, dbeta, dg = got_back(do)
    return (
        dict(zip(KERNEL_OUTPUTS, (got, dq, dk_, dv_, dbeta, dg.sum(-1)))),
        dict(zip(KERNEL_OUTPUTS, (want, *want_back(do)))),
    )


@pytest.mark.parametrize("output", KERNEL_OUTPUTS)
def test_a_constant_decay_through_the_kernels_is_the_scalar_rules_kernels(output):
    got, want = _constant_decay_run()
    _close(got[output], want[output], rtol=2e-5)


def test_the_kernels_pair_matrices_do_not_overflow_where_the_decay_is_strong():
    """``test_decayed_pairs_do_not_overflow_where_the_decay_is_strong`` at the
    same decays, against what the kernels build of a chunk: every level's
    exponents are at most zero."""
    from shallowspeed_tpu import pallas_ops as K

    rng = np.random.default_rng(1)
    c, d = 32, 4
    x = rng.standard_normal((c, d)).astype(np.float32)
    log_decay = (-20 * rng.random((c, d))).astype(np.float32)
    log_decay[0] = 0.0  # the chunk's first token starts the document
    g = np.cumsum(log_decay, axis=0)
    assert g.min() < -200
    p = np.zeros((K.KDA_ROWS, c), np.float32)
    p[K.KDA_FIRST, 0] = 1.0
    dot = K._gdn_dot(HIGHEST)
    built = K._kda_chunk(x, x, log_decay, jnp.asarray(p), jnp.ones((c, 1)), dot, dot)
    for e, _ in built["levels"]:
        assert float(e.max()) <= 1.0 and np.all(np.isfinite(np.asarray(e)))
    assert np.all(np.isfinite(np.asarray(built["kk"])))
    want = np.einsum(
        "id,jd,ijd->ij", x.astype(np.float64), x.astype(np.float64),
        np.exp(np.minimum(g[:, None, :].astype(np.float64) - g[None, :, :], 0.0)),
    )
    _close(built["kk"], np.tril(want, -1), rtol=1e-5)
    _close(built["m"], np.tril(want), rtol=1e-5)


@pytest.mark.parametrize(
    "seq,heads,dk,dv,dtype,want",
    [
        (2048, 64, 128, 128, jnp.float32, "pallas"),  # the cell
        (64, 8, 128, 128, jnp.float32, "pallas"),  # one chunk; tests/test_solar_model.py's
        (4096, 16, 128, 256, jnp.float32, "pallas"),
        (64, 4, 8, 8, jnp.float32, "xla"),  # the rehearsal: heads of 8 channels
        (48, 3, 8, 12, jnp.float32, "xla"),  # this file's
        (2048, 64, 96, 128, jnp.float32, "xla"),  # d_k off the lanes
        (2048, 64, 256, 256, jnp.float32, "xla"),  # two tiles of key channels
        (2048, 64, 128, 384, jnp.float32, "xla"),  # d_v beyond what was compiled
        (2048, 60, 128, 128, jnp.float32, "xla"),  # the heads do not come in eights
        (2080, 64, 128, 128, jnp.float32, "xla"),  # 2080 = 32.5 chunks of 64
        (2048, 64, 128, 128, jnp.bfloat16, "xla"),
    ],
)
def test_the_kda_kernels_engage_by_shape_alone(seq, heads, dk, dv, dtype, want):
    assert ops.kda_scan_path(seq, heads, dk, dv, dtype) == want


@pytest.mark.parametrize(
    "precision,want",
    [(lax.Precision.HIGHEST, "pallas"), (lax.Precision.DEFAULT, "pallas"),
     (lax.Precision.HIGH, "xla")],  # Mosaic lowers no three-pass product
)
def test_the_kda_kernels_take_the_precisions_mosaic_lowers(precision, want):
    assert ops.kda_scan_path(2048, 64, 128, 128, jnp.float32, precision) == want


def test_nothing_but_the_shapes_selects_the_kda_kernels(monkeypatch):
    """No environment variable and no flag: the MLP kernels' switch leaves
    the rule alone, and ``kda_scan`` asks the rule and nothing else."""
    monkeypatch.setenv("SHALLOWSPEED_PALLAS", "0")
    monkeypatch.setattr(ops, "_PALLAS", False)
    assert ops.kda_scan_path(2048, 64, 128, 128, jnp.float32) == "pallas"
    monkeypatch.setattr(ops, "_PALLAS", True)
    assert ops.kda_scan_path(48, 4, 8, 8, jnp.float32) == "xla"
    asked = []
    monkeypatch.setattr(ops, "kda_scan_path", lambda *a: asked.append(a) or "xla")
    r = np.zeros((1, 64, 8, 128), np.float32)
    ops.kda_scan(r, r, r, r[..., 0], r, np.zeros((1, 64), np.int32))
    assert asked == [(64, 8, 128, 128, np.dtype("float32"), ops.SCAN_PRECISION)]


# -- grouped-query attention --------------------------------------------------


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 1), (6, 3), (4, 4)])
def test_grouped_attention_is_attention_with_the_kv_heads_repeated(heads, kv_heads):
    rng = np.random.default_rng(3)
    rows, seq, d = 2, 48, 8
    q = rng.standard_normal((rows, heads, seq, d)).astype(np.float32)
    k = rng.standard_normal((rows, kv_heads, seq, d)).astype(np.float32)
    v = rng.standard_normal((rows, kv_heads, seq, d)).astype(np.float32)
    do = rng.standard_normal((rows, heads, seq, d)).astype(np.float32)
    seg = _segments(rows, seq, seed=4)
    group = heads // kv_heads
    got, got_back = ops.attention(q, k, v, seg, HIGHEST, block=16)
    want, want_back = ops.attention(
        q, np.repeat(k, group, axis=1), np.repeat(v, group, axis=1), seg, HIGHEST, block=16
    )
    _close(got, want, rtol=1e-6)
    dq, dk, dv = got_back(do)
    wq, wk, wv = want_back(do)
    _close(dq, wq, rtol=1e-5)
    # a repeated head's gradient is the sum over the query heads that read it
    fold = lambda a: np.asarray(a).reshape(rows, kv_heads, group, seq, d).sum(2)  # noqa: E731
    _close(dk, fold(wk), rtol=1e-5)
    _close(dv, fold(wv), rtol=1e-5)


def test_attention_refuses_key_value_heads_that_do_not_divide():
    a = np.zeros((1, 4, 16, 8), np.float32)
    with pytest.raises(ValueError, match="do not divide"):
        ops.attention(a, a[:, :3], a[:, :3], np.zeros((1, 16), np.int32))


# -- the routed experts -------------------------------------------------------

TOKENS, D, FF, E, TOP = 96, 16, 12, 16, 4
M_MOE = dict(
    num_experts_per_tok=TOP, norm_topk_prob=True, routed_scaling_factor=1.0,
)


@pytest.fixture(scope="module")
def mixture():
    rng = np.random.default_rng(5)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return dict(
        x=f32(TOKENS, D), W_r=f32(E, D), W1=0.3 * f32(E, FF, D), W3=0.3 * f32(E, FF, D),
        W2=0.3 * f32(E, D, FF), Ws1=0.3 * f32(FF, D), Ws3=0.3 * f32(FF, D),
        Ws2=0.3 * f32(D, FF), dout=f32(TOKENS, D),
    )


def _held(p, lo, hi):
    return {**p, "W1": p["W1"][lo:hi], "W3": p["W3"][lo:hi], "W2": p["W2"][lo:hi]}


def _reference_routed(p, x, lo, hi, policy):
    """The reference's layer less its shared expert: the routed part alone."""
    m = dict(M_MOE, routed_experts_held=[lo, hi])
    shared = {k: jnp.zeros_like(p[k]) for k in ("Ws1", "Ws3", "Ws2")}
    return ref.moe({**_held(p, lo, hi), **shared}, x, m, ref._matmul(policy))


def _system_routed(p, x, lo, hi, precision, tile=8):
    (weights, sel), route_back = ops.route(x, p["W_r"], TOP)
    out, back, rows = ops.experts(
        x, sel, weights, (lo, hi), p["W1"][lo:hi], p["W3"][lo:hi], p["W2"][lo:hi],
        precision, tile,
    )
    return out, back, route_back, rows, sel


@pytest.mark.parametrize("held", [(0, 4), (5, 13), (0, 16), (15, 16)])
def test_routed_experts_are_the_references_dense_loop(mixture, held):
    p, (lo, hi) = mixture, held
    out, back, route_back, rows, sel = _system_routed(p, p["x"], lo, hi, HIGHEST)
    _close(out, _reference_routed(p, p["x"], lo, hi, "highest"), rtol=1e-5)
    counted = np.bincount(np.asarray(sel).reshape(-1), minlength=E)[lo:hi]
    assert np.array_equal(np.asarray(rows), counted)
    want = jax.grad(
        lambda x, w_r, w1, w3, w2: jnp.sum(
            ref.moe(
                dict(W_r=w_r, W1=w1, W3=w3, W2=w2,
                     **{k: jnp.zeros_like(p[k]) for k in ("Ws1", "Ws3", "Ws2")}),
                x, dict(M_MOE, routed_experts_held=[lo, hi]), ref._matmul("highest"),
            ) * p["dout"]
        ),
        argnums=(0, 1, 2, 3, 4),
    )(p["x"], p["W_r"], p["W1"][lo:hi], p["W3"][lo:hi], p["W2"][lo:hi])
    dx_e, dweights, dw1, dw3, dw2 = back(p["dout"])
    dx_r, dw_r = route_back(dweights)
    for got, w in zip((dx_e + dx_r, dw_r, dw1, dw3, dw2), want):
        _close(got, w, rtol=2e-5)


def test_routed_experts_round_as_the_reference_states(mixture):
    """Under ``Precision.DEFAULT`` the operands are rounded to bfloat16 outright,
    on a CPU as on the chip: identical selections (the router is float32), so
    the system and the reference's ``default`` policy agree to summation order,
    and the ``highest`` policy is a rounding away."""
    p = mixture
    out, back, _, _, _ = _system_routed(p, p["x"], 0, 8, lax.Precision.DEFAULT)
    stated = _reference_routed(p, p["x"], 0, 8, "default")
    _close(out, stated, rtol=1e-5)
    exact = _reference_routed(p, p["x"], 0, 8, "highest")
    assert np.linalg.norm(np.asarray(out) - np.asarray(exact)) > 1e-3 * np.linalg.norm(exact)
    want = jax.grad(
        lambda w1: jnp.sum(
            _reference_routed({**p, "W1": jnp.asarray(p["W1"]).at[0:8].set(w1)}, p["x"], 0, 8, "default")
            * p["dout"]
        )
    )(p["W1"][0:8])
    _close(back(p["dout"])[2], want, rtol=1e-5)


@pytest.mark.parametrize("tile", [1, 8, 64, 512])
def test_the_tile_changes_no_result(mixture, tile):
    p = mixture
    base, base_back, *_ = _system_routed(p, p["x"], 2, 9, HIGHEST, tile=8)
    out, back, *_ = _system_routed(p, p["x"], 2, 9, HIGHEST, tile=tile)
    _close(out, base, rtol=1e-6)
    for a, b in zip(back(p["dout"]), base_back(p["dout"])):
        _close(a, b, rtol=1e-5)


def test_no_token_is_dropped_when_every_token_picks_one_expert(mixture):
    """All 96 tokens on expert 3 and three more: 12 full tiles of 8, no
    capacity to exceed."""
    p = mixture
    w_r = p["W_r"].copy()
    w_r[:] = 0.0
    x = np.abs(p["x"])  # every score of a positive row is above one half
    w_r[[3, 7, 8, 9]] = 1.0
    out, _, _, rows, sel = _system_routed({**p, "W_r": w_r}, x, 0, 8, HIGHEST)
    assert np.array_equal(np.asarray(rows), [0, 0, 0, TOKENS, 0, 0, 0, TOKENS])
    _close(out, _reference_routed({**p, "W_r": w_r}, x, 0, 8, "highest"), rtol=1e-5)


def _stacked_accumulator(lo, hi, seed=11):
    """Stale sums of earlier microbatches, shaped like the held ``W1``,
    ``W3``, ``W2``."""
    rng = np.random.default_rng(seed)
    return tuple(
        rng.standard_normal((hi - lo, *shape)).astype(np.float32)
        for shape in ((FF, D), (FF, D), (D, FF))
    )


@pytest.mark.parametrize("tile", [512, 4])
def test_the_backward_adds_into_the_accumulator_it_is_handed(mixture, tile):
    """Handed the accumulator's stacked leaves, the backward returns ``acc +
    gradient`` with ``dx`` and ``dweights`` untouched. With one tile an
    expert (512 rows) that is the old form's sum to the bit, ``acc + (0 +
    p)``; with tiles of 4 rows (three or more an expert) the products are
    added one by one, ``(acc + p1) + p2``: summation order alone."""
    p, (lo, hi) = mixture, (2, 9)
    _, back, _, rows, _ = _system_routed(p, p["x"], lo, hi, lax.Precision.DEFAULT, tile)
    tiles = -(-np.asarray(rows) // tile)
    assert tiles.max() == 1 if tile == 512 else tiles.min() >= 3
    acc = _stacked_accumulator(lo, hi)
    alone = back(p["dout"])
    into = back(p["dout"], tuple(map(jnp.asarray, acc)))
    for a, b in zip(into[:2], alone[:2]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for got, a, g in zip(into[2:], acc, alone[2:]):
        want = a + np.asarray(g)
        if tile == 512:
            assert np.array_equal(np.asarray(got), want)
        else:
            _close(got, want, rtol=1e-6)


def _idle_experts_case(mixture):
    """All 96 tokens on experts 3, 7, 8 and 9: of the held 0 to 7, experts 0,
    1, 2, 4, 5 and 6 take no row."""
    p = mixture
    w_r = np.zeros_like(p["W_r"])
    w_r[[3, 7, 8, 9]] = 1.0
    x = np.abs(p["x"])  # every score of a positive row is above one half
    _, back, _, rows, _ = _system_routed({**p, "W_r": w_r}, x, 0, 8, HIGHEST)
    idle = np.flatnonzero(np.asarray(rows) == 0)
    assert idle.tolist() == [0, 1, 2, 4, 5, 6]
    return back, idle


def test_an_expert_no_row_reaches_leaves_its_accumulator_slice_untouched(mixture):
    """The idle experts' slices come back as handed in, to the bit; the two
    that work add their gradient."""
    back, idle = _idle_experts_case(mixture)
    acc = _stacked_accumulator(0, 8)
    alone = back(mixture["dout"])
    into = back(mixture["dout"], tuple(map(jnp.asarray, acc)))
    for got, a, g in zip(into[2:], acc, alone[2:]):
        got = np.asarray(got)
        assert np.array_equal(got[idle], a[idle])
        _close(got[[3, 7]], a[[3, 7]] + np.asarray(g)[[3, 7]], rtol=1e-6)
        assert not np.array_equal(got[[3, 7]], a[[3, 7]])


@pytest.mark.parametrize("fresh", [True, jnp.asarray(True)])
def test_a_fresh_accumulator_is_read_as_zero(mixture, fresh):
    """``fresh`` (a Python or a traced bool): what the accumulator holds is
    never read, the idle experts' slices among it, and every output is the
    backward's without an accumulator, to the bit."""
    back, _ = _idle_experts_case(mixture)
    acc = _stacked_accumulator(0, 8)
    alone = back(mixture["dout"])
    into = back(mixture["dout"], tuple(map(jnp.asarray, acc)), fresh)
    for got, want in zip(into, alone):
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shares", [4, 2, 16])
def test_the_shares_add_up_to_the_uncut_layer(mixture, shares):
    """The share test: every share of the experts computes its own part of
    the routed result (routing over all of them, weights normalised over all
    the selected), and the parts, with the shared expert counted once, add up
    to what the uncut reference gives for the whole layer."""
    p, per = mixture, E // shares
    whole = ref.moe(p, p["x"], dict(M_MOE, routed_experts_held=[0, E]), ref._matmul("highest"))
    shared = ref.moe(
        {**_held(p, 0, 1), "W2": jnp.zeros_like(p["W2"][:1])}, p["x"],
        dict(M_MOE, routed_experts_held=[0, 1]), ref._matmul("highest"),
    )
    total, counted = shared, 0
    for share in range(shares):
        part, _, _, rows, _ = _system_routed(p, p["x"], share * per, (share + 1) * per, HIGHEST)
        total, counted = total + part, counted + int(np.asarray(rows).sum())
    assert counted == TOKENS * TOP  # every (token, slot) pair is some share's
    _close(total, whole, rtol=1e-5)


def test_route_selects_the_largest_scores_and_normalises_over_all_selected(mixture):
    p = mixture
    (weights, sel), back = ops.route(p["x"], p["W_r"], TOP)
    scores = jax.nn.sigmoid(jnp.matmul(p["x"], p["W_r"].T, precision=HIGHEST))
    want_sel = np.argsort(-np.asarray(scores), axis=-1, kind="stable")[:, :TOP]
    assert np.array_equal(np.asarray(sel), want_sel)
    picked = np.take_along_axis(np.asarray(scores), want_sel, axis=-1)
    _close(weights, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    _close(np.asarray(weights).sum(-1), np.ones(TOKENS), rtol=1e-6)
    dx, dw = back(jnp.ones_like(weights))  # the weights sum to one: no gradient
    assert float(jnp.abs(dx).max()) < 1e-6 and float(jnp.abs(dw).max()) < 1e-5
