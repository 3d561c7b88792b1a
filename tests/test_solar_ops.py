"""The second token family's ops (``ops.kda_scan``, grouped-query
``ops.attention``, ``ops.route`` and ``ops.experts``) against what they state
they compute, written plainly: the per-channel delta rule token by token,
attention with the key/value heads repeated, the routed mixture as a dense
loop over experts. Tiny sizes, a CPU, float32 passes."""

import functools
import importlib.util
import itertools
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl

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
    token-by-token rule on one case's inputs, and under ``"calls"`` what
    each kernel was handed and gave back, ``{"fwd" | "bwd": (operands,
    static, outputs)}``. One run per case."""
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
    calls = {}

    def recorded(name, kernel):
        def call(*operands, **static):
            calls[name] = (operands, static, kernel(*operands, **static))
            return calls[name][2]

        return call

    runs = {"calls": calls}
    with (
        mock.patch.object(ops, "_kda_kernel_fwd", recorded("fwd", ops._kda_kernel_fwd)),
        mock.patch.object(ops, "_kda_kernel_bwd", recorded("bwd", ops._kda_kernel_bwd)),
    ):
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


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_forward_keeps_each_chunks_pair_matrices(case):
    """The forward's fourth output is ``[kk | m]`` of every head and chunk,
    each as ``_kda_chunk`` builds it, masks included: to the bit but for
    ``m``'s diagonal, a sum over the channels that XLA's CPU backend orders
    by the program around it (one rounding apart, here or in a kernel)."""
    from shallowspeed_tpu import pallas_ops as K

    (q, k, _, g, p, _), _, (*_, pairs) = _kernel_run(case)["calls"]["fwd"]
    rows, seq, heads, _ = q.shape
    n, c = p.shape[1], p.shape[-1]
    assert pairs.shape == (rows * heads, seq, 2 * c)
    dot = K._gdn_dot(HIGHEST)
    built = jax.jit(lambda *a: K._kda_chunk(*a, jnp.ones((c, 1)), dot, dot))
    off = ~np.eye(c, dtype=bool)
    for r, h, j in itertools.product(range(rows), range(heads), range(n)):
        at = slice(j * c, (j + 1) * c)
        x = built(q[r, at, h], k[r, at, h], g[r, at, h], p[r, j])
        kk, m = np.split(np.asarray(pairs[r * heads + h, at]), 2, axis=1)
        assert np.array_equal(kk, x["kk"]), (r, h, j)
        assert np.array_equal(m[off], np.asarray(x["m"])[off]), (r, h, j)
        _close(np.diag(m), np.diag(x["m"]), rtol=1e-6)


def _rebuilding_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, p_ref, b_ref, s_ref, t_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, precision,
):
    """``pallas_ops._kda_bwd_kernel`` as it was before the forward kept the
    pair matrices: it builds them again, every level's product, with
    ``_kda_chunk``. The reference the kernel that reads them is held to."""
    from shallowspeed_tpu import pallas_ops as K

    dot, top = K._gdn_dot(precision), K._gdn_dot(HIGHEST)
    _NT, _TN = K._NT, K._TN

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def lanes(x):
        return jnp.sum(x, axis=1, keepdims=True)

    db_ref[...] = jnp.zeros_like(db_ref)

    def several(heads):
        at, beta, q, k, v, gl, p = K._kda_operands(
            (q_ref, k_ref, v_ref, g_ref), p_ref, b_ref, heads
        )
        each = range(len(heads))
        x = [K._kda_chunk(q[n], k[n], gl[n], p, beta[n], dot, top) for n in each]
        i, j, c = x[0]["i"], x[0]["j"], k[0].shape[0]
        g_in, g_out, keep = ([x[n][name] for n in each] for name in ("g_in", "g_out", "keep"))
        s, t = [s_ref[h, 0] for h in heads], [t_ref[h] for h in heads]
        do, ds_out = [do_ref[a] for a in at], [dstate[h] for h in heads]
        u0, w, u = K._kda_corrected(t, beta, k, v, g_in, s, dot)
        du = [dot(x[n]["m"], do[n], _TN) for n in each]
        du = [du[n] + dot(k[n] * g_out[n], ds_out[n], _NT) for n in each]
        dm = [dot(do[n], u[n], _NT) for n in each]
        dq_in = [dot(do[n], s[n]) for n in each]
        dk_out = [dot(u[n], ds_out[n]) for n in each]
        dw = [-dot(du[n], s[n]) for n in each]
        ds_in = [dot(do[n], q[n] * g_in[n], _TN) for n in each]
        ds_in = [ds_in[n] - dot(du[n], w[n], _TN) for n in each]
        dr_v = [dot(t[n], du[n], _TN) for n in each]
        dr_k = [dot(t[n], dw[n], _TN) for n in each]
        da = [dot(dr_v[n], u0[n], _NT) for n in each]
        da = [jnp.where(x[n]["lower"], -(da[n] + dot(dr_k[n], w[n], _NT)), 0.0) for n in each]
        dq, dk, dg, sym, dqk, dqk_t = [], [], [], [], [], []
        for n, h in enumerate(heads):
            dstate[h] = ds_out[n] * keep[n] + ds_in[n]
            dkeep = jnp.sum(ds_out[n] * s[n], axis=0, keepdims=True)
            on_diagonal = lanes(jnp.where(i == j, dm[n], 0.0))
            dq.append(dq_in[n] * g_in[n] + on_diagonal * k[n])
            dk.append(
                dk_out[n] * g_out[n] + (beta[n] * g_in[n]) * dr_k[n] + on_diagonal * q[n]
            )
            leaving = (dk_out[n] * k[n]) * g_out[n]
            last = jnp.sum(leaving, axis=0, keepdims=True) + dkeep * keep[n]
            through = (dq_in[n] * q[n] + beta[n] * dr_k[n] * k[n]) * g_in[n] - leaving
            dg.append(through + jnp.where(i[:, :1] == c - 1, last, 0.0))
            dkk = da[n] * beta[n]
            sym.append(dkk + dkk.T)
            dqk.append(jnp.where(x[n]["lower"], dm[n], 0.0))
            dqk_t.append(dqk[n].T)
            dv_ref[at[n]] = beta[n] * dr_v[n]
            dbeta = lanes(da[n] * x[n]["kk"]) + lanes(dr_v[n] * v[n])
            dbeta = dbeta + lanes(dr_k[n] * k[n] * g_in[n])
            db_ref[0, 0] += jnp.where(j == h, dbeta, 0.0).T[: K.KDA_ROWS]
        for level in range(1, len(x[0]["levels"]) + 1):
            here = x[0]["level_of"] == level
            e = [x[n]["levels"][level - 1][0] for n in each]
            k_e = [k[n] * e[n] for n in each]
            q_e = [q[n] * e[n] for n in each]
            rows, back = K._kda_halves(2 ** (level - 1), c)
            both = [
                dot(
                    jnp.concatenate(
                        [rows(jnp.where(here, dqk[n], 0.0), 1), jnp.where(here, sym[n], 0.0)],
                        axis=0,
                    ),
                    k_e[n],
                )
                for n in each
            ]
            as_i, of_kk = [back(m[:-c], 1) for m in both], [m[-c:] for m in both]
            as_j = [
                back(dot(rows(jnp.where(here, dqk_t[n], 0.0), 0), q_e[n]), 0) for n in each
            ]
            for n in each:
                upper = x[n]["levels"][level - 1][1]
                dx = as_i[n] * e[n]
                dk_level = (of_kk[n] + as_j[n]) * e[n]
                dq[n] = dq[n] + dx
                dk[n] = dk[n] + dk_level
                signed = jnp.where(upper, k[n] * dk_level, -(k[n] * dk_level))
                dg[n] = dg[n] + q[n] * dx + signed
        pulled = [top((i <= j).astype(dg[n].dtype), dg[n]) for n in each]
        for n in each:
            dq_ref[at[n]] = dq[n]
            dk_ref[at[n]] = dk[n]
            dg_ref[at[n]] = jnp.where(x[n]["first"], 0.0, pulled[n])

    K._kda_side_by_side(dstate, several)


@functools.partial(jax.jit, static_argnames=("precision", "interpret"))
def _rebuilding_scan_bwd(q, k, v, g, p, betas, states, inverses, do, *, precision, interpret):
    """``pallas_ops.kda_scan_bwd`` on ``_rebuilding_bwd_kernel``: no pairs in."""
    from shallowspeed_tpu import pallas_ops as K

    n = p.shape[1]
    x = K._kda_operand_specs(q, v, p, lambda j: n - 1 - j, interpret)
    return pl.pallas_call(
        functools.partial(_rebuilding_bwd_kernel, precision=precision),
        in_specs=[
            x["qk"], x["qk"], x["v"], x["qk"], x["p"], x["betas"], x["states"],
            x["inverses"], x["v"],
        ],
        out_specs=[x["qk"], x["qk"], x["v"], x["qk"], x["betas"]],
        out_shape=[jax.ShapeDtypeStruct(a.shape, q.dtype) for a in (q, k, v, g, betas)],
        **x["call"],
    )(q, k, v, g, p, betas, states, inverses, do)


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_backward_on_the_kept_pairs_is_the_rebuilding_backward_to_the_bit(case):
    """``dq, dk, dv, dg`` of the backward that reads the forward's pair
    matrices equal, bit for bit, those of the backward that built them
    again from the same operands. ``dbetas`` sums ``da kk`` over a chunk's
    tokens, a sum XLA's CPU backend orders by the program around it: there
    the two kernels differ by a rounding in a few of its entries."""
    (*operands, pairs, do), static, got = _kernel_run(case)["calls"]["bwd"]
    want = _rebuilding_scan_bwd(*operands, do, **static)
    for name, a, b in zip(("dq", "dk", "dv", "dg"), got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    _close(got[4], want[4], rtol=1e-6)


def test_the_backward_builds_the_running_sum_and_no_pair_matrix(monkeypatch):
    """Counted on the traced jaxpr at the kernels' chunk of 64 and 128 key
    channels: the helper the backward calls for a chunk issues one product,
    the running sum's triangle, where ``_kda_chunk`` issues that and one a
    level, seven; and the backward kernel traces without ``_kda_chunk``."""
    from shallowspeed_tpu import pallas_ops as K

    c, d = 64, 128
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    dot = K._gdn_dot(HIGHEST)

    def products(fn, *shapes):
        return sum(e.primitive.name == "dot_general" for e in jax.make_jaxpr(fn)(*shapes).eqns)

    assert products(lambda gl, p: K._kda_decays(gl, p, dot), shape(c, d), shape(8, c)) == 1
    chunk = lambda q, k, gl, p: K._kda_chunk(q, k, gl, p, jnp.ones((c, 1)), dot, dot)  # noqa: E731
    assert products(chunk, shape(c, d), shape(c, d), shape(c, d), shape(8, c)) == 1 + 6

    def refused(*_):
        raise AssertionError("the backward built the pair matrices again")

    monkeypatch.setattr(K, "_kda_chunk", refused)
    tokens = shape(1, c, 8, d)
    jax.make_jaxpr(functools.partial(K.kda_scan_bwd, precision=HIGHEST, interpret=True))(
        tokens, tokens, tokens, tokens, shape(1, 1, 8, c), shape(1, 1, 8, c),
        shape(8, 1, d, d), shape(8, c, c), shape(8, c, 2 * c), tokens,
    )
    with pytest.raises(AssertionError, match="built the pair matrices"):
        jax.make_jaxpr(
            functools.partial(K.kda_scan_fwd, leaf=16, precision=HIGHEST, interpret=True)
        )(tokens, tokens, tokens, tokens, shape(1, 1, 8, c), shape(1, 1, 8, c))


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
