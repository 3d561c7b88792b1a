"""The second token family (``model_type: solar_open2``: ``model.token_*``, the
sequential trainer's looped step and ``TrainingSession``) against the plain
reference the benchmark keeps, ``benchmarks/references/solar_open2.py``: tiny
widths, seeded weights, a CPU. The reference writes the per-channel delta rule
token by token, attention with the key/value heads repeated and the routed
mixture as a dense loop, and differentiates with ``jax.grad``."""

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu import trainer
from shallowspeed_tpu.api import TrainingSession
from shallowspeed_tpu.observability import JsonlMetrics, costmodel, read_jsonl, scopes
from shallowspeed_tpu.optimizer import SGD, WithGradScratch

ROOT = Path(__file__).resolve().parents[1]
HIGHEST = lax.Precision.HIGHEST


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(ROOT / "benchmarks" / "references" / "solar_open2.py", "ref_solar_open2_model")
check = _load(ROOT / "benchmarks" / "check.py", "bench_check_solar")
strata = _load(ROOT / "benchmarks" / "datasets" / "packed_tokens_strata.py", "strata")

TINY = dict(
    model_type="solar_open2", vocab_size=96, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=4, head_dim=8, num_key_value_heads=2, rms_norm_eps=1e-5,
    gqa_layers=[0],
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=8, num_heads=4, num_kv_heads=None),
    kda_allow_neg_eigval=True, n_routed_experts=16, routed_experts_held=[2, 6],
    n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=24,
    norm_topk_prob=True, routed_scaling_factor=1, kda_gate_rank=8,
)
SEQ = 48
M_REF = ref.model_config({"session": {"model": TINY}})
MM = ref._matmul("highest")


def _segments(rows, seed=0, rate=0.12, width=SEQ + 1):
    starts = np.random.default_rng(seed).random((rows, width)) < rate
    starts[:, 0] = False
    return np.cumsum(starts, axis=1).astype(np.int32)


def _tokens(rows, seed=1, width=SEQ + 1):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (rows, width)).astype(np.int32)


def _spec(batch=2, **changes):
    spec = Mo.make_token_spec(TINY, SEQ, batch, recompute=True)
    return dataclasses.replace(spec, scan_chunk=8, attn_block=16, moe_tile=8, **changes)


def _params(spec, seed=2):
    """The program's init, moved off its symmetric points."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: a + 0.1 * rng.standard_normal(a.shape).astype(np.float32)
        if a.ndim == 1 else a * 3.0,
        Mo.init_token_model(spec),
    )


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    scale = np.linalg.norm(want) + 1e-30
    assert np.linalg.norm(got - want) <= rtol * scale, np.linalg.norm(got - want) / scale


@pytest.fixture(scope="module")
def microbatch():
    spec = _spec()
    params = _params(spec)
    tokens, segments = _tokens(2), _segments(2)
    census = []
    loss, grads = Mo.token_loss_and_grads(
        params, spec, tokens, segments, HIGHEST, census=census
    )

    def reference(layers):
        return sum(
            ref.row_loss(layers, jnp.asarray(t), jnp.asarray(s), M_REF, MM, float(2 * SEQ))
            for t, s in zip(tokens, segments)
        )

    want_loss, want = jax.value_and_grad(reference)(params[0])
    return dict(spec=spec, params=params, tokens=tokens, segments=segments, loss=loss,
                grads=grads, census=census, want_loss=want_loss, want=want)


def test_model_loss_is_the_references(microbatch):
    _close(microbatch["loss"], microbatch["want_loss"], rtol=1e-6)


@pytest.mark.parametrize("index", range(6), ids=["embedding", "gqa", "kda1", "kda2", "kda3", "head"])
def test_model_gradients_are_the_references(microbatch, index):
    got, want = microbatch["grads"][0][index], microbatch["want"][index]
    assert set(got) == set(want) == set(Mo.token_layer_shapes(microbatch["spec"])[index])
    for name in got:
        # the gradient through the L2-normalised q and k of a delta-rule layer
        # is the float32-sensitive one (the Olmo cell's too)
        _close(got[name], want[name], rtol=5e-4)


@pytest.mark.parametrize(
    "recompute,order", [(True, [0, 1, 2, 3, 2, 1, 0]), (False, [0, 1, 2, 3])],
    ids=["recompute", "kept"],
)
def test_every_layer_but_the_last_is_recomputed(microbatch, monkeypatch, recompute, order):
    """``token_layer`` traced once a layer forward, then again for each
    layer but the last, a delta-rule layer here, as its backward comes due."""
    spec = dataclasses.replace(microbatch["spec"], recompute=recompute)
    real, calls = Mo.token_layer, []

    def spy(p, *args, **kw):
        calls.append(id(p))
        return real(p, *args, **kw)

    monkeypatch.setattr(Mo, "token_layer", spy)
    jax.eval_shape(
        lambda p: Mo.token_loss_and_grads(
            p, spec, microbatch["tokens"], microbatch["segments"], HIGHEST
        ),
        microbatch["params"],
    )
    layer_of = {key: index for index, key in enumerate(calls[:4])}
    assert [layer_of[key] for key in calls] == order
    assert spec.recomputed == tuple(i in order[4:] for i in range(4))


def test_recomputation_on_against_off(microbatch):
    spec = dataclasses.replace(microbatch["spec"], recompute=False)
    census = []
    loss, grads = Mo.token_loss_and_grads(
        microbatch["params"], spec, microbatch["tokens"], microbatch["segments"], HIGHEST,
        census=census,
    )
    _close(loss, microbatch["loss"], rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(microbatch["grads"])):
        _close(got, want, rtol=2e-3)
    # the census is the first forward's either way
    for got, want in zip(census, microbatch["census"], strict=True):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_census_counts_the_pairs_routed_to_the_experts_held(microbatch):
    census, spec = microbatch["census"], microbatch["spec"]
    assert len(census) == spec.routed_layers == 4
    assert all(c.shape == (4,) and c.dtype == jnp.int32 for c in census)
    # 96 tokens x 4 slots over 16 experts: 24 a held expert under even routing
    total = sum(int(c.sum()) for c in census)
    assert 0.5 * 4 * 4 * 24 < total < 2 * 4 * 4 * 24


def test_layer_kinds_shapes_and_the_family_switch():
    spec = _spec()
    assert spec.family == "solar_open2" and spec.layer_types == ("gqa", "kda", "kda", "kda")
    assert spec.experts_held == (2, 6) and spec.kv_heads == 2 and spec.attn_head_dim == 8
    shapes = Mo.token_layer_shapes(spec)
    assert shapes[1]["Wk"][0] == (2 * 8, 32) and shapes[1]["Wz"][0] == (4 * 8, 32)
    assert shapes[2]["W_fa"][0] == (8, 32) and shapes[2]["W_fb"][0] == (32, 8)
    assert shapes[2]["dt_bias"][0] == (32,) and shapes[2]["A_log"][0] == (4,)
    for layer in shapes[1:-1]:
        assert layer["W_r"][0] == (16, 32)  # the router keeps its published width
        assert layer["W1"][0] == (4, 24, 32) and layer["W2"][0] == (4, 32, 24)
    plan = Mo.token_scan_plan(spec, 2)
    assert plan["path"] == "xla" and plan["kernel_calls_per_step"] == 0
    assert plan["pairs_read_per_step"] == 0
    assert plan["recomputed_layers"] == 3 and spec.recomputed == (True, True, True, False)


def test_the_named_model_is_the_configuration_file_at_the_issues_count():
    config = Mo.token_model_config("solar-open2-250b")
    assert config["model_type"] == "solar_open2" and config["n_routed_experts"] == 320
    spec = Mo.make_token_spec(config, 2048, 8, mubatch_rows=1)
    assert spec.recompute and spec.experts_held == (0, 8) and spec.gate_rank == 128
    count = sum(
        int(np.prod(shape)) for layer in Mo.token_layer_shapes(spec) for shape, _ in layer.values()
    )
    # ISSUE 35's arithmetic: 1.296B parameters, 10.4 GB at 8 bytes
    assert count == pytest.approx(1.296e9, rel=2e-3)
    flops = costmodel.token_train_flops_per_token(spec, pairs_per_token=600)
    assert 4e9 < flops < 8e9  # ~6 x 0.93B matrix weights a token takes, and the scan


def test_the_cost_model_counts_what_the_reference_counts():
    """``costmodel.token_train_flops_per_token`` (what the session's ``mfu``
    divides) against ``references/solar_open2.py``'s ``train_flops_per_sample``
    (what the benchmark's does), at the published widths."""
    config = Mo.token_model_config("solar-open2-250b")
    spec = Mo.make_token_spec(config, 2048, 8, mubatch_rows=1)
    theirs = ref.train_flops_per_sample(
        {**config, "session": {"seq_len": 2048}}, pairs_per_token=500.0
    )
    ours = costmodel.token_train_flops_per_sample(spec, pairs_per_token=500.0)
    assert ours == pytest.approx(theirs, rel=1e-12)


@pytest.mark.parametrize(
    "change,match",
    [
        (dict(use_rope=True), "use_rope"),
        (dict(kda_use_full_proj=True), "kda_use_full_proj"),
        (dict(first_k_dense_replace=1), "first_k_dense_replace"),
        (dict(routed_experts_held=[4, 20]), "no range"),
        (dict(routed_experts_held=[3, 3]), "no range"),
        (dict(num_key_value_heads=3), "divide"),
        (dict(gqa_layers=[0, 9]), "past num_hidden_layers"),
        (dict(model_type="mamba9"), "model_type"),
    ],
)
def test_what_the_equations_do_not_cover_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        Mo.make_token_spec({**TINY, **change}, SEQ, 2)


def test_a_missing_key_is_named():
    config = {k: v for k, v in TINY.items() if k != "routed_experts_held"}
    with pytest.raises(ValueError, match="routed_experts_held"):
        Mo.make_token_spec(config, SEQ, 2)


# -- the step: unrolled and looped --------------------------------------------


def _step(spec, params, opt, state, xb, yb):
    fn = trainer.make_train_epoch(spec, opt, precision=HIGHEST)
    return fn(params, state, xb[None], yb[None])


def test_the_looped_step_is_the_unrolled_step(monkeypatch):
    """Three microbatches go through the loop (its accumulator the optimizer
    state's scratch), two through the straight-line program: the same update,
    loss and census, whatever the scratch held."""
    spec = _spec(batch=3)
    tokens, segments = _tokens(3, seed=3)[:, None], _segments(3, seed=4)[:, None]
    assert trainer.token_step_is_scanned(3) and not trainer.token_step_is_scanned(2)
    opt = WithGradScratch(SGD(0.05))
    state = opt.init(_params(spec))
    state["grads"] = jax.tree.map(lambda g: g + 7.0, state["grads"])  # never read
    looped = _step(spec, _params(spec), opt, state, tokens, segments)
    monkeypatch.setattr(trainer, "_UNROLLED_MUBATCHES", 3)
    unrolled = _step(spec, _params(spec), SGD(0.05), (), tokens, segments)
    for a, b in zip(jax.tree.leaves(looped[0]), jax.tree.leaves(unrolled[0])):
        _close(a, b, rtol=1e-6)
    _close(looped[2], unrolled[2], rtol=1e-6)
    assert np.array_equal(np.asarray(looped[-1]), np.asarray(unrolled[-1]))
    # the scratch comes back holding the step's gradient: (new - old) / -lr
    start = _params(spec)
    _close(
        looped[1]["grads"][0][-1]["W"],
        (np.asarray(looped[0][0][-1]["W"]) - np.asarray(start[0][-1]["W"])) / -0.05,
        rtol=1e-3,
    )


def test_with_grad_scratch_wraps_any_optimizer():
    opt = WithGradScratch(SGD(0.1))
    params = [[{"W": jnp.ones((2, 3))}]]
    state = opt.init(params)
    assert opt.lr == 0.1 and set(state) == {"opt", "grads"}
    assert opt.state_layout() == {"grads": "params"}
    grads = [[{"W": jnp.full((2, 3), 2.0)}]]
    new, state = opt.apply(params, grads, state)
    assert np.allclose(new[0][0]["W"], 0.8) and state["grads"] is grads


def test_the_looped_step_reads_nothing_of_the_scratch_it_is_handed():
    """The first microbatch takes the accumulator as zero, whatever the
    scratch held: a stale one and a zero one give the same weights, loss
    and census, to the bit."""
    spec = _spec(batch=3)
    tokens, segments = _tokens(3, seed=3)[:, None], _segments(3, seed=4)[:, None]
    opt = WithGradScratch(SGD(0.05))
    zero = opt.init(_params(spec))
    rng = np.random.default_rng(9)
    stale = {**zero, "grads": jax.tree.map(
        lambda g: g + rng.standard_normal(g.shape).astype(np.float32), zero["grads"]
    )}
    fresh = _step(spec, _params(spec), opt, zero, tokens, segments)
    after_stale = _step(spec, _params(spec), opt, stale, tokens, segments)
    for a, b in zip(jax.tree.leaves(fresh[0]), jax.tree.leaves(after_stale[0])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(fresh[2]) == float(after_stale[2])
    assert np.array_equal(np.asarray(fresh[-1]), np.asarray(after_stale[-1]))


def test_two_looped_steps_are_two_unrolled_steps(monkeypatch):
    """Two steps in one epoch program, the scratch carried from the first to
    the second: the second step's accumulator starts from zero again, so the
    weights are those of two steps of the straight-line program, to
    summation order."""
    spec = _spec(batch=3)
    tokens = _tokens(6, seed=5).reshape(2, 3, 1, SEQ + 1)
    segments = _segments(6, seed=6).reshape(2, 3, 1, SEQ + 1)
    opt = WithGradScratch(SGD(0.05))
    epoch = trainer.make_train_epoch(spec, opt, precision=HIGHEST)
    looped = epoch(_params(spec), opt.init(_params(spec)), tokens, segments)
    monkeypatch.setattr(trainer, "_UNROLLED_MUBATCHES", 3)
    unrolled = trainer.make_train_epoch(spec, SGD(0.05), precision=HIGHEST)(
        _params(spec), (), tokens, segments
    )
    for a, b in zip(jax.tree.leaves(looped[0]), jax.tree.leaves(unrolled[0])):
        _close(a, b, rtol=1e-6)
    _close(looped[2], unrolled[2], rtol=1e-6)
    assert np.array_equal(np.asarray(looped[-1]), np.asarray(unrolled[-1]))


def test_the_held_experts_gradients_are_made_in_the_accumulator():
    """The looped step as compiled: no held expert's weight gradient is
    stacked (``jnp.stack`` under ``moe/experts``) or added to the
    accumulator afterwards (``acc/add`` of a ``(held, out, in)`` leaf); the
    tile loops add into the accumulator's slices themselves."""
    spec = _spec(batch=3)
    params = _params(spec)
    opt = WithGradScratch(SGD(0.05))
    rows = jax.ShapeDtypeStruct((1, 3, 1, SEQ + 1), np.int32)
    epoch = trainer.make_train_epoch(spec, opt, precision=lax.Precision.DEFAULT)
    text = epoch.lower(params, opt.init(params), rows, rows).compile().as_text()
    held, ff, d = params[0][1]["W1"].shape
    stacked = {f"f32[{held},{ff},{d}]", f"f32[{held},{d},{ff}]"}
    made = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        shape = re.match(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])", line)
        if name and shape:
            made.append((name.group(1), shape.group(1)))
    assert not [n for n, _ in made if n.endswith("moe/experts/concatenate")]
    assert not [(n, t) for n, t in made if n.endswith("/acc/add") and t in stacked]
    updates = [t for n, t in made if n.endswith("experts/dynamic_update_slice")]
    assert any(t in stacked for t in updates)


# -- through TrainingSession --------------------------------------------------


@pytest.fixture(scope="module")
def token_set(tmp_path_factory):
    where = tmp_path_factory.mktemp("strata")
    tokens, segments = strata.make_dataset(
        11, 4, {"seq_len": SEQ}, {"vocab_size": TINY["vocab_size"]}, where
    )
    return where, np.array(tokens), np.array(segments)


@pytest.fixture(scope="module")
def trained(token_set):
    where, tokens, segments = token_set
    session = TrainingSession(
        data_dir=str(where), model=TINY, seq_len=SEQ, global_batch_size=4, mubatches=4,
        optimizer="sgd", lr=0.5, precision="highest",
    )
    start = check.layers(session.params())
    loss = session.train_epoch()
    counts = scopes.program_counts("jit_epoch_core")
    return dict(session=session, start=start, after=check.layers(session.params()),
                loss=loss, counts=dict(counts), tokens=tokens, segments=segments)


def test_one_step_through_the_session_is_the_references(trained):
    config = {"session": {"model": TINY, "optimizer": "sgd", "lr": 0.5,
                          "precision": "highest", "seq_len": SEQ}}
    prefix = check.prefix((trained["tokens"], trained["segments"]), 1, 4, 4)
    want, losses = ref.make_reference(config)(trained["start"], *prefix)
    report = check.compare(
        trained["after"], want, trained["start"],
        {"update_rtol": 2e-3, "weight_ulps": 8, "loss_rtol": 1e-5},
        loss=trained["loss"], ref_loss=losses[0],
    )
    assert report["ok"], report


def test_the_session_leaves_the_routing_counters_with_the_program(trained):
    counts = trained["counts"]
    assert counts["tokens"] == 4 * SEQ and counts["moe_layers"] == 4
    assert counts["moe_experts_held"] == 4
    # 192 tokens x 4 slots x 4 layers, 4 of 16 experts held: 768 under even routing
    assert 400 < counts["moe_rows_held"] < 1500
    mean = counts["moe_rows_held"] / (4 * 4)
    assert mean <= counts["moe_load_max"] <= counts["moe_rows_held"]
    assert trained["session"].scan_path == "xla"
    assert counts["scan_pairs_read"] == 0  # the XLA form keeps no pairs
    assert isinstance(trained["session"]._opt, WithGradScratch)


def test_the_session_counts_the_leaves_made_in_the_accumulator(trained):
    """4 routed layers x (W1, W3, W2) x 4 microbatches x 1 step."""
    assert trained["counts"]["acc_inplace_leaf_passes"] == 4 * 3 * 4


@pytest.mark.parametrize("mubatches,with_acc", [(8, 8), (3, 3), (2, 1), (1, 0)])
def test_the_count_follows_the_step_form(mubatches, with_acc):
    """The looped step makes the held experts' leaves in its accumulator in
    every microbatch; the straight-line step's first microbatch has no
    accumulator and makes them from zeros."""
    spec = _spec()
    assert spec.routed_layers == 4
    assert trainer.accumulated_expert_leaves(spec, mubatches) == 4 * 3 * with_acc


# heads of 128 channels in eights and rows of whole 64-token chunks: the
# shapes ``ops.kda_scan_path`` gives the kernels (interpreted on a CPU)
TILING = dict(
    TINY, num_hidden_layers=2,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=128, num_heads=8, num_kv_heads=None),
)


@pytest.fixture(scope="module")
def on_kernels(tmp_path_factory):
    where = tmp_path_factory.mktemp("strata_kernels")
    tokens, segments = strata.make_dataset(
        12, 2, {"seq_len": 64}, {"vocab_size": TINY["vocab_size"]}, where
    )
    with JsonlMetrics(where / "run.jsonl") as metrics:
        session = TrainingSession(
            data_dir=str(where), model=TILING, seq_len=64, global_batch_size=2, mubatches=2,
            optimizer="sgd", lr=0.5, precision="highest", metrics=metrics,
        )
        start = check.layers(session.params())
        loss = session.train_epoch()
    return dict(session=session, start=start, after=check.layers(session.params()),
                loss=loss, counts=dict(scopes.program_counts("jit_epoch_core")),
                tokens=np.array(tokens), segments=np.array(segments),
                events=[r for r in read_jsonl(where / "run.jsonl") if r.get("name") == "scan_path"])


def test_shapes_that_tile_run_the_kernels_and_the_session_says_so(on_kernels):
    session = on_kernels["session"]
    assert session.scan_path == "pallas"
    (event,) = on_kernels["events"]
    # the one kda layer is the last, whose forward runs once either way: 2
    # microbatches x (forward + backward)
    calls = 2 * 2
    recomputed = 1 if session.spec.recompute else 0  # the gqa layer
    fields = {k: event[k] for k in ("path", "chunk", "d_k", "d_v", "kernel_calls_per_step",
                                    "recomputed_layers")}
    assert fields == dict(path="pallas", chunk=64, d_k=128, d_v=128, kernel_calls_per_step=calls,
                          recomputed_layers=recomputed)
    # an epoch of one step
    assert on_kernels["counts"]["scan_kernel_calls"] == calls
    assert on_kernels["counts"]["recomputed_layer_passes"] == recomputed * 2
    # the kda layer's backward reads its forward's pairs, once a microbatch
    assert event["pairs_read_per_step"] == on_kernels["counts"]["scan_pairs_read"] == 2


def test_one_step_on_the_kernels_is_the_references(on_kernels):
    config = {"session": {"model": TILING, "optimizer": "sgd", "lr": 0.5,
                          "precision": "highest", "seq_len": 64}}
    prefix = check.prefix((on_kernels["tokens"], on_kernels["segments"]), 1, 2, 2)
    want, losses = ref.make_reference(config)(on_kernels["start"], *prefix)
    report = check.compare(
        on_kernels["after"], want, on_kernels["start"],
        {"update_rtol": 2e-3, "weight_ulps": 8, "loss_rtol": 1e-5},
        loss=on_kernels["loss"], ref_loss=losses[0],
    )
    assert report["ok"], report


@pytest.mark.parametrize(
    "seq,batch,mubatches,want",
    [
        # the cell: 8 microbatches x (2 recomputed kda layers x 3 passes + the
        # last x 2); the gqa layer is recomputed too
        (2048, 8, 8, dict(path="pallas", chunk=64, kernel_calls_per_step=64, recomputed_layers=3)),
        (2048, 8, 4, dict(path="pallas", chunk=64, kernel_calls_per_step=4 * 8,
                          recomputed_layers=3)),
        # no whole chunks of 64
        (2080, 8, 8, dict(path="xla", chunk=52, kernel_calls_per_step=0, recomputed_layers=3)),
    ],
)
def test_the_named_models_plan_by_shape(seq, batch, mubatches, want):
    config = Mo.token_model_config("solar-open2-250b")
    spec = Mo.make_token_spec(config, seq, batch, mubatch_rows=batch // mubatches)
    plan = Mo.token_scan_plan(spec, mubatches)
    assert {k: plan[k] for k in want} == want and plan["d_k"] == plan["d_v"] == 128


@pytest.mark.parametrize(
    "model,seq,batch,mubatches,want",
    [
        # the cell: 3 kda layers x 8 microbatches, each backward reading the
        # pair matrices its forward kept
        ("solar-open2-250b", 2048, 8, 8, 24),
        # the scalar rule's kernels keep no pair matrix
        ("olmo-hybrid-7b", 8192, 2, 2, 0),
    ],
)
def test_the_backwards_that_read_the_kept_pairs(model, seq, batch, mubatches, want):
    spec = Mo.make_token_spec(
        Mo.token_model_config(model), seq, batch, mubatch_rows=batch // mubatches
    )
    plan = Mo.token_scan_plan(spec, mubatches)
    assert plan["path"] == "pallas" and plan["pairs_read_per_step"] == want


def test_scopes_classes_of_the_new_work():
    assert scopes._CLASS_OF["kda/scan"] == "kda_scan"
    assert scopes._CLASS_OF["moe/route"] == "moe_route"
    assert scopes._CLASS_OF["moe/experts"] == "moe_experts"
    assert scopes.scope_of("jit(f)/while/body/moe/experts/dot_general") == ("moe/experts", "moe_experts")
    assert scopes.CACHE_TAG == scopes.cache_tag()


# -- the stratified generator -------------------------------------------------


def test_strata_segments_are_the_same_for_every_seed_and_tokens_are_not(tmp_path):
    session, data = {"seq_len": 2048}, {"vocab_size": 5000}
    a_tok, a_seg = strata.make_dataset(5, 8, session, data, tmp_path / "a")
    b_tok, b_seg = strata.make_dataset(2147483659, 8, session, data, tmp_path / "b")
    assert np.array_equal(a_seg, b_seg) and not np.array_equal(a_tok, b_tok)
    assert a_tok.shape == a_seg.shape == (8, 2049) and a_tok.dtype == np.int32
    # the frequent ids are the same ids under either seed
    top = lambda t: set(np.argsort(np.bincount(t.reshape(-1), minlength=5000))[-5:])  # noqa: E731
    assert len(top(a_tok) & top(b_tok)) >= 4
    lengths = strata.document_lengths(8 * 2049, 2048)
    assert lengths.min() >= 16 and lengths.max() <= 2048 and lengths.sum() >= 8 * 2049
    assert np.array_equal(np.sort(lengths), strata.stratum_lengths(len(lengths), 2048))


def test_the_benchmark_names_the_configuration_and_its_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "solar-open2-250b")
    config = json.loads((ROOT / entry["file"]).read_text())
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    cell = next(w for w in bench["workloads"] if w["config"] == "solar-open2-250b")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [cell["name"]]]
    assert {m["name"] for m in mine} == {
        "kda_scan_ms_per_step", "kda_scan_roofline", "moe_experts_ms_per_step",
        "moe_experts_roofline", "moe_route_ms_per_step", "moe_rows_per_token",
        "moe_load_max_over_mean",
    }
