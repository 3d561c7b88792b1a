"""The token model (``model.token_*``, the token ops of ``ops.py``, the
sequential trainer and ``TrainingSession``) against the plain reference the
benchmark keeps, ``benchmarks/references/olmo_hybrid.py``: tiny widths, seeded
weights, a CPU. The reference writes the gated delta rule token by token and
attention as one masked softmax, and gets its gradients from ``jax.grad``; the
system's chunked scan, blocked attention and hand-chained backward have to
agree with it leaf by leaf."""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from shallowspeed_tpu import model as Mo
from shallowspeed_tpu import trainer
from shallowspeed_tpu.api import TrainingSession
from shallowspeed_tpu.data import packed_counts
from shallowspeed_tpu.observability import costmodel, scopes
from shallowspeed_tpu.optimizer import SGD

ROOT = Path(__file__).resolve().parents[1]
HIGHEST = lax.Precision.HIGHEST


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(ROOT / "benchmarks" / "references" / "olmo_hybrid.py", "ref_olmo_hybrid")
check = _load(ROOT / "benchmarks" / "check.py", "bench_check")
solar_ref = _load(ROOT / "benchmarks" / "references" / "solar_open2.py", "ref_solar_open2_cost")

TINY = dict(
    model_type="olmo_hybrid", vocab_size=96, hidden_size=32, intermediate_size=48,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    rms_norm_eps=1e-6,
    layer_types=["linear_attention", "linear_attention", "linear_attention", "full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=6,
    linear_value_head_dim=12, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
)
SEQ = 48
M_REF = ref.model_config({"session": {"model": TINY}})
MM = ref._matmul("highest")


def _segments(rows, seed=0, rate=0.12, width=SEQ + 1):
    """Documents of random length, numbered from 0 in each row: starts fall
    inside chunks and blocks, and some documents span several."""
    starts = np.random.default_rng(seed).random((rows, width)) < rate
    starts[:, 0] = False
    return np.cumsum(starts, axis=1).astype(np.int32)


def _tokens(rows, seed=1, width=SEQ + 1):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], (rows, width)).astype(np.int32)


def _spec(batch=2, **changes):
    spec = Mo.make_token_spec(TINY, SEQ, batch, recompute=True)
    # chunks of 8 in blocks of 2, attention blocks of 16: several of each in
    # 48 tokens
    return dataclasses.replace(spec, scan_chunk=8, attn_block=16, **changes)


def _params(spec, seed=2):
    """The program's init, moved off its symmetric points (norm scales of
    exactly 1, tiny weights) so that every gradient is generic."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: a + 0.1 * rng.standard_normal(a.shape).astype(np.float32)
        if a.ndim == 1 else a * 20.0,
        Mo.init_token_model(spec),
    )


def _close(got, want, rtol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    scale = np.linalg.norm(want) + 1e-30
    assert np.linalg.norm(got - want) <= rtol * scale, (
        np.linalg.norm(got - want) / scale
    )


# -- the whole model ----------------------------------------------------------


def _reference_loss_and_grads(layers, tokens, segments, step_tokens):
    def loss(layers):
        return sum(
            ref.row_loss(layers, jnp.asarray(t), jnp.asarray(s), M_REF, MM, step_tokens)
            for t, s in zip(tokens, segments)
        )

    return jax.value_and_grad(loss)(layers)


@pytest.fixture(scope="module")
def microbatch():
    spec = _spec()
    params = _params(spec)
    tokens, segments = _tokens(2), _segments(2)
    fn = jax.jit(lambda p, t, s: Mo.token_loss_and_grads(p, spec, t, s, HIGHEST))
    loss, grads = fn(params, tokens, segments)
    want_loss, want = _reference_loss_and_grads(params[0], tokens, segments, float(2 * SEQ))
    return dict(spec=spec, params=params, tokens=tokens, segments=segments,
                loss=loss, grads=grads, want_loss=want_loss, want=want)


def test_model_loss_is_the_references(microbatch):
    _close(microbatch["loss"], microbatch["want_loss"], rtol=1e-6)


_LEAVES = [
    (index, name)
    for index, layer in enumerate(Mo.token_layer_shapes(Mo.make_token_spec(TINY, SEQ, 2)))
    for name in layer
]


@pytest.mark.parametrize("index,name", _LEAVES, ids=[f"{i}-{n}" for i, n in _LEAVES])
def test_model_gradient_is_the_references(microbatch, index, name):
    # float32 through four layers of weights scaled up twentyfold
    _close(microbatch["grads"][0][index][name], microbatch["want"][index][name], rtol=1e-3)


def test_recomputation_on_against_off(microbatch):
    spec = dataclasses.replace(microbatch["spec"], recompute=False)
    loss, grads = jax.jit(
        lambda p, t, s: Mo.token_loss_and_grads(p, spec, t, s, HIGHEST)
    )(microbatch["params"], microbatch["tokens"], microbatch["segments"])
    # the same expressions, fused otherwise: float32 rounding through four
    # layers of weights scaled up twentyfold
    _close(loss, microbatch["loss"], rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(microbatch["grads"])):
        _close(got, want, rtol=2e-3)


@pytest.mark.parametrize(
    "recompute,order", [(True, [0, 1, 2, 3, 2, 1, 0]), (False, [0, 1, 2, 3])],
    ids=["recompute", "kept"],
)
def test_every_layer_but_the_last_is_recomputed(microbatch, monkeypatch, recompute, order):
    """``token_layer`` traced once a layer forward, then again for each
    layer but the last as its backward comes due (the last one's backward
    runs on its first forward's residuals, right after the head)."""
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


def test_recomputation_is_chosen_by_what_a_microbatch_keeps():
    assert Mo.make_token_spec(TINY, SEQ, 2).recompute is False
    assert Mo.make_token_spec(
        Mo.token_model_config("olmo-hybrid-7b"), 8192, 2, mubatch_rows=1
    ).recompute is True


def test_two_documents_in_one_row_are_the_documents_in_two_rows(microbatch):
    """Nothing crosses a document start: not attention, not the recurrent
    state, not the convolution. (The target of a document's last token is
    the next row position's token in both layouts.)"""
    spec1 = dataclasses.replace(_spec(batch=1), seq_len=SEQ)
    half = SEQ // 2
    tokens = _tokens(1)
    one_row = np.concatenate([np.zeros(half, np.int32), np.ones(half + 1, np.int32)])[None]
    # the same two documents, one a row: same step_tokens, so the same scale
    spec2 = dataclasses.replace(_spec(batch=2), seq_len=half)
    two_tokens = np.stack([tokens[0, : half + 1], tokens[0, half:]])
    two_rows = np.zeros((2, half + 1), np.int32)
    p = microbatch["params"]
    loss1, g1 = jax.jit(lambda p: Mo.token_loss_and_grads(p, spec1, tokens, one_row, HIGHEST))(p)
    loss2, g2 = jax.jit(lambda p: Mo.token_loss_and_grads(p, spec2, two_tokens, two_rows, HIGHEST))(p)
    _close(loss1, loss2, rtol=1e-6)
    for got, want in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        _close(got, want, rtol=2e-3)


@pytest.fixture(scope="module")
def token_set(tmp_path_factory):
    """A packed set of 8 rows, as ``benchmarks/datasets/packed_tokens.py``
    writes one."""
    packed = _load(ROOT / "benchmarks" / "datasets" / "packed_tokens.py", "packed_tokens")
    data_dir = tmp_path_factory.mktemp("tokens")
    tokens, segments = packed.make_dataset(
        5, 8, {"seq_len": SEQ}, {"vocab_size": TINY["vocab_size"]}, data_dir
    )
    return data_dir, np.array(tokens), np.array(segments)


def _session(data_dir, **changes):
    kw = dict(
        model=TINY, seq_len=SEQ, global_batch_size=4, mubatches=2, lr=0.5,
        optimizer="sgd", precision="highest", data_dir=str(data_dir),
    )
    return TrainingSession(**{**kw, **changes})


@pytest.fixture(scope="module")
def trained(token_set):
    """Two steps (one whole epoch) through ``TrainingSession`` and through
    the reference, from the same start."""
    data_dir, tokens, segments = token_set
    session = _session(data_dir)
    start = check.layers(session.params())
    steps, loss = session.train_steps(2)
    after = check.layers(session.params())
    config = {"session": dict(model=TINY, optimizer="sgd", lr=0.5, precision="highest")}
    want, want_losses = ref.make_reference(config)(
        start, *check.prefix([tokens, segments], 2, 4, 2)
    )
    return dict(session=session, start=start, after=after, loss=loss, steps=steps,
                want=want, want_losses=want_losses)


def test_session_trains_the_references_steps(trained):
    assert trained["steps"] == 2 and trained["session"].batches_per_epoch == 2
    report = check.compare(
        trained["after"], trained["want"], trained["start"],
        dict(update_rtol=1e-3, weight_ulps=2, loss_rtol=1e-5),
        loss=trained["loss"], ref_loss=sum(trained["want_losses"]) / 2,
    )
    assert report["ok"], report
    # every leaf moved: the comparison judged an update, not storage
    moved = [
        float(np.linalg.norm(np.asarray(a[k]) - np.asarray(s[k])))
        for a, s in zip(trained["after"], trained["start"]) for k in a
    ]
    assert min(moved) > 0


def test_session_epochs_go_on_and_the_loss_falls(trained):
    session = trained["session"]
    losses = [session.train_epoch() for _ in range(3)]
    assert session.epoch == 4 and session.step_in_epoch == 0
    assert losses[-1] < trained["loss"]
    assert len(session.model_hash()) == 40
    session.assert_replicas_in_sync()


def test_one_microbatch_against_two(token_set, trained):
    session = _session(token_set[0], mubatches=1)
    session.train_steps(2)
    report = check.compare(
        check.layers(session.params()), trained["after"], trained["start"],
        dict(update_rtol=1e-4, weight_ulps=2),
    )
    assert report["ok"], report


def test_session_leaves_the_sets_counts_with_the_program(token_set, trained):
    counts = dict(scopes.program_counts("jit_epoch_core"))
    assert counts.pop("scan_kernel_calls") == 0  # 6 and 12 wide: the XLA form
    assert counts.pop("scan_pairs_read") == 0
    assert counts.pop("recomputed_layer_passes") == 0  # 96 tokens keep every layer
    assert counts.pop("acc_inplace_leaf_passes") == 0  # no routed layer
    assert counts == packed_counts(token_set[2]) == ref.packed_counts(token_set[2])
    assert counts["tokens"] == 8 * SEQ
    assert counts["documents"] <= counts["tokens"] <= counts["pairs"]


def test_packed_counts_by_hand():
    seg = np.array([[0, 0, 0, 1, 1, 9], [0, 1, 2, 2, 2, 9]])  # the last column is no input
    assert packed_counts(seg) == {"tokens": 10, "documents": 5, "pairs": (1 + 2 + 3 + 1 + 2) + (1 + 1 + 1 + 2 + 3)}


# -- which form the scan runs: the session says, by the shapes alone ---------

TILING = {**TINY, "linear_key_head_dim": 8, "linear_value_head_dim": 16}
SCAN_SEQ = 128  # one chunk of exactly 128 tokens


@pytest.fixture(scope="module")
def on_kernels(tmp_path_factory):
    """One step of a session whose head sizes and sequence the kernels take
    (interpreted here), through the reference from the same start, and what
    the session wrote."""
    from shallowspeed_tpu.observability import JsonlMetrics, read_jsonl

    packed = _load(ROOT / "benchmarks" / "datasets" / "packed_tokens.py", "packed_tokens")
    data_dir = tmp_path_factory.mktemp("tokens128")
    tokens, segments = packed.make_dataset(
        5, 2, {"seq_len": SCAN_SEQ}, {"vocab_size": TILING["vocab_size"]}, data_dir
    )
    with JsonlMetrics(data_dir / "run.jsonl") as metrics:
        session = _session(
            data_dir, model=TILING, seq_len=SCAN_SEQ, global_batch_size=2,
            metrics=metrics,
        )
        start = check.layers(session.params())
        steps, loss = session.train_steps(1)
    config = {"session": dict(model=TILING, optimizer="sgd", lr=0.5, precision="highest")}
    want, want_losses = ref.make_reference(config)(
        start, *check.prefix([np.array(tokens), np.array(segments)], 1, 2, 2)
    )
    return dict(
        session=session, start=start, after=check.layers(session.params()),
        loss=loss, want=want, want_losses=want_losses,
        events=[r for r in read_jsonl(data_dir / "run.jsonl") if r.get("name") == "scan_path"],
        counts=scopes.program_counts("jit_epoch_core"),
    )


def test_session_on_the_kernels_trains_the_references_step(on_kernels):
    report = check.compare(
        on_kernels["after"], on_kernels["want"], on_kernels["start"],
        dict(update_rtol=1e-3, weight_ulps=2, loss_rtol=1e-5),
        loss=on_kernels["loss"], ref_loss=on_kernels["want_losses"][0],
    )
    assert report["ok"], report


def test_session_says_which_form_the_scan_runs(on_kernels, trained):
    assert on_kernels["session"].scan_path == "pallas"
    assert trained["session"].scan_path == "xla"


def test_scan_path_event_and_the_programs_count(on_kernels):
    spec = on_kernels["session"].spec
    # 3 Gated DeltaNet layers, none of them the last, x 2 microbatches x
    # (forward [+ forward again] + backward)
    recomputed = 3 if spec.recompute else 0
    calls = 2 * (3 * 2 + recomputed)
    (event,) = on_kernels["events"]
    fields = {k: event[k] for k in ("path", "chunk", "d_k", "d_v", "kernel_calls_per_step",
                                    "recomputed_layers")}
    assert fields == dict(path="pallas", chunk=128, d_k=8, d_v=16, kernel_calls_per_step=calls,
                          recomputed_layers=recomputed)
    # an epoch of one step
    assert on_kernels["counts"]["scan_kernel_calls"] == calls
    assert on_kernels["counts"]["recomputed_layer_passes"] == recomputed * 2
    assert on_kernels["counts"]["tokens"] == 2 * SCAN_SEQ
    # the scalar rule's backward rebuilds what it needs: no pairs are kept
    assert event["pairs_read_per_step"] == on_kernels["counts"]["scan_pairs_read"] == 0


@pytest.mark.parametrize(
    "changes,seq,recompute,want",
    [
        ({}, SEQ, True, dict(path="xla", chunk=48, kernel_calls_per_step=0, recomputed_layers=3)),
        # the rehearsal's
        ({}, 64, False, dict(path="xla", chunk=64, kernel_calls_per_step=0, recomputed_layers=0)),
        (TILING, 128, False,
         dict(path="pallas", chunk=128, kernel_calls_per_step=12, recomputed_layers=0)),
        (TILING, 256, True,
         dict(path="pallas", chunk=128, kernel_calls_per_step=18, recomputed_layers=3)),
        # a scan layer last: its forward runs once, 2 x (3 + 3 + 2)
        ({**TILING, "layer_types": ["full_attention"] + 3 * ["linear_attention"]}, 256, True,
         dict(path="pallas", chunk=128, kernel_calls_per_step=16, recomputed_layers=3)),
        # the cell's: 3 scan layers x 2 microbatches x (2 forwards + 1
        # backward); the last layer, full attention, is the one kept
        (dict(linear_key_head_dim=96, linear_value_head_dim=192), 8192, True,
         dict(path="pallas", chunk=128, d_k=96, d_v=192, kernel_calls_per_step=18,
              recomputed_layers=3)),
    ],
)
def test_scan_plan_counts_layers_microbatches_and_passes(changes, seq, recompute, want):
    spec = Mo.make_token_spec({**TINY, **changes}, seq, 2, recompute=recompute)
    plan = Mo.token_scan_plan(spec, mubatches=2)
    assert {k: plan[k] for k in want} == want


@pytest.mark.parametrize(
    "sizes,batch,layout",
    [
        ((784, 128, 127, 126, 125, 124, 123, 10), 128, "row_major"),  # mnist-mlp, narrow
        ((784, 128, 127, 126, 125, 124, 123, 10), 2048, "feature_major"),
        ((784,) + (64,) * 6 + (10,), 256, "row_major"),  # mlp-deep's form, thin
    ],
)
def test_mlp_epoch_program_never_asks_the_scans_rule(monkeypatch, sizes, batch, layout):
    """The MLP's lowered text with the scan's rule, its wrapper and the
    kernels' module broken is the text without: nothing of them is traced."""
    import sys

    def lowered():
        spec = Mo.make_model_spec(sizes, 1, batch)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), Mo.init_model(spec)
        )
        mb = batch // 4
        x = (2, 4, sizes[0], mb) if layout == "feature_major" else (2, 4, mb, sizes[0])
        epoch = trainer.make_train_epoch(spec, SGD(0.006), precision=HIGHEST, x_layout=layout)
        return epoch.lower(
            params, (), jax.ShapeDtypeStruct(x, jnp.float32),
            jax.ShapeDtypeStruct((2, 4, mb, sizes[-1]), jnp.float32),
        ).as_text()

    plain = lowered()

    def broken(*a, **kw):
        raise AssertionError("an MLP reached the scan")

    from shallowspeed_tpu import ops

    for name in ("scan_path", "gated_delta_scan", "_gated_delta_scan_pallas"):
        monkeypatch.setattr(ops, name, broken)
    monkeypatch.setitem(sys.modules, "shallowspeed_tpu.pallas_ops", None)
    assert lowered() == plain
    assert "gdn" not in plain and "custom_call" not in plain


REFUSED = {
    "dp": dict(dp=2), "pp": dict(pp=2), "tp": dict(tp=2),
    "zero": dict(dp=2, zero=1), "fuse_mubatches": dict(fuse_mubatches=True),
    "megakernel": dict(fuse_mubatches=True, megakernel=True),
    "pallas": dict(pp=2, kernel_backend="pallas"),
    "mpmd": dict(pp=2, runtime="mpmd"), "digests": dict(digests=True),
    "checkpoint_dir": dict(checkpoint_dir="/nowhere"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_mesh_layouts_and_linear_only_paths_refuse_a_token_model(token_set, what):
    with pytest.raises(ValueError, match="R0a"):
        _session(token_set[0], **REFUSED[what])


@pytest.mark.parametrize("method", ["save", "predict", "accuracy", "train_run"])
def test_linear_only_methods_refuse_a_token_model(trained, method):
    args = {"save": ("x.npz",), "predict": (np.zeros((1, 4)),), "accuracy": (), "train_run": (1,)}
    with pytest.raises(ValueError, match="R0a"):
        getattr(trained["session"], method)(*args[method])


def test_ids_outside_the_vocabulary_are_refused(token_set):
    with pytest.raises(ValueError, match="vocabulary of 64"):
        _session(token_set[0], model={**TINY, "vocab_size": 64})


def test_a_token_model_needs_a_sequence_length_and_an_mlp_takes_none(token_set):
    with pytest.raises(ValueError, match="seq_len"):
        _session(token_set[0], seq_len=None)
    with pytest.raises(ValueError, match="seq_len"):
        _session(token_set[0], seq_len=SEQ - 1)
    with pytest.raises(ValueError, match="seq_len"):
        TrainingSession(model="mlp-wide", seq_len=8, data_dir=str(token_set[0]))


@pytest.mark.parametrize(
    "change,match",
    [
        (dict(num_key_value_heads=2), "grouped"),
        (dict(rope_parameters={"rope_theta": 10000.0}), "rotary"),
        (dict(tie_word_embeddings=True), "tie_word_embeddings"),
        (dict(layer_types=["linear_attention"] * 3), "num_hidden_layers"),
        (dict(layer_types=["sliding_attention"] * 4), "unknown layer types"),
    ],
)
def test_what_the_equations_do_not_cover_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        Mo.make_token_spec({**TINY, **change}, SEQ, 2)


def test_the_named_model_is_the_configuration_file():
    """The program hard-codes no width: ``olmo-hybrid-7b`` is the file the
    benchmark's configuration names, published widths and the stated cut."""
    config = Mo.token_model_config("olmo-hybrid-7b")
    spec = Mo.make_token_spec(config, 8192, 2, mubatch_rows=1)
    assert (spec.hidden_size, spec.intermediate_size, spec.num_attention_heads) == (3840, 11008, 30)
    assert (spec.linear_num_heads, spec.linear_key_head_dim, spec.linear_value_head_dim) == (30, 96, 192)
    assert spec.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert spec.vocab_size == 12544 == config["published"]["vocab_size"] // 8
    held = sum(int(np.prod(shape)) for layer in Mo.token_layer_shapes(spec) for shape, _ in layer.values())
    assert 928.7e6 < held < 928.9e6  # ISSUE 32's 928.8M, norms and gates included


def test_init_is_seeded_by_layer_and_leaf():
    a, b = Mo.init_token_model(_spec()), Mo.init_token_model(_spec())
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    layers = a[0]
    assert not np.array_equal(layers[1]["Wq"], layers[2]["Wq"])  # same shape, other layer
    assert np.all(layers[1]["attn_norm"] == 1.0)
    decay = np.exp(-np.exp(layers[1]["A_log"]) * np.log1p(np.exp(layers[1]["dt_bias"])))
    assert np.all((decay > 0.1) & (decay < 1.0))


@pytest.mark.parametrize(
    "reference,model,seq,pairs,rel",
    [
        (ref, TINY, SEQ, (3.0, 20.5), None),
        # the second family's named configuration, as tests/test_solar_model.py
        # checks it: the session's mfu and the benchmark's divide the same count
        (solar_ref, "solar-open2-250b", 2048, (500.0, 600.0), 1e-12),
    ],
    ids=["olmo-tiny", "solar-open2-250b"],
)
def test_cost_model_counts_what_the_reference_counts(reference, model, seq, pairs, rel):
    config = Mo.token_model_config(model)
    spec = Mo.make_token_spec(config, seq, 2, mubatch_rows=1)
    session = {**config, "session": {"model": config, "seq_len": seq}}
    for p in pairs:
        assert costmodel.token_train_flops_per_sample(spec, p) == pytest.approx(
            reference.train_flops_per_sample(session, p), rel=rel
        )
    assert costmodel.train_flops_per_sample(spec) == costmodel.token_train_flops_per_sample(spec)


# -- the epoch program's names ------------------------------------------------


def test_epoch_programs_op_names_carry_the_new_scopes():
    """Forward and backward of every token op trace under the op's scope,
    also where the backward is ``jax.vjp``'s (the transform wraps what
    follows the scope in the path). (That every instruction the chip's
    compiler makes of them lands in a class is tests/test_op_index.py's.)"""
    import re

    spec = _spec(batch=4)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), Mo.init_token_model(spec))
    rows = jax.ShapeDtypeStruct((2, 2, 2, SEQ + 1), jnp.int32)
    text = trainer.make_train_epoch(spec, SGD(0.1)).lower(shapes, (), rows, rows).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text)) | set(re.findall(r'op_name="([^"]+)"', text))
    found = {scopes.scope_of(name) for name in names}
    for scope in ("gdn/scan", "attn/core", "gdn/conv", "gdn/gate", "norm", "swiglu",
                  "fanin", "embed", "head/xent", "linear/fwd", "linear/dgrad",
                  "linear/wgrad", "acc", "update", "batch", "loss"):
        assert (scope, scopes._CLASS_OF[scope]) in found, scope
    assert scopes.scope_of("jit(f)/gdn/scan/transpose(jvp(while))/body/mul") == ("gdn/scan", "gdn_scan")


def test_scopes_classes_and_cache_tag():
    assert {scopes._CLASS_OF[s] for s in ("gdn/conv", "gdn/gate", "norm", "swiglu")} == {"token_mix"}
    assert scopes._CLASS_OF["embed"] == scopes._CLASS_OF["head/xent"] == "head"
    assert scopes.CACHE_TAG == scopes.cache_tag()
    assert scopes.cache_tag(scopes.SCOPES[:-8]) != scopes.CACHE_TAG


def test_trainer_refuses_the_linear_only_paths():
    with pytest.raises(ValueError, match="token model"):
        trainer.make_train_epoch(_spec(), SGD(0.1), fuse_mubatches=True)
