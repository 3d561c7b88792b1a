"""A model's initial leaves drawn concurrently (``init.draw_leaves``): whatever
the number of workers, the tree is bit for bit the one a plain serial loop
over ``init.token_leaf_init`` / ``init.linear_init`` gives, in the same
order; the span ``draw`` and the event ``weights_init`` say what the pool did.
"""

import importlib.util
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from shallowspeed_tpu import init
from shallowspeed_tpu import model as Mo
from shallowspeed_tpu.api import TrainingSession
from shallowspeed_tpu.observability import JsonlMetrics, read_jsonl, spans

ROOT = Path(__file__).resolve().parent.parent
# each token family tiny, but for a vocabulary of 8,192 rows 128 wide: the
# embedding and the head are 2**20 elements each, so draws really overlap
OLMO = dict(
    model_type="olmo_hybrid", vocab_size=8192, hidden_size=128, intermediate_size=48,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    rms_norm_eps=1e-6,
    layer_types=["linear_attention", "linear_attention", "linear_attention",
                 "full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=6,
    linear_value_head_dim=12, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True,
)
SOLAR = dict(
    model_type="solar_open2", vocab_size=8192, hidden_size=128, num_hidden_layers=4,
    num_attention_heads=4, head_dim=8, num_key_value_heads=2, rms_norm_eps=1e-5,
    gqa_layers=[0],
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=8, num_heads=4, num_kv_heads=None),
    kda_allow_neg_eigval=True, n_routed_experts=16, routed_experts_held=[2, 6],
    n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=24,
    norm_topk_prob=True, routed_scaling_factor=1, kda_gate_rank=8,
)
MLP_SIZES = (1024, 1024, 37, 1024, 64, 10)  # at pp 2: 3 Linears and 2


def _serial_token_tree(spec):
    return [[
        {
            name: init.token_leaf_init(index, name, shape, kind)
            for name, (shape, kind) in layer.items()
        }
        for index, layer in enumerate(Mo.token_layer_shapes(spec))
    ]]


def _serial_mlp_tree(spec):
    return [
        [
            dict(zip(("W", "b"), init.linear_init(s.local_sizes[l], s.local_sizes[l + 1])))
            for l in range(s.n_linears)
        ]
        for s in spec.stages
    ]


TREES = {
    "olmo_hybrid": lambda: (Mo.make_token_spec(OLMO, 48, 2), Mo.init_token_model, _serial_token_tree),
    "solar_open2": lambda: (Mo.make_token_spec(SOLAR, 48, 2), Mo.init_token_model, _serial_token_tree),
    "mlp": lambda: (Mo.make_model_spec(MLP_SIZES, 1, 64), Mo.init_model, _serial_mlp_tree),
    "mlp_two_stages": lambda: (Mo.make_model_spec(MLP_SIZES, 2, 64), Mo.init_model, _serial_mlp_tree),
}


@pytest.fixture(scope="module", params=TREES)
def tree(request):
    """``(spec, the program's init, the serial loop's tree)``."""
    spec, program, serial = TREES[request.param]()
    return spec, program, serial(spec)


def _cores(monkeypatch, n):
    monkeypatch.setattr(init.os, "sched_getaffinity", lambda pid: set(range(n)))


def _pools(monkeypatch):
    """The worker counts of the pools ``init.draw_leaves`` starts from here on."""
    used = []
    real = init.concurrent.futures.ThreadPoolExecutor
    monkeypatch.setattr(
        init.concurrent.futures, "ThreadPoolExecutor",
        lambda n, *a: used.append(n) or real(n, *a),
    )
    return used


def _described(tree):
    """Names in order, shapes, dtypes and bytes of every leaf."""
    return [
        (jax.tree_util.keystr(path), leaf.shape, leaf.dtype.str, leaf.tobytes())
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


@pytest.mark.parametrize("workers", [1, 2, 8])
def test_the_tree_is_bitwise_the_serial_loops(tree, workers, monkeypatch):
    spec, program, want = tree
    _cores(monkeypatch, workers)
    used = _pools(monkeypatch)
    got = program(spec)
    draws = len(jax.tree.leaves(want)) // (1 if program is Mo.init_token_model else 2)
    # one worker: no pool at all
    assert used == ([] if workers == 1 else [min(workers, draws)])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    # dictionaries keep the order of token_layer_shapes, which a flattened
    # tree (sorted keys) does not show
    for got_stage, want_stage in zip(got, want):
        assert [list(layer) for layer in got_stage] == [list(layer) for layer in want_stage]
    assert max(leaf.size for leaf in jax.tree.leaves(got)) >= 1 << 20
    assert all(leaf.dtype == np.float32 for leaf in jax.tree.leaves(got))
    assert _described(got) == _described(want)


def test_a_tree_under_the_floor_is_drawn_by_the_caller(monkeypatch):
    _cores(monkeypatch, 8)
    monkeypatch.setattr(
        init.concurrent.futures, "ThreadPoolExecutor",
        lambda *a: pytest.fail("a pool for a tiny tree"),
    )
    spec = Mo.make_model_spec((784, 128, 127, 126, 125, 124, 123, 10), 1, 128)
    assert sum(leaf.size for leaf in jax.tree.leaves(Mo.init_model(spec))) < init.POOL_MIN_ELEMENTS
    mark = time.perf_counter_ns()
    threads = set()
    draws = [init.Draw(lambda: threads.add(threading.get_ident()), (), (1,))] * 3
    init.draw_leaves(draws)
    assert threads == {threading.get_ident()}
    assert [e.path for e in spans.log().entries() if e.start >= mark] == ["draw"]


def test_results_come_back_in_the_order_given_whatever_order_they_finish_in(monkeypatch):
    _cores(monkeypatch, 4)
    monkeypatch.setattr(init, "POOL_MIN_ELEMENTS", 0)
    started, threads = [], set()

    def draw(i, seconds):
        started.append(i)
        threads.add(threading.get_ident())
        time.sleep(seconds)
        return i

    # the first of the list is the slowest, the largest is in the middle
    sizes = [3, 1, 9, 2, 7, 5]
    draws = [
        init.Draw(draw, (i, 0.2 if i == 0 else 0.01 * i), (size,))
        for i, size in enumerate(sizes)
    ]
    assert init.draw_leaves(draws) == list(range(6))
    # the four largest start first, largest of all first
    assert started[0] == 2 and set(started[:4]) == {2, 4, 5, 0}
    assert 1 < len(threads) <= 4 and threading.get_ident() not in threads


def test_never_more_workers_than_draws(monkeypatch):
    _cores(monkeypatch, 64)
    monkeypatch.setattr(init, "POOL_MIN_ELEMENTS", 0)
    used = _pools(monkeypatch)
    assert init.draw_leaves([init.Draw(int, (str(i),), (1,)) for i in range(3)]) == [0, 1, 2]
    assert used == [3]
    monkeypatch.undo()
    assert init.draw_leaves([]) == []  # a stage that owns no Linear


@pytest.mark.parametrize("workers", [1, 8])
def test_an_unknown_leaf_kind_raises_from_the_callers_frame(workers, monkeypatch):
    _cores(monkeypatch, workers)
    monkeypatch.setattr(init, "POOL_MIN_ELEMENTS", 0)
    spec = Mo.make_token_spec({**OLMO, "vocab_size": 96, "hidden_size": 32}, 48, 2)
    shapes = Mo.token_layer_shapes(spec)
    shapes[2]["Wq"] = (shapes[2]["Wq"][0], "orthogonal")
    monkeypatch.setattr(Mo, "token_layer_shapes", lambda spec: shapes)
    before = threading.active_count()
    with pytest.raises(ValueError, match="unknown leaf kind 'orthogonal'") as raised:
        Mo.init_token_model(spec)
    frames = [entry.name for entry in raised.traceback]
    assert "init_token_model" in frames and frames[-1] == "token_leaf_init"
    assert threading.active_count() == before  # the pool is gone


def _mlp_dir(tmp_path):
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 96)):
        np.save(tmp_path / f"x_{suffix}.npy", rng.randn(n, MLP_SIZES[0]).astype(np.float32))
        np.save(
            tmp_path / f"y_{suffix}.npy",
            np.eye(MLP_SIZES[-1], dtype=np.float32)[rng.randint(0, MLP_SIZES[-1], n)],
        )
    return dict(sizes=MLP_SIZES, global_batch_size=64)


def _token_dir(tmp_path):
    module = importlib.util.spec_from_file_location(
        "packed_tokens", ROOT / "benchmarks" / "datasets" / "packed_tokens.py"
    )
    packed = importlib.util.module_from_spec(module)
    module.loader.exec_module(packed)
    packed.make_dataset(5, 8, {"seq_len": 48}, {"vocab_size": OLMO["vocab_size"]}, tmp_path)
    return dict(model=OLMO, seq_len=48, global_batch_size=4, mubatches=2, optimizer="sgd")


SESSIONS = {
    "mlp_sequential": (_mlp_dir, {}),
    "mlp_mesh_4_devices": (_mlp_dir, dict(dp=2, pp=2, schedule="pipedream")),
    "token": (_token_dir, {}),
}


@pytest.mark.parametrize("family", SESSIONS)
def test_a_session_under_metrics_says_what_the_pool_did(family, tmp_path, monkeypatch):
    _cores(monkeypatch, 3)
    make, layout = SESSIONS[family]
    kwargs = make(tmp_path)
    mark = time.perf_counter_ns()
    with JsonlMetrics(tmp_path / "run.jsonl") as metrics:
        session = TrainingSession(data_dir=str(tmp_path), metrics=metrics, **kwargs, **layout)
    leaves = jax.tree.leaves(session.params())
    records = read_jsonl(tmp_path / "run.jsonl")
    (event,) = [r for r in records if r.get("name") == "weights_init"]
    assert event["kind"] == "event"
    assert event["workers"] == 3
    assert event["leaves"] == len(leaves)
    assert event["elements"] == sum(leaf.size for leaf in leaves)
    assert event["largest_leaf_elements"] == max(leaf.size for leaf in leaves)
    # the log: one draw, inside the first block of session/weights, on the
    # thread that built the session
    entries = [e for e in spans.log().entries() if e.start >= mark]
    (draw,) = [e for e in entries if e.name == "draw"]
    assert draw.path == "session/init/session/weights/draw"
    assert draw.thread == threading.get_ident()
    assert event["draw_s"] == draw.duration / 1e9
    first = [e for e in entries if e.path == "session/init/session/weights"][0]
    assert first.start <= draw.start
    assert draw.start + draw.duration <= first.start + first.duration
    # placement follows the draw, on the same thread
    (put,) = [e for e in entries if e.path == "session/init/session/weights/device_put"]
    assert put.start >= draw.start + draw.duration and put.thread == draw.thread
    # and the stream holds the span as a record too
    (record,) = [r for r in records if r.get("kind") == "span" and r["name"] == "draw"]
    assert record["path"] == draw.path and record["seconds"] == event["draw_s"]
