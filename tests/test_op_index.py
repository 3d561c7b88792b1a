"""Scopes in the device programs and the op index that reads them
(observability/scopes.py, program_audit.op_index, compile_cache's tag)."""

import functools
import re
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from shallowspeed_tpu.api import TrainingSession
from shallowspeed_tpu.observability import scopes
from shallowspeed_tpu.observability.program_audit import op_index

ROOT = Path(__file__).resolve().parent.parent
SAMPLE = ROOT / "tests" / "op_index_sample.hlo.txt"
SIZES = (24, 20, 18, 16, 14, 12, 11, 10)
LAYOUTS = {"seq": {}, "dp2pp2": dict(dp=2, pp=2, schedule="pipedream")}
# every class of the scope table that the layout has
EXPECTED = {
    "seq": {"linear", "pointwise", "grad_acc", "update", "batch", "control"},
    "dp2pp2": {
        "linear", "pointwise", "stash", "mailbox", "relay", "grad_acc",
        "sync", "update", "batch", "control",
    },
}


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    rng = np.random.RandomState(0)
    dst = tmp_path_factory.mktemp("op_index_data")
    for suffix, n in (("train", 256), ("val", 64)):
        x = rng.randn(n, SIZES[0]).astype(np.float32)
        y = np.eye(SIZES[-1], dtype=np.float32)[rng.randint(0, SIZES[-1], n)]
        np.save(dst / f"x_{suffix}.npy", x)
        np.save(dst / f"y_{suffix}.npy", y)
    return dst


@pytest.fixture(scope="module", params=list(LAYOUTS))
def registered(request, data_dir):
    """One epoch of a toy session per layout: ``(layout, what the registry
    holds, the op index built from it)``. Both layouts register under one
    name, so the index is taken before the next session trains."""
    session = TrainingSession(
        data_dir=data_dir, sizes=SIZES, global_batch_size=64, lr=0.01,
        **LAYOUTS[request.param],
    )
    session.train_epoch()
    del session
    return (
        request.param,
        scopes.registered("jit_epoch_core"),
        scopes.program_index("jit_epoch_core"),
    )


def test_scopes_reach_the_compiled_text(registered):
    layout, _, index = registered
    executed = [
        e for e in index.values()
        if not e["computation"].startswith(("fused_", "wrapped_"))
    ]
    assert EXPECTED[layout] <= {e["cls"] for e in executed}
    named = {e["scope"] for e in index.values()} - {None}
    assert {"linear/fwd", "linear/dgrad", "linear/wgrad", "acc", "update"} <= named
    if layout == "dp2pp2":
        assert {"tick", "stash", "unstash", "mail", "relay", "sync/dp"} <= named
    assert named <= set(scopes.SCOPES)


def _arrays_in(obj, seen, path="fn"):
    """Paths to every ``jax.Array`` reachable from ``obj`` through closures,
    defaults, containers, partials, bound methods and instance attributes."""
    if id(obj) in seen:
        return []
    seen.add(id(obj))
    if isinstance(obj, jax.Array):
        return [path]
    if isinstance(obj, (list, tuple, set, frozenset)):
        children = [(f"{path}[{i}]", x) for i, x in enumerate(obj)]
    elif isinstance(obj, dict):
        children = [(f"{path}[{k!r}]", x) for k, x in obj.items()]
    elif isinstance(obj, functools.partial):
        children = [(f"{path}.partial", (obj.func, obj.args, obj.keywords))]
    elif isinstance(obj, types.MethodType):
        children = [(f"{path}.method", (obj.__func__, obj.__self__))]
    elif isinstance(obj, types.FunctionType):
        cells = zip(obj.__code__.co_freevars, obj.__closure__ or ())
        children = [(f"{path}.defaults", obj.__defaults__ or ())]
        for name, cell in cells:
            try:
                children.append((f"{path}.{name}", cell.cell_contents))
            except ValueError:  # an empty cell
                pass
    elif hasattr(obj, "__wrapped__"):
        children = [(f"{path}.__wrapped__", obj.__wrapped__)]
    elif hasattr(obj, "__dict__") and not isinstance(obj, (type, types.ModuleType)):
        children = [(f"{path}.__dict__", vars(obj))]
    else:
        children = []
    return [p for child_path, x in children for p in _arrays_in(x, seen, child_path)]


def test_the_registry_holds_no_array(registered):
    _, (jit_fn, args), _ = registered
    assert _arrays_in(jit_fn, set()) == []
    assert _arrays_in(args, set(), "args") == []
    shapes = [a for a in jax.tree.leaves(args) if isinstance(a, jax.ShapeDtypeStruct)]
    assert shapes and all(a.shape is not None for a in shapes)


# instruction of tests/op_index_sample.hlo.txt -> what the index says of it
RULES = {
    # a fusion that holds a convolution is the matmul, whatever its root
    "fusion.1": dict(cls="linear", scope="linear/fwd", kind="kOutput",
                     mixed=["linear", "pointwise"]),
    # own op_name
    "fusion.2": dict(cls="stash", scope="stash"),
    "dynamic-update-slice.2": dict(cls="mailbox", scope="mail"),
    "collective-permute-start.1": dict(cls="relay", scope="relay"),
    # a scope-less copy of a carry leaf: the class of what writes the leaf
    "copy.1": dict(cls="stash", via="while.1#1 stash"),
    # carried unchanged: what it was initialised from, an argument
    "copy.2": dict(cls="update", via="arg stacked['W'][0]"),
    "copy.4": dict(cls="batch", via="arg X"),
    # the chain ends at a relay's result: a consumer decides, and data
    # staged beside a relay is the mailbox's
    "copy.3": dict(cls="mailbox", via="to mail"),
    # zeros that initialise a carried buffer: the buffer's class
    "broadcast.2": dict(cls="stash", via="to while.1#1 stash"),
    # scalar integers under `tick` alone, or under nothing
    "add.1": dict(cls="control", scope="tick"),
    "compare.1": dict(cls="control"),
    "multiply.1": dict(cls="unattributed"),
    # containers: control flow, and every computation's own name
    "while.1": dict(cls="container", container=True),
    "conditional.1": dict(cls="container", container=True),
    "region_1.2": dict(cls="container", container=True, opcode="computation"),
}


@pytest.fixture(scope="module")
def sample_index():
    return op_index(SAMPLE.read_text())


@pytest.mark.parametrize("name", list(RULES))
def test_op_index_rules(sample_index, name):
    entry = sample_index[name]
    for key, value in RULES[name].items():
        assert entry.get(key) == value, (name, key, entry)
    assert entry["bytes"] >= 0 and entry["computation"]


def test_scope_of_takes_the_last_known_scope():
    path = "jit(f)/while/body/closed_call/tick/cond/branch_1_fun/act/linear/fwd/dot"
    assert scopes.scope_of(path) == ("linear/fwd", "linear")
    assert scopes.scope_of("jit(f)/tick/sync/dp/update/mul") == ("update", "update")
    assert scopes.scope_of("jit(f)/tick/min") == ("tick", None)
    assert scopes.scope_of("jit(f)/while/body/add") == (None, None)
    with pytest.raises(ValueError):
        scopes.scope("not-a-scope")


def test_compile_cache_dir_carries_the_scope_tag(tmp_path, monkeypatch):
    from shallowspeed_tpu.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        first = enable_compile_cache()
        assert first == str(tmp_path / scopes.CACHE_TAG) == enable_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert scopes.cache_tag() == scopes.CACHE_TAG == scopes.cache_tag(scopes.SCOPES)
    assert scopes.cache_tag(scopes.SCOPES + ("new",)) != scopes.CACHE_TAG
    assert scopes.cache_tag(salt="2") != scopes.CACHE_TAG
    assert re.fullmatch(r"s[0-9a-f]{8}", scopes.CACHE_TAG)


def test_named_scope_is_spelled_in_scopes_py_only():
    package = ROOT / "shallowspeed_tpu"
    users = [
        str(p.relative_to(package))
        for p in package.rglob("*.py")
        if "named_scope(" in p.read_text()
    ]
    assert users == ["observability/scopes.py"]
