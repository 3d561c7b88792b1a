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
    assert scopes.cache_tag(salt=scopes._SALT + "x") != scopes.CACHE_TAG
    assert re.fullmatch(r"s[0-9a-f]{8}", scopes.CACHE_TAG)


def test_named_scope_is_spelled_in_scopes_py_only():
    package = ROOT / "shallowspeed_tpu"
    users = [
        str(p.relative_to(package))
        for p in package.rglob("*.py")
        if "named_scope(" in p.read_text()
    ]
    assert users == ["observability/scopes.py"]


# -- the tick program's stash rings, in the text compiled for the chip --------
#
# The CPU compiler assigns no layouts, so only a compile for a described
# v5e:2x2 (no chip attached, nothing runs) can show whether a tick re-lays-out
# whole stash rings around its one-slot write (executor.py, where the rings
# are allocated; the recipe is in docs/observability.md).

# dp2 x pp2 pipedream, M 4 -> whole-ring ``copy`` the BACKWARD branch holds
# today: it hands every ring on in the layout it took it in, to read one slot.
# A later PR lowers these pins; the forward branch and the loop body hold none.
RING_CASES = {
    "deep-default": dict(
        sizes=(784,) + (256,) * 22 + (10,), batch=4096, precision="default",
        backward_copies=25,
    ),
    "mnist-highest": dict(
        sizes=(784, 128, 127, 126, 125, 124, 123, 10), batch=65536,
        precision="highest", backward_copies=9,
    ),
}


@pytest.fixture(scope="module")
def v5e_mesh():
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever libtpu raises where it cannot describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.asarray(topo.devices).reshape(2, 2), ("dp", "pp"))


def _compile_off_cache(lowered):
    """``lowered.compile()`` with the persistent cache off: a compile for a
    described chip is written to it but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@functools.lru_cache(maxsize=None)
def _compiled_tick_step(mesh, sizes, batch, precision, M=4):
    """``Compiled.as_text()`` of the training step for ``mesh`` (described
    devices: shapes in, no array), and the program's ring geometry. One
    compile per case, whichever test asks first."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu.api import PRECISIONS
    from shallowspeed_tpu.optimizer import SGD
    from shallowspeed_tpu.parallel import executor as E
    from shallowspeed_tpu.parallel import lower_schedule, make_mesh
    from shallowspeed_tpu.schedules import SCHEDULES

    spec = Mo.make_model_spec(sizes, 2, batch)
    prog = lower_schedule(SCHEDULES["pipedream"], M, 2)
    mb = batch // 2 // M

    def described(shape, dtype, pspec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))

    stacked, flags = jax.tree.map(
        lambda a: described(a.shape, a.dtype, a.sharding.spec),
        E.init_stacked(spec, make_mesh(2, 2)),
    )
    x, y = (described((batch, w), np.float32, P("dp")) for w in (sizes[0], sizes[-1]))
    step = E.make_pipeline_step(
        mesh, spec, prog, mb, SGD(0.006), precision=PRECISIONS[precision]
    )
    text = _compile_off_cache(step.lower(stacked, flags, (), x, y)).as_text()
    return text, prog.n_stash_slots + 1, mb


def _moves_memory_space_only(raw_type):
    """A ``copy-start`` whose two buffers differ in nothing but ``S(n)``:
    the compiler parking a small ring in fast memory, not a re-layout."""
    layouts = [re.sub(r"S\(\d+\)", "", l) for l in re.findall(r"\{[^{}]*\}", raw_type)]
    return len(layouts) >= 2 and layouts[0] == layouts[1]


@pytest.mark.parametrize("case", list(RING_CASES))
def test_forward_tick_copies_no_whole_stash_ring(v5e_mesh, case):
    from shallowspeed_tpu.observability.program_audit import parse_hlo

    cfg = RING_CASES[case]
    text, slots, mb = _compiled_tick_step(
        v5e_mesh, cfg["sizes"], cfg["batch"], cfg["precision"]
    )
    instrs, _ = parse_hlo(text)
    index = op_index(text)
    (tick,) = [
        i for i in instrs.values()
        if i["opcode"] == "conditional" and len(i["called"].get("branches", ())) == 3
    ]
    noop, forward, backward = tick["called"]["branches"]  # the op-code order

    def ring_movers(computation):
        """``{opcode: [names]}`` of the top-level ``copy`` / ``copy-start`` /
        ``transpose`` of ``computation`` whose result is a whole stash ring,
        in either orientation."""
        found = {"copy": [], "copy-start": [], "transpose": []}
        for name, e in index.items():
            if (
                e["computation"] != computation
                or e["cls"] != "stash"
                or e["opcode"] not in found
            ):
                continue
            dims = re.search(r"\[([\d,]*)\]", e["type"]).group(1).split(",")
            dims = [int(d) for d in dims if d]
            if len(dims) == 3 and dims[0] == slots and mb in dims[1:]:
                found[e["opcode"]].append(name)
        return found

    for computation in (forward, noop, tick["computation"]):
        movers = ring_movers(computation)
        assert not movers["copy"] and not movers["transpose"], (computation, movers)
        relayouts = [
            n for n in movers["copy-start"]
            if not _moves_memory_space_only(instrs[n]["type"])
        ]
        assert not relayouts, (computation, relayouts)
    # also what shows that the rings were found at all
    assert len(ring_movers(backward)["copy"]) == cfg["backward_copies"]


@pytest.mark.parametrize("case", list(RING_CASES))
def test_relays_run_under_their_predicate_and_copy_no_mailbox(v5e_mesh, case):
    """The relays follow the send tables: in the compiled tick body every
    ``collective-permute`` sits in the taken branch of a two-way conditional
    of its own (one per direction, beside the op-code ``switch``), whose
    other branch hands the mailbox on untouched; and nowhere in the loop is a
    whole mailbox copied or turned (a conditional that carried one through as
    a copy would cost more than the relay it skips)."""
    from shallowspeed_tpu.observability.program_audit import parse_hlo
    from shallowspeed_tpu.parallel import lower_schedule
    from shallowspeed_tpu.parallel.executor import relay_width
    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu.schedules import SCHEDULES

    cfg = RING_CASES[case]
    text, _, mb = _compiled_tick_step(
        v5e_mesh, cfg["sizes"], cfg["batch"], cfg["precision"]
    )
    instrs, comps = parse_hlo(text)
    conds = [i for i in instrs.values() if i["opcode"] == "conditional"]
    (tick,) = [c for c in conds if len(c["called"]["branches"]) == 3]
    relays = [c for c in conds if len(c["called"]["branches"]) == 2]
    assert len(relays) == 2  # forward and backward
    assert {c["computation"] for c in relays} == {tick["computation"]}
    skipped = {c["called"]["branches"][0] for c in relays}
    issued = {c["called"]["branches"][1] for c in relays}
    for name in skipped:  # nothing but the mailbox it was given
        assert [instrs[n]["opcode"] for n in comps[name]["instructions"]] == ["parameter"]
    permutes = [
        i for i in instrs.values() if i["opcode"].startswith("collective-permute")
    ]
    assert len(permutes) == 4  # a start and a done per direction
    assert {i["computation"] for i in permutes} == issued

    prog = lower_schedule(SCHEDULES["pipedream"], 4, 2)
    width = relay_width(Mo.make_model_spec(cfg["sizes"], 2, cfg["batch"]))
    mailboxes = {
        (prog.n_fwd_slots + 1, width, mb),  # forward: feature-major slots
        (prog.n_bwd_slots + 1, mb, width),
    }
    # the switch's backward branch is left out, as above: where a mailbox is
    # small enough it parks the backward one in fast memory, turned, to read
    # its slot (the parent did too); no relay is concerned in that
    noop, forward, _ = tick["called"]["branches"]
    held_to = skipped | issued | {tick["computation"], noop, forward}
    seen = set()
    for ins in instrs.values():
        shapes = [
            tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\[([\d,]*)\]", ins["type"])
        ]
        if not shapes or shapes[0] not in mailboxes:
            continue
        seen.add(ins["computation"])
        if ins["computation"] not in held_to:
            continue
        assert ins["opcode"] not in ("copy", "transpose"), ins["name"]
        if ins["opcode"] == "copy-start":
            assert _moves_memory_space_only(ins["type"]), ins["name"]
    assert issued <= seen  # the mailboxes were found where they are written


# -- the sequential path's resident set ------------------------------------
#
# The runtime stores ``X: f32[96,4,2048,784]`` rows-minor (``{2,3,1,0}``:
# 784 is no multiple of 128, 2,048 is), so a scan that asks for row-major
# microbatches re-lays-out each step's slab: a 26 MB ``copy`` per step in
# ``mnist-mlp.seq-b8192``. ``trainer.data_layout`` keeps such a set
# feature-major in shape too, and the microbatch scan reads it as it lies.

SEQ_CASES = {
    # mnist-mlp.seq-b8192: engages
    "b8192": dict(batch=8192, steps=96, layout="feature_major", temporaries=0),
    # mnist-mlp.seq-b128: 32-row microbatches, the bypass. Its temporaries
    # are the whole set, re-laid-out row-major on every call (the runtime
    # stores it step-axis minor, PERF.md section 4): a later PR lowers this pin
    "b128": dict(
        batch=128, steps=6144, layout="row_major", temporaries=3_021_189_120
    ),
}
SEQ_SIZES, SEQ_M = (784, 128, 127, 126, 125, 124, 123, 10), 4
SEQ_ARGUMENT_BYTES = 2_498_448_896  # X, Y and the parameters: nothing padded


@pytest.mark.parametrize("case", list(SEQ_CASES))
def test_sequential_step_copies_no_slab_of_x(v5e_mesh, case):
    """The epoch program at the benchmark's shapes, built the way the session
    builds it (``data_layout`` decides, ``feature_major`` re-orients): where
    the orientation engages no ``copy`` anywhere in the program has a step's
    slab of X for its result, in either orientation, and nothing is padded;
    at 32-row microbatches the program keeps the shapes and the bytes it had."""
    from jax.sharding import SingleDeviceSharding

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import trainer
    from shallowspeed_tpu.api import PRECISIONS
    from shallowspeed_tpu.observability.program_audit import parse_hlo
    from shallowspeed_tpu.optimizer import SGD

    cfg = SEQ_CASES[case]
    one_chip = SingleDeviceSharding(v5e_mesh.devices.flat[0])

    def described(shape):
        return jax.ShapeDtypeStruct(shape, np.float32, sharding=one_chip)

    nb, mb, F = cfg["steps"], cfg["batch"] // SEQ_M, SEQ_SIZES[0]
    layout = trainer.data_layout(mb, SEQ_SIZES, PRECISIONS["highest"])
    assert layout == cfg["layout"]
    placed = (nb, SEQ_M, mb, F)  # what the session places, by microbatch
    x_shape = placed
    if layout == "feature_major":
        turn = _compile_off_cache(trainer.feature_major.lower(described(placed)))
        x_shape = tuple(turn.out_info.shape)
        assert x_shape == (nb, SEQ_M, F, mb)
        # the set in and the set out: the transient peak of two sets that
        # the reshape it replaces had (PERF.md section 4), and no third
        assert turn.memory_analysis().temp_size_in_bytes == 0

    spec = Mo.make_model_spec(SEQ_SIZES, 1, cfg["batch"])
    params = jax.tree.map(lambda a: described(a.shape), Mo.init_model(spec))
    epoch = trainer.make_train_epoch(
        spec, SGD(0.006), precision=PRECISIONS["highest"], x_layout=layout
    )
    compiled = _compile_off_cache(
        epoch.lower(
            params, (), described(x_shape),
            described((nb, SEQ_M, mb, SEQ_SIZES[-1])),
        )
    )
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == SEQ_ARGUMENT_BYTES
    assert memory.temp_size_in_bytes == cfg["temporaries"]
    if layout == "row_major":
        return  # the program the bypass always ran
    instrs, _ = parse_hlo(compiled.as_text())

    def dims(ins):
        found = re.search(r"\[([\d,]*)\]", ins["type"])
        return tuple(int(d) for d in found.group(1).split(",") if d)

    slabs = {(SEQ_M, mb, F), (SEQ_M, F, mb), (1, SEQ_M, mb, F), (1, SEQ_M, F, mb)}
    slab_copies = [
        i["name"] for i in instrs.values()
        if i["opcode"] in ("copy", "transpose") and dims(i) in slabs
    ]
    assert not slab_copies, slab_copies


TOKEN_MODEL = dict(
    model_type="olmo_hybrid", vocab_size=1024, hidden_size=256, intermediate_size=512,
    num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
    rms_norm_eps=1e-6, layer_types=["linear_attention", "full_attention"],
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=96,
    linear_value_head_dim=192, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
)


def test_token_epoch_program_lands_in_classes_on_the_chip(v5e_mesh):
    """The token model's epoch program as the chip's compiler makes it (two
    microbatches of one 1,024-token row, head sizes as published, recomputed
    layers): it compiles (the blocked attention's loops with bounds read from
    the documents, the scan's matrix-product inverse), every class the model
    adds is there, and of the instructions that run on their own (outside
    fusions, containers aside) the unattributed hold under 1% of the bytes."""
    from jax.sharding import SingleDeviceSharding

    from shallowspeed_tpu import model as Mo
    from shallowspeed_tpu import trainer
    from shallowspeed_tpu.api import PRECISIONS
    from shallowspeed_tpu.optimizer import SGD

    one_chip = SingleDeviceSharding(v5e_mesh.devices.flat[0])
    spec = Mo.make_token_spec(TOKEN_MODEL, 1024, 2, recompute=True)
    params = [[
        {
            name: jax.ShapeDtypeStruct(shape, np.float32, sharding=one_chip)
            for name, (shape, _) in layer.items()
        }
        for layer in Mo.token_layer_shapes(spec)
    ]]
    rows = jax.ShapeDtypeStruct((1, 2, 1, 1025), np.int32, sharding=one_chip)
    epoch = trainer.make_train_epoch(spec, SGD(0.05), precision=PRECISIONS["default"])
    text = _compile_off_cache(epoch.lower(params, (), rows, rows)).as_text()
    index = op_index(text)
    alone = [
        e for e in index.values()
        if not e["container"] and "fused_computation" not in e["computation"]
        and e["opcode"] not in ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")
    ]
    classes = {e["cls"] for e in alone}
    assert {"gdn_scan", "attn", "token_mix", "head", "linear", "grad_acc", "update"} <= classes
    total = sum(e["bytes"] for e in alone)
    loose = sum(e["bytes"] for e in alone if e["cls"] == "unattributed")
    assert loose < 0.01 * total, (loose, total, [
        (e["opcode"], e["type"]) for e in alone if e["cls"] == "unattributed"
    ][:10])


# -- a Pallas kernel traced under a scope (the scan's, ops.gated_delta_scan) --

# as the chip's compiler prints the scan's two kernels (compiled for a
# described v5e, PR 33): a ``custom-call`` whose ``op_name`` is the scope's
# path, then the kernel's ``name=`` and ``pallas_call``
KERNEL_TEXT = """HloModule jit_epoch_core, is_scheduled=true

ENTRY %main.9 (q.1: f32[30,8192,96], p.1: f32[30,64,8,128]) -> f32[30,8192,192] {
  %q.1 = f32[30,8192,96]{2,1,0:T(8,128)} parameter(0), metadata={op_name="q"}
  %p.1 = f32[30,64,8,128]{3,2,1,0:T(8,128)} parameter(1), metadata={op_name="p"}
  %gdn_scan_fwd.1 = (f32[30,8192,192]{2,1,0:T(8,128)}, f32[30,64,96,192]{3,2,1,0:T(8,128)}) custom-call(f32[30,8192,96]{2,1,0:T(8,128)} %q.1, f32[30,64,8,128]{3,2,1,0:T(8,128)} %p.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[30,8192,96]{2,1,0}, f32[30,64,8,128]{3,2,1,0}}, metadata={op_name="jit(epoch_core)/while/body/closed_call/gdn/scan/gdn_scan_fwd/pallas_call" source_file="pallas_ops.py" source_line=1}
  %copy.7 = f32[30,8192,96]{2,1,0:T(8,128)} copy(f32[30,8192,96]{2,1,0:T(8,128)} %q.1), metadata={op_name="jit(epoch_core)/while/body/closed_call/gdn/scan/transpose"}
  ROOT %get-tuple-element.1 = f32[30,8192,192]{2,1,0:T(8,128)} get-tuple-element((f32[30,8192,192]{2,1,0:T(8,128)}, f32[30,64,96,192]{3,2,1,0:T(8,128)}) %gdn_scan_fwd.1), index=0, metadata={op_name="jit(epoch_core)/while/body/closed_call/gdn/scan/gdn_scan_fwd/pallas_call"}
}
"""


@pytest.mark.parametrize(
    "name,want",
    [
        ("gdn_scan_fwd.1", dict(opcode="custom-call", cls="gdn_scan", scope="gdn/scan")),
        ("copy.7", dict(opcode="copy", cls="gdn_scan", scope="gdn/scan")),
        ("get-tuple-element.1", dict(opcode="get-tuple-element", cls="gdn_scan", scope="gdn/scan")),
    ],
)
def test_a_kernel_under_a_scope_takes_the_scopes_class(name, want):
    entry = op_index(KERNEL_TEXT)[name]
    assert {key: entry.get(key) for key in want} == want
    assert not entry["container"]


def test_the_scan_kernels_trace_under_the_scans_scope():
    """The two ``pallas_call``s of the scan carry ``gdn/scan`` in their
    path, after the scope and before the kernel's own name: what the chip's
    compiler copies into the ``custom-call``'s ``op_name``."""
    import jax.numpy as jnp

    from shallowspeed_tpu import ops

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731

    def both(q, k, v, beta, log_decay, seg, do):
        o, back = ops.gated_delta_scan(q, k, v, beta, log_decay, seg)
        return o, back(do)

    text = jax.jit(both).lower(
        shape(1, 256, 2, 8), shape(1, 256, 2, 8), shape(1, 256, 2, 16),
        shape(1, 256, 2), shape(1, 256, 2),
        jax.ShapeDtypeStruct((1, 256), jnp.int32), shape(1, 256, 2, 16),
    ).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    for kernel in ("gdn_scan_fwd", "gdn_scan_bwd"):
        (path,) = [p for p in paths if p.endswith(f"{kernel}/pallas_call")]
        assert scopes.scope_of(path) == ("gdn/scan", "gdn_scan"), path


def _kda_both(q, k, v, beta, log_decay, seg, do):
    from shallowspeed_tpu import ops

    o, back = ops.kda_scan(q, k, v, beta, log_decay, seg)
    return o, back(do)


def _kda_shapes(seq, sharding=None):
    import jax.numpy as jnp

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)  # noqa: E731
    tokens = shape(1, seq, 8, 128)
    seg = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=sharding)
    return tokens, tokens, tokens, shape(1, seq, 8), tokens, seg, tokens


def test_the_kda_kernels_trace_under_the_scans_scope():
    """As the scalar rule's: ``kda/scan`` before the kernel's own name."""
    text = jax.jit(_kda_both).lower(*_kda_shapes(128)).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    for kernel in ("kda_scan_fwd", "kda_scan_bwd"):
        (path,) = [p for p in paths if p.endswith(f"{kernel}/pallas_call")]
        assert scopes.scope_of(path) == ("kda/scan", "kda_scan"), path


def test_the_kda_kernels_compile_for_the_chip_and_copy_no_operand(v5e_mesh, monkeypatch):
    """Mosaic takes both kernels at the cell's blocks (a chunk of 64 tokens
    of 8 heads of 128 channels, a head a sublane of each token's tile) with
    Mosaic in place of the interpreter a CPU would get, and XLA hands them
    ``q, k, v``, the log decay and the cotangents as they lie: no copy or
    transposition of a (1, seq, 8, 128) array is left in the program."""
    from jax.sharding import SingleDeviceSharding

    from shallowspeed_tpu import pallas_ops

    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    one_chip = SingleDeviceSharding(v5e_mesh.devices.flat[0])
    lowered = jax.jit(_kda_both).lower(*_kda_shapes(256, one_chip))
    text = _compile_off_cache(lowered).as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"', text)
    assert len(calls) == 2 and "kda_scan_fwd" in text and "kda_scan_bwd" in text
    moved = [
        line for line in text.splitlines()
        if re.search(r"= f32\[1,256,8,128\]\S* (copy|transpose)\(", line)
    ]
    assert not moved, moved


# -- the looped token step's first-microbatch flag (trainer._token_step_scanned)


def test_a_first_microbatch_select_survives_the_chips_compiler_behind_a_barrier(v5e_mesh):
    """A scan whose body selects zero for its carry on the first step, the
    step flag read through an optimization barrier as the looped token step
    reads it: the select is in the program the chip's compiler makes.
    Written on the counter itself as ``where(m > 0, a, 0)``, that compiler
    drops it and adds the first step onto the carry's start (PERF.md
    section 6). A CPU computes either form right, so only the compiled text
    is read here."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import SingleDeviceSharding

    def f(init, xs):
        def body(carry, x):
            a, m = carry
            first = lax.optimization_barrier(m) == 0
            return (jnp.where(first, 0.0, a) + 2.0 * x, m + 1), None

        return lax.scan(body, (init, jnp.zeros((), jnp.int32)), xs)[0][0]

    one_chip = SingleDeviceSharding(v5e_mesh.devices.flat[0])
    init = jax.ShapeDtypeStruct((1024, 1024), np.float32, sharding=one_chip)
    xs = jax.ShapeDtypeStruct((8, 1024, 1024), np.float32, sharding=one_chip)
    text = _compile_off_cache(jax.jit(f).lower(init, xs)).as_text()
    assert [line for line in text.splitlines() if " select(" in line]


def test_the_held_experts_first_tile_keeps_its_select_for_the_chip(v5e_mesh):
    """``ops.experts``' backward handed an accumulator and a traced
    ``fresh``, compiled for the chip: each held expert's first tile still
    selects zero in place of its three accumulator slices, one select a
    leaf and expert under ``moe/experts``, the tile's counter read through
    a barrier as the looped step reads its microbatch's."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from shallowspeed_tpu import ops

    held, tokens, top, d, ff = 4, 64, 2, 128, 256
    one_chip = SingleDeviceSharding(v5e_mesh.devices.flat[0])
    shape = lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip
    )
    leaves = (shape(held, ff, d), shape(held, ff, d), shape(held, d, ff))

    def f(x, sel, weights, w, dout, acc, fresh):
        _, back, _ = ops.experts(x, sel, weights, (0, held), *w, tile=16)
        return back(dout, acc, fresh)[2:]

    text = _compile_off_cache(jax.jit(f).lower(
        shape(tokens, d), shape(tokens, top, dtype=jnp.int32), shape(tokens, top),
        leaves, shape(tokens, d), leaves, shape(dtype=jnp.bool_),
    )).as_text()
    resets = [
        line for line in text.splitlines()
        if " select(" in line
        and re.search(r'op_name="[^"]*moe/experts/[^"]*select', line)
    ]
    assert len(resets) == 3 * held, resets
