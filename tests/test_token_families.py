"""The seam between the token families and the rest of the package: every
layer kind a family emits is one ``model.Part`` whose leaves, backward, FLOPs
and scan rule agree, and nothing outside ``model.py`` names a family or a
kind. A new family or kind that forgets a leaf, a FLOPs term or a rule fails
here by name."""

import ast
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from shallowspeed_tpu import model as Mo

ROOT = Path(__file__).resolve().parents[1]
SEQ = 16

OLMO = dict(
    model_type="olmo_hybrid", vocab_size=64, hidden_size=16, intermediate_size=24,
    num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2, rms_norm_eps=1e-6,
    layer_types=["linear_attention", "full_attention"],
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=4,
    linear_value_head_dim=8, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
)
SOLAR = dict(
    model_type="solar_open2", vocab_size=64, hidden_size=16, num_hidden_layers=2,
    num_attention_heads=4, head_dim=4, num_key_value_heads=2, rms_norm_eps=1e-5,
    gqa_layers=[0],
    linear_attn_config=dict(
        short_conv_kernel_size=4, head_dim=4, num_heads=2, num_kv_heads=None
    ),
    kda_allow_neg_eigval=True, n_routed_experts=8, routed_experts_held=[2, 5],
    n_shared_experts=1, num_experts_per_tok=2, moe_intermediate_size=8,
    norm_topk_prob=True, routed_scaling_factor=1, kda_gate_rank=4,
)
CONFIGS = {"olmo_hybrid": OLMO, "solar_open2": SOLAR}
# every (family, kind, layer index) the configurations above emit
LAYERS = [
    (family, kind, index)
    for family, config in CONFIGS.items()
    for index, kind in enumerate(Mo.make_token_spec(config, SEQ, 2).layer_types)
]
NAMES = {"olmo_hybrid", "solar_open2", "linear_attention", "full_attention", "kda", "gqa"}


def test_the_configurations_here_reach_every_family_and_kind():
    assert set(CONFIGS) == set(Mo.FAMILIES)
    assert {kind for _, kind, _ in LAYERS} == set(Mo.MIXERS)


@pytest.mark.parametrize(
    "family,kind,index", LAYERS, ids=[f"{family}-{kind}" for family, kind, _ in LAYERS]
)
def test_a_layers_backward_gives_each_of_its_leaves_and_nothing_else(family, kind, index):
    """``token_layer``'s backward, alone and into an accumulator, returns a
    gradient for exactly the leaves ``token_layer_shapes`` gives the layer,
    each of its leaf's shape; the kind's part has a FLOPs term and a scan
    rule or none."""
    spec = Mo.make_token_spec(CONFIGS[family], SEQ, 2)
    shapes = Mo.token_layer_shapes(spec)[index + 1]
    want = {name: shape for name, (shape, _) in shapes.items()}
    params = {name: jax.ShapeDtypeStruct(shape, jnp.float32) for name, shape in want.items()}
    x = jax.ShapeDtypeStruct((2, SEQ, spec.hidden_size), jnp.float32)
    seg = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)

    def both(p, x, seg):
        y, back = Mo.token_layer(p, x, seg, kind, spec, lax.Precision.HIGHEST, census=[])
        return back(y)[1], back(y, p, True)[1]

    for grads in jax.eval_shape(both, params, x, seg):
        got = {name: g.shape for name, g in grads.items()}
        assert set(got) == set(want), (
            f"{family} {kind}: no gradient for {sorted(set(want) - set(got))}, "
            f"a gradient without a leaf for {sorted(set(got) - set(want))}"
        )
        assert got == want
    part = Mo.MIXERS[kind]
    assert part.mix_flops is not None and 0 < part.flops(spec, 7.5) < math.inf
    if part.scan is None:
        assert not part.reads_pairs
    else:
        path, chunk = part.scan(spec)
        assert path in ("pallas", "xla") and SEQ % chunk == 0


def test_no_module_but_the_models_names_a_family_or_a_kind():
    """Outside ``model.py``, no string literal of the package is a family's
    or a kind's name and nothing compares ``.family``; the cost model's text
    names neither."""
    package = ROOT / "shallowspeed_tpu"
    found = []
    for path in sorted(package.rglob("*.py")):
        if path == package / "model.py":
            continue
        where = path.relative_to(package)
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and node.value in NAMES:
                found.append(f"{where}:{node.lineno} {node.value!r}")
            if isinstance(node, ast.Compare) and any(
                isinstance(side, ast.Attribute) and side.attr == "family"
                for side in (node.left, *node.comparators)
            ):
                found.append(f"{where}:{node.lineno} compares .family")
    assert found == []
    text = (package / "observability" / "costmodel.py").read_text()
    assert sorted(name for name in NAMES if name in text) == []
