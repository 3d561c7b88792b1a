"""The second token configuration's reference module: its cut, its cost
functions against arithmetic done by hand, and its control (one precision step
below the stated policy) failing the rehearsal's tolerance."""

import json
from pathlib import Path

import numpy as np
import pytest

import cells
import check
import tolerance_probe_tokens

BENCH = Path(__file__).resolve().parents[1]
CELL = "solar-open2-250b.seq-strata-t16384"
ref = cells.load_module(BENCH / "references" / "solar_open2.py")
CATALOG = {  # the catalog row's config (architectures.jsonl, Solar-Open2-250B)
    "model_type": "solar_open2", "partial_rotary_factor": 1, "hidden_size": 4096,
    "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
}


@pytest.fixture(scope="module")
def config():
    return cells.load_cell(CELL)["config"]


def test_every_published_key_is_unchanged_but_the_reduced(config):
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "gqa_layers", "routed_experts_held", "vocab_size"}
    for key, value in CATALOG.items():
        if key not in reduced:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 4 and config["gqa_layers"] == [0]
    assert config["routed_experts_held"] == [0, 8] and config["n_routed_experts"] == 320
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] == 196608
    assert config["published"]["num_hidden_layers"] == 48
    entry = next(
        c for c in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == "solar-open2-250b"
    )
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert set(config["check"]) >= {"steps", "update_rtol", "weight_ulps", "loss_rtol", "why"}


@pytest.mark.parametrize(
    "off_by, ok",
    [
        (0.357, True),  # the largest sound reading on the chip (seed 173221021, a router)
        (1.0, False),  # a state left unchanged: the gap is the whole update
    ],
)
def test_the_cells_limit_stands_between_the_largest_reading_and_no_update(config, off_by, ok):
    """A router on the chip: one step moves it by 4.7 times its float32
    grid (check.why), so the grid's term is a quarter of what is allowed."""
    rng = np.random.default_rng(0)
    start = rng.standard_normal((64, 320)).astype(np.float32)
    step = rng.standard_normal(start.shape)
    grid = np.linalg.norm(np.spacing(np.abs(start)))
    step *= 4.7 * grid / np.linalg.norm(step)
    wrong = rng.standard_normal(start.shape)
    wrong *= off_by * np.linalg.norm(step) / np.linalg.norm(wrong)
    reference = start.astype(np.float64) + step
    report = check.compare(
        [{"W_r": reference + wrong}], [{"W_r": reference}], [{"W_r": start}], config["check"]
    )
    assert report["ok"] is ok, report["worst"]


def test_parameters_are_issue_35s(config):
    m = ref.model_config(config)
    assert m["layer_types"] == ["gqa", "kda", "kda", "kda"] and m["gate_rank"] == 128
    per = {kind: sum(i * o for i, o in products) for kind, products in ref._products(m).items()}
    ffn = 4096 * 320 + 3 * 4096 * 1280  # the router and the shared expert
    assert per["gqa"] - ffn == 3 * 4096 * 8192 + 2 * 4096 * 1024  # 109.1M
    # 137.9M less the 98,304 taps, A_log, dt_bias and the head norm
    assert per["kda"] - ffn == 4 * 4096 * 8192 + 4096 * 64 + 2 * (4096 * 128 + 128 * 8192)
    assert per["head"] == 24576 * 4096
    experts = 8 * 3 * 4096 * 1280  # 125.8M a layer
    total = per["gqa"] + 3 * per["kda"] + 4 * experts + 2 * per["head"]
    assert total == pytest.approx(1.296e9, rel=1e-3)


def test_train_flops_per_sample_by_hand(config):
    m, seq = ref.model_config(config), config["session"]["seq_len"]
    assert seq == 2048
    per = {kind: sum(i * o for i, o in products) for kind, products in ref._products(m).items()}
    weights = per["gqa"] + 3 * per["kda"] + per["head"]
    pairs = ref.expected_pairs_per_token(seq)
    rows = 8 * 8 / 320  # a token's slots on the experts held, a layer
    want = (
        6 * weights * seq
        + 4 * 6 * (3 * 4096 * 1280) * rows * seq  # four layers' held experts
        + 3 * (4 * 128 * 64) * pairs * seq  # attention: 2 products forward, 4 backward
        + 3 * 3 * (7 * 128 * 128 * 64) * seq  # three scans, recurrence form
    )
    assert ref.train_flops_per_sample(config) == pytest.approx(want)
    assert 8e12 < want < 10e12  # 8 rows a step: about 72 TFLOP
    assert 300 < pairs < (seq + 1) / 2
    assert ref.train_flops_per_sample(config, rows_per_token=8) > 1.5 * want  # the whole model


def test_kernel_costs_by_hand(config):
    m = ref.model_config(config)
    assert ref.scan_train_flops(m, 10) == 3 * 7 * 128 * 128 * 64 * 10
    inputs, o = 4 * 8192 + 64, 8192
    assert ref.scan_train_bytes(m, 10) == 4 * (3 * inputs + 3 * o) * 10
    assert ref.moe_train_flops(m, 100) == 6 * 3 * 4096 * 1280 * 100
    assert ref.moe_train_bytes(m, 100, 8) == 4 * (5 * 4096 * 100 + 9 * 4096 * 1280 * 8)
    assert ref.attention_train_flops(m, 1000) == 3 * 4 * 128 * 64 * 1000
    assert ref.expected_rows_per_token(m) == 0.2
    per_row = ref.matmul_bytes_per_sample(config, 1)
    assert ref.matmul_bytes_per_sample(config, 2) < per_row  # weights amortise over rows


def test_the_control_fails_the_rehearsals_tolerance():
    report = tolerance_probe_tokens.main(CELL, "bfloat16", seed=5, rehearse=True)
    assert not report["ok"]
    assert report["worst"] > 1.5, report["worst"]


def test_the_stated_policy_passes_against_itself():
    report = tolerance_probe_tokens.main(CELL, "highest", seed=5, rehearse=True)
    assert report["ok"] and report["worst"] == 0.0
