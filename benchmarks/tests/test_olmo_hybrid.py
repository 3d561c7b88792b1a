"""The token configuration's reference module: its counts and cost functions
against arithmetic done by hand, and its control (one precision step below
the stated policy) failing the rehearsal's tolerance."""

import json
from pathlib import Path

import numpy as np
import pytest

import cells
import tolerance_probe_tokens

BENCH = Path(__file__).resolve().parents[1]
CELL = "olmo-hybrid-7b.seq-s8192-b2"
ref = cells.load_module(BENCH / "references" / "olmo_hybrid.py")


@pytest.fixture(scope="module")
def config():
    return cells.load_cell(CELL)["config"]


def test_the_cut_is_what_the_file_says(config):
    m = ref.model_config(config)
    assert (m["hidden_size"], m["intermediate_size"], m["num_attention_heads"]) == (3840, 11008, 30)
    assert m["num_hidden_layers"] == len(m["layer_types"]) == 4
    assert m["layer_types"].count("linear_attention") == 3
    assert m["vocab_size"] * 8 == config["published"]["vocab_size"] == 100352
    assert config["published"]["num_hidden_layers"] == 32
    assert sorted(config["reduced"]) == ["layer_types", "num_hidden_layers", "vocab_size"]
    entry = next(
        c for c in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == "olmo-hybrid-7b"
    )
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]


def test_parameters_per_layer_are_issue_32s(config):
    per = ref._linear_params(ref.model_config(config))
    mlp = 3 * 3840 * 11008
    assert per["full_attention"] == 4 * 3840**2 + mlp  # 185.8M
    # 88.75M less the 46,080 convolution taps, which are no matrix product
    assert per["linear_attention"] - mlp == 88_750_080 - 46_080
    assert per["head"] == 12544 * 3840


def test_train_flops_per_sample_by_hand(config):
    m, seq = ref.model_config(config), 8192
    per = ref._linear_params(m)
    weights = 3 * per["linear_attention"] + per["full_attention"] + per["head"]
    pairs = ref.expected_pairs_per_token(seq)
    want = (
        6 * weights * seq
        + 3 * (4 * 128 * 30) * pairs * seq  # attention: 2 products forward, 4 backward
        + 3 * 3 * (9 * 96 * 192 * 30) * seq  # three scans, recurrence form
    )
    assert ref.train_flops_per_sample(config) == pytest.approx(want)
    assert 40e12 < want < 46e12
    # the pairs come from the traffic: well under the causal mask's half a row
    assert 1500 < pairs < 3000 < (seq + 1) / 2
    assert ref.train_flops_per_sample(config, pairs_per_token=0) < want


def test_kernel_costs_by_hand(config):
    m = ref.model_config(config)
    assert ref.attention_flops_per_pair(m) == 4 * 128 * 30
    assert ref.attention_train_flops(m, 1000) == 3 * 4 * 128 * 30 * 1000
    assert ref.attention_train_bytes(m, 10) == 4 * 12 * 3840 * 10
    assert ref.scan_train_flops(m, 10) == 3 * 9 * 96 * 192 * 30 * 10
    q_k_v_b_a, o = 2 * 2880 + 5760 + 60, 5760
    assert ref.scan_train_bytes(m, 10) == 4 * (3 * q_k_v_b_a + 3 * o) * 10
    rows = 1
    per_row = ref.matmul_bytes_per_sample(config, rows)
    assert per_row > 4 * 3 * 8192 * 3840 * 4  # at least the activations of one layer's products
    assert ref.matmul_bytes_per_sample(config, 2) < per_row  # weights amortise over rows


def test_packed_counts_by_hand():
    seg = np.array([[0, 0, 0, 1, 1, 7], [0, 1, 2, 2, 2, 7]])  # the last column is no input
    assert ref.packed_counts(seg) == {
        "tokens": 10, "documents": 5, "pairs": (1 + 2 + 3 + 1 + 2) + (1 + 1 + 1 + 2 + 3),
    }


def test_the_control_fails_the_rehearsals_tolerance():
    report = tolerance_probe_tokens.main(CELL, "bfloat16", seed=5, rehearse=True)
    assert not report["ok"]
    assert report["worst"] > 1.5, report["worst"]


def test_the_stated_policy_passes_against_itself():
    report = tolerance_probe_tokens.main(CELL, "default", seed=5, rehearse=True)
    assert report["ok"] and report["worst"] == 0.0
