"""End-to-end CLI smoke tests: run train.py as a subprocess on tiny data.

The reference has no driver-level tests at all; these execute the actual
user-facing command (sequential and a DP x PP mesh layout) against a small
synthetic dataset and assert on the printed contract: per-epoch accuracy
lines, mean-train-loss lines, the replica-sync confirmation and the final
model hash.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def tiny_data(tmp_path_factory):
    d = tmp_path_factory.mktemp("tiny_mnist")
    rng = np.random.RandomState(0)
    for suffix, n in (("train", 256), ("val", 96)):
        np.save(d / f"x_{suffix}.npy", rng.rand(n, 784).astype(np.float32))
        np.save(
            d / f"y_{suffix}.npy",
            np.eye(10, dtype=np.float32)[rng.randint(0, 10, n)],
        )
    return d


def _run_raw(args, data_dir, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, str(ROOT / "train.py"), "--data-dir", str(data_dir), *args],
        capture_output=True,
        text=True,
        timeout=540,
        cwd=ROOT,
        env=env,
    )


def _run(args, data_dir, extra_env=None):
    r = _run_raw(args, data_dir, extra_env=extra_env)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


def test_sequential_cli(tiny_data):
    out = _run(
        ["--epochs", "2", "--global-batch-size", "32", "--mubatches", "2"], tiny_data
    )
    assert out.count("mean train loss") == 2
    assert "Accuracy:" in out
    assert re.search(r"final model hash: [0-9a-f]{40}", out)
    assert "(sequential)" in out


def test_sequential_cli_fused(tiny_data):
    out = _run(
        ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--fuse-mubatches"],
        tiny_data,
    )
    assert re.search(r"final model hash: [0-9a-f]{40}", out)


def test_sequential_cli_epoch_kernel_matches_fused(tiny_data):
    """--epoch-kernel (whole epoch as one Pallas kernel) trains to the same
    model hash as the fused XLA path through the real CLI."""
    hashes = {}
    for extra in ([], ["--epoch-kernel"]):
        out = _run(
            ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
             "--no-eval", "--fuse-mubatches", *extra],
            tiny_data,
        )
        hashes[bool(extra)] = re.search(
            r"final model hash: ([0-9a-f]{40})", out
        ).group(1)
    assert hashes[False] == hashes[True]


def test_mesh_cli_dp2_pp2(tiny_data):
    out = _run(
        [
            "--dp", "2", "--pp", "2", "--schedule", "pipedream",
            "--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
            "--no-eval",
        ],
        tiny_data,
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    )
    assert "(pipedream pipeline)" in out
    assert "DP replicas in sync" in out
    assert re.search(r"final model hash: [0-9a-f]{40}", out)


def test_mesh_cli_backward_split_matches_unsplit(tiny_data):
    """--backward-split through the real CLI (with --audit enforcing the
    split program's collective contract): the final model hash must equal
    the unsplit run's — the deferred B-weights change tick packing, never
    the numerics."""
    common = [
        "--pp", "4", "--schedule", "pipedream", "--epochs", "1",
        "--global-batch-size", "32", "--mubatches", "2", "--no-eval",
    ]
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    unsplit = _run(common, tiny_data, extra_env=env)
    split = _run(common + ["--backward-split", "--audit"], tiny_data, extra_env=env)
    h = re.compile(r"final model hash: ([0-9a-f]{40})")
    assert h.search(unsplit).group(1) == h.search(split).group(1)


def test_mesh_cli_interleaved_zero1_momentum(tiny_data):
    """The round-2 flag surface in one run: interleaved virtual stages,
    ZeRO-1 sharded momentum."""
    out = _run(
        [
            "--dp", "2", "--pp", "2", "--schedule", "interleaved",
            "--virtual-stages", "2", "--zero1", "--optimizer", "momentum",
            "--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
            "--no-eval",
        ],
        tiny_data,
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    )
    assert "(interleaved pipeline, V=2)" in out
    assert "DP replicas in sync" in out
    assert re.search(r"final model hash: [0-9a-f]{40}", out)


@pytest.mark.slow
def test_mesh_cli_zero23_hash_pin(tiny_data):
    """The ZeRO lattice's CLI surface: --zero 2 and --zero 1 at
    --mubatches 1 print the SAME final model hash (the fixed-layout
    bitwise pin — one scatter contribution per shard element), and
    --zero 3 trains, evals and syncs on the same layout. (Slow tier:
    `make zero-smoke` runs the identical CLI pin end-to-end, and the
    session/executor pins cover it in tier-1.)"""
    common = [
        "--dp", "2", "--pp", "2", "--optimizer", "momentum",
        "--epochs", "1", "--global-batch-size", "32", "--mubatches", "1",
    ]
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    hashes = {}
    for stage in ("1", "2"):
        out = _run(common + ["--zero", stage, "--no-eval"], tiny_data,
                   extra_env=env)
        hashes[stage] = re.search(r"final model hash: ([0-9a-f]{40})", out).group(1)
    assert hashes["1"] == hashes["2"]
    out = _run(common + ["--zero", "3"], tiny_data, extra_env=env)
    assert "DP replicas in sync" in out
    assert re.search(r"final model hash: [0-9a-f]{40}", out)


CLI_REFUSALS = {
    # id -> (flags, the refusal's own words on stderr[, environment])
    "ckpt-every-steps-negative": (
        ["--checkpoint-every-steps", "-1"],
        "--checkpoint-every-steps must be >= 0",
    ),
    "ckpt-every-steps-no-dir": (
        ["--checkpoint-every-steps", "2"],
        "--checkpoint-every-steps needs --checkpoint-dir",
    ),
    "ckpt-every-steps-fused-run": (
        ["--fused-run", "--checkpoint-every-steps", "2",
         "--checkpoint-dir", "/nonexistent/ck"],
        "--checkpoint-every-steps is incompatible with --fused-run",
    ),
    "resume-auto-no-dir": (
        ["--resume", "auto"],
        "--resume auto discovers snapshots in --checkpoint-dir",
    ),
    "async-checkpoint-no-dir": (
        ["--async-checkpoint"], "--async-checkpoint needs --checkpoint-dir",
    ),
    "resume-auto-fused-run": (
        ["--fused-run", "--resume", "auto",
         "--checkpoint-dir", "/nonexistent/ck"],
        "the fused run has no mid-epoch entry point",
    ),
    "keep-below-one": (
        ["--keep", "0", "--checkpoint-dir", "/nonexistent/ck"],
        "--keep must be >= 1",
    ),
    "mpmd-fused-run": (
        ["--runtime", "mpmd", "--pp", "2", "--fused-run"],
        "the fused ONE-dispatch run is a lockstep contract",
    ),
    "digests-fused-run": (
        ["--digests", "--fused-run"],
        "--digests rides the epoch/step scan aux",
    ),
    "mpmd-no-mesh": (
        ["--runtime", "mpmd"], "--runtime mpmd needs a mesh layout",
    ),
    "recompute-no-mesh": (
        ["--recompute"], "--recompute drops pipeline activation stashes",
    ),
    "recompute-virtual-stages": (
        ["--recompute", "--pp", "2", "--schedule", "interleaved",
         "--virtual-stages", "2"],
        "--recompute is not supported with interleaved virtual stages",
    ),
    "zero1-against-zero": (
        ["--zero1", "--zero", "2"], "conflicting dp-stage selectors",
    ),
    "zero3-fused-run": (
        ["--zero", "3", "--dp", "2", "--fused-run"],
        "--zero 3 is incompatible with --fused-run",
    ),
    "zero3-pallas": (
        ["--zero", "3", "--dp", "2", "--kernel-backend", "pallas"],
        "--zero 3 is incompatible with --kernel-backend pallas",
    ),
    "zero-mpmd": (
        ["--zero", "2", "--dp", "2", "--pp", "2", "--runtime", "mpmd"],
        "--runtime mpmd does not support --zero 2",
    ),
    "zero2-digests": (
        ["--zero", "2", "--dp", "2", "--digests"],
        "--digests is incompatible with --zero 2",
    ),
    # an active env fault plan needs the step loop — silently completing
    # the uninjected fused run would fake a survived crash
    "faults-env-fused-run": (
        ["--fused-run", "--epochs", "1", "--no-eval"],
        "the fault harness needs the step loop",
        {"SHALLOWSPEED_FAULTS": "die@step=3:mode=sigkill"},
    ),
}


@pytest.mark.parametrize("case", sorted(CLI_REFUSALS))
def test_cli_refusals_exit_2(case):
    """Every argparse-time refusal train.py has, by name: exit code 2 and
    the refusal's own words on stderr, before any data or backend is
    touched — the data directory does not exist, and the platform named
    would fail the first backend call with another exit code."""
    flags, words, *env = CLI_REFUSALS[case]
    r = _run_raw(
        flags, "/nonexistent/data",
        extra_env={"JAX_PLATFORMS": "no_such_platform", **(env[0] if env else {})},
    )
    assert r.returncode == 2, (flags, r.stderr[-500:])
    # argparse wraps its message: compare on single-spaced text
    assert words in " ".join(r.stderr.split()), (flags, r.stderr[-500:])
    assert "Traceback" not in r.stderr


def test_mesh_cli_kernel_backend_pallas_matches_xla(tiny_data):
    """The executor's Pallas backend is a product feature, not a test-only
    artifact: the CLI flag must train bit-identically to the default XLA
    backend (interpreter mode off-TPU — same contract as on hardware)."""
    hashes = {}
    for kb in ("xla", "pallas"):
        out = _run(
            [
                "--dp", "2", "--pp", "2", "--schedule", "gpipe",
                "--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
                "--no-eval", "--kernel-backend", kb,
            ],
            tiny_data,
            extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        )
        hashes[kb] = re.search(r"final model hash: ([0-9a-f]{40})", out).group(1)
    assert hashes["xla"] == hashes["pallas"]


def test_cli_clip_and_decay_flags(tiny_data):
    out = _run(
        ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--clip-norm", "0.5", "--weight-decay", "0.01",
         "--optimizer", "momentum", "--lr", "0.001"],
        tiny_data,
    )
    assert re.search(r"final model hash: [0-9a-f]{40}", out)


def test_cli_checkpoint_resume_round_trip(tiny_data, tmp_path):
    ck = tmp_path / "ck.npz"
    _run(
        ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--checkpoint", str(ck)],
        tiny_data,
    )
    assert ck.exists()
    out = _run(
        ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--resume", str(ck)],
        tiny_data,
    )
    assert "resumed at epoch 1" in out


def test_fused_run_cli_matches_loop(tiny_data):
    """--fused-run (all epochs + eval in one device program) prints the SAME
    per-epoch contract as the epoch loop — same epoch-labeled accuracy
    sequence (pre-epoch semantics), same losses, same final hash."""
    common = ["--epochs", "2", "--global-batch-size", "32", "--mubatches", "2"]
    fused = _run(common + ["--fused-run"], tiny_data)
    loop = _run(common, tiny_data)

    def contract(out):
        # fused mode omits the per-line cumulative clock (all its lines print
        # after the one dispatch) — epoch labels and values must still agree
        accs = re.findall(
            r"Epoch: (\d+),(?: Time Spent: [\d.]+s,)? Accuracy: ([\d.]+)%", out
        )
        losses = re.findall(r"Epoch: (\d+), mean train loss: ([\d.]+)", out)
        h = re.search(r"final model hash: ([0-9a-f]{40})", out).group(1)
        return accs, losses, h

    f_accs, f_losses, f_hash = contract(fused)
    l_accs, l_losses, l_hash = contract(loop)
    assert f_losses == l_losses and len(f_losses) == 2
    assert f_accs == l_accs and len(f_accs) == 3  # pre-run, between, final
    assert f_hash == l_hash


def test_fused_run_cli_no_eval(tiny_data):
    """--fused-run honors --no-eval: losses printed, no accuracy lines except
    the final summary."""
    out = _run(
        ["--epochs", "2", "--global-batch-size", "32", "--mubatches", "2",
         "--fused-run", "--no-eval"],
        tiny_data,
    )
    assert out.count("mean train loss") == 2
    assert out.count("Accuracy:") == 1  # the final summary only


def test_sequential_cli_run_kernel_matches_fused(tiny_data):
    """--run-kernel --fused-run --no-eval (the ENTIRE 2-epoch run as one
    Pallas kernel) trains to the same model hash and prints the same
    per-epoch losses as the fused XLA run through the real CLI."""
    import re as _re

    outs = {}
    for extra in ([], ["--run-kernel"]):
        outs[bool(extra)] = _run(
            ["--epochs", "2", "--global-batch-size", "32", "--mubatches", "2",
             "--no-eval", "--fuse-mubatches", "--fused-run", *extra],
            tiny_data,
        )
    for key in (r"final model hash: ([0-9a-f]{40})",):
        a = _re.search(key, outs[False]).group(1)
        b = _re.search(key, outs[True]).group(1)
        assert a == b
    losses = {
        k: _re.findall(r"mean train loss: ([0-9.]+)", v) for k, v in outs.items()
    }
    assert losses[False] == losses[True] and len(losses[True]) == 2


# ---------------------------------------------------------------------------
# fault-tolerance CLI contracts (docs/robustness.md)
# ---------------------------------------------------------------------------


def test_fused_run_checkpoint_contract(tiny_data, tmp_path):
    """The pinned --checkpoint x --fused-run contract: the fused run is ONE
    dispatch, so --checkpoint saves exactly once, after it returns (the
    STEP-checkpoint flags, which need a host step boundary, are refused at
    argparse time: ``test_cli_refusals_exit_2``)."""
    ck = tmp_path / "fused.npz"
    out = _run(
        ["--epochs", "2", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--fused-run", "--checkpoint", str(ck)],
        tiny_data,
    )
    assert ck.exists()
    from shallowspeed_tpu.checkpoint import verify_checkpoint

    # one snapshot, of the post-run state: epoch = last COMPLETED epoch
    assert verify_checkpoint(ck)["epoch"] == 1
    assert re.search(r"final model hash: [0-9a-f]{40}", out)


def test_fused_run_rejects_explicit_mid_epoch_resume(tiny_data, tmp_path):
    """--resume <path> escapes the argparse-time net (the snapshot's cursor
    is only known after reading it): restoring a MID-EPOCH snapshot under
    --fused-run must exit 2 with the same clean contract message as the
    argparse checks — not a raw mid-flight traceback out of the fused
    dispatch (which drivers would misread as an infrastructure crash)."""
    ck_dir = tmp_path / "ck"
    r = _run_raw(
        ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--checkpoint-dir", str(ck_dir),
         "--checkpoint-every-steps", "2"],
        tiny_data,
        extra_env={"SHALLOWSPEED_FAULTS": "die@step=3"},
    )
    assert r.returncode != 0  # the injected death left a mid-epoch snapshot
    snap = ck_dir / "step-00000002.npz"
    assert snap.exists()
    r = _run_raw(
        ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--fused-run", "--resume", str(snap)],
        tiny_data,
    )
    assert r.returncode == 2, (r.stdout, r.stderr[-2000:])
    assert "no mid-epoch entry point" in r.stderr
    assert "Traceback" not in r.stderr


def test_exit_code_3_on_health_halt(tiny_data):
    """The exit-code contract (README): a numerics halt exits 3 — here a
    NaN injected into the params at step 2 via the env-var fault harness,
    caught by --health halt, after flushing the finding to telemetry."""
    r = _run_raw(
        ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--health", "halt"],
        tiny_data,
        extra_env={"SHALLOWSPEED_FAULTS": "nan@step=2"},
    )
    assert r.returncode == 3, (r.stdout, r.stderr[-2000:])
    assert "HEALTH HALT" in r.stderr


def test_exit_code_4_on_unrecoverable_checkpoint_state(tiny_data, tmp_path):
    """The exit-code contract (README): --resume auto over a directory
    where NO snapshot verifies exits 4 (unrecoverable checkpoint state),
    naming every candidate and its failure cause."""
    ck_dir = tmp_path / "ck"
    ck_dir.mkdir()
    (ck_dir / "step-00000004.npz").write_bytes(b"not a zip archive")
    r = _run_raw(
        ["--epochs", "1", "--global-batch-size", "32", "--mubatches", "2",
         "--no-eval", "--resume", "auto", "--checkpoint-dir", str(ck_dir)],
        tiny_data,
    )
    assert r.returncode == 4, (r.stdout, r.stderr[-2000:])
    assert "CHECKPOINT UNRECOVERABLE" in r.stderr
    assert "step-00000004.npz" in r.stderr


@pytest.mark.slow
def test_sigkill_and_resume_auto_round_trip(tiny_data, tmp_path):
    """The real preemption shape through the real CLI (the in-suite twin of
    `make recovery-smoke`): SIGKILL a checkpointing run at an injected
    step — nothing flushes — then `--resume auto` finishes on exactly the
    uninterrupted twin's final hash."""
    common = ["--epochs", "2", "--global-batch-size", "32", "--mubatches",
              "2", "--no-eval"]
    twin = _run(common, tiny_data)
    ck_dir = tmp_path / "ck"
    r = _run_raw(
        common + ["--checkpoint-dir", str(ck_dir),
                  "--checkpoint-every-steps", "4"],
        tiny_data,
        extra_env={"SHALLOWSPEED_FAULTS": "die@step=11:mode=sigkill"},
    )
    assert r.returncode == -9  # killed, not exited
    assert (ck_dir / "step-00000008.npz").exists()
    out = _run(
        common + ["--checkpoint-dir", str(ck_dir),
                  "--checkpoint-every-steps", "4", "--resume", "auto"],
        tiny_data,
    )
    assert "resumed at epoch 1" in out
    want = re.search(r"final model hash: ([0-9a-f]{40})", twin).group(1)
    got = re.search(r"final model hash: ([0-9a-f]{40})", out).group(1)
    assert got == want


def test_resume_auto_epoch_boundary_honors_total_epochs(tiny_data, tmp_path):
    """--resume auto's TOTAL-epochs contract holds even when the restored
    cursor sits ON an epoch boundary and no step grid is active on the
    resuming run: 1 epoch trained + resume --epochs 2 == exactly one more
    epoch, bitwise equal to the uninterrupted 2-epoch twin."""
    common = ["--global-batch-size", "32", "--mubatches", "2", "--no-eval"]
    twin = _run(common + ["--epochs", "2"], tiny_data)
    ck_dir = tmp_path / "ck"
    _run(
        common + ["--epochs", "1", "--checkpoint-dir", str(ck_dir),
                  "--checkpoint-every-steps", "8"],
        tiny_data,
    )
    assert (ck_dir / "step-00000008.npz").exists()  # the epoch boundary
    out = _run(
        common + ["--epochs", "2", "--checkpoint-dir", str(ck_dir),
                  "--resume", "auto"],
        tiny_data,
    )
    assert "resumed at epoch 1" in out
    assert out.count("mean train loss") == 1  # ONE more epoch, not two
    want = re.search(r"final model hash: ([0-9a-f]{40})", twin).group(1)
    got = re.search(r"final model hash: ([0-9a-f]{40})", out).group(1)
    assert got == want
