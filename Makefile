# Convenience targets. The CPU_MESH prefix runs any layout on 8 emulated
# host devices.
CPU_MESH = env JAX_PLATFORMS=cpu \
           XLA_FLAGS=--xla_force_host_platform_device_count=8

# verify needs bash (pipefail / PIPESTATUS)
SHELL := /bin/bash

.PHONY: test verify lint analyze-smoke metrics-smoke report-smoke \
        audit-smoke split-smoke tp-smoke recovery-smoke \
        diverge-smoke \
        serve-smoke chaos-smoke alerts-smoke fleet-smoke trace-smoke \
        mpmd-smoke bench-mpmd replay-smoke recompute-smoke \
        zero-smoke bench-zero \
        bench-serving bench-ckpt-aot data train train-mesh bench \
        bench-scaling schedules clean

test:
	python -m pytest tests/ -q

# the ROADMAP tier-1 command, verbatim — the gate every PR must keep green
verify:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# the house-rule linter (shallowspeed_tpu/analysis/lint.py,
# docs/static-analysis.md): repo-wide AST rules — justified broad
# excepts, strict-JSON metrics writes, the one-atomic-write discipline,
# the donation whitelist, the metrics schema-kind registry, lock
# discipline. Exit 0 clean / 2 with file:line findings; --format json
# is the stable machine-readable mode. Also run inside tier-1
# (tests/test_lint.py::test_repo_is_lint_clean).
lint:
	python -m shallowspeed_tpu.analysis.lint

# static program analysis end-to-end (docs/static-analysis.md): every
# training layout (seq, dp2, gpipe-pp4, zero1-dp2xpp2) compiled with
# --audit + one serving rung — the lowering-time passes (send/recv
# match, MPMD deadlock-freedom, stash lifetime) and the HLO donation
# dispatch-safety pass all green BEFORE first dispatch, the report CLI
# renders the Static checks row — then one deliberately-broken program
# per check class (unmatched send, leaked stash, cyclic wait, donating
# executable) each asserted REFUSED naming the offending tick/evidence
analyze-smoke:
	rm -rf /tmp/asmoke; mkdir -p /tmp/asmoke
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/asmoke/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	$(CPU_MESH) python scripts/analyze_smoke.py --phase clean \
	    --data-dir /tmp/asmoke/data --out-dir /tmp/asmoke
	$(CPU_MESH) python scripts/analyze_smoke.py --phase violate
	python -m shallowspeed_tpu.observability.report /tmp/asmoke/pp4.jsonl \
	    --format md > /tmp/asmoke/pp4.report.md
	grep -q "static checks" /tmp/asmoke/pp4.report.md
	@echo "analyze-smoke OK: four layouts + the serving rung ladder statically clean before dispatch, all injected violations refused, Static checks row rendered"

# telemetry end-to-end smoke: 1 CPU epoch with --metrics-out, then assert
# the file is non-empty valid JSONL with a per-epoch record (needs data:
# `make data` first, or point SHALLOWSPEED_DATA_DIR at a prepared dir)
metrics-smoke:
	rm -f /tmp/metrics.jsonl
	$(CPU_MESH) python train.py --epochs 1 --no-eval --metrics-out /tmp/metrics.jsonl
	python -c "import json; lines = [json.loads(l) for l in open('/tmp/metrics.jsonl') if l.strip()]; assert lines, 'metrics file is empty'; assert any(r.get('kind') == 'event' and r.get('name') == 'epoch' for r in lines), 'no per-epoch record'; print(f'metrics-smoke OK: {len(lines)} valid JSONL records')"

# run-report end-to-end smoke: 1 CPU epoch with telemetry + health
# recording, then render the run report (throughput, MFU, span breakdown,
# step-loss sparkline, health verdict) — a nonzero report exit fails the
# target, which is the CI gate contract (needs data, like metrics-smoke)
report-smoke:
	rm -f /tmp/report_smoke.jsonl
	$(CPU_MESH) python train.py --epochs 1 --no-eval --health record \
	    --metrics-out /tmp/report_smoke.jsonl
	python -m shallowspeed_tpu.observability.report /tmp/report_smoke.jsonl \
	    --format md

# XLA program audit end-to-end: 1 CPU epoch per layout (sequential, DP,
# gpipe pipeline, ZeRO-1) with --audit — train.py itself raises (nonzero
# exit) if the compiled collective census violates the layout contract —
# then assert the schema-v3 xla_audit record landed census-clean and the
# report CLI renders the Memory + Comms sections with exit 0 (needs data,
# like metrics-smoke)
audit-smoke:
	rm -f /tmp/audit_seq.jsonl /tmp/audit_dp.jsonl /tmp/audit_pp.jsonl \
	    /tmp/audit_z1.jsonl
	$(CPU_MESH) python train.py --epochs 1 --no-eval --audit \
	    --metrics-out /tmp/audit_seq.jsonl
	$(CPU_MESH) python train.py --epochs 1 --no-eval --audit --dp 2 \
	    --metrics-out /tmp/audit_dp.jsonl
	$(CPU_MESH) python train.py --epochs 1 --no-eval --audit --pp 4 \
	    --schedule gpipe --metrics-out /tmp/audit_pp.jsonl
	$(CPU_MESH) python train.py --epochs 1 --no-eval --audit --dp 2 --pp 2 \
	    --schedule gpipe --zero1 --metrics-out /tmp/audit_z1.jsonl
	set -e; for f in /tmp/audit_seq /tmp/audit_dp /tmp/audit_pp /tmp/audit_z1; do \
	  python -c "import json,sys; p=sys.argv[1]; recs=[json.loads(l) for l in open(p) if l.strip()]; a=[r for r in recs if r.get('kind')=='xla_audit']; assert a, p+': no xla_audit record'; assert all(r.get('census_ok') for r in a), p+': census mismatch'; print(p+': collective census matches the layout contract')" $$f.jsonl; \
	  python -m shallowspeed_tpu.observability.report $$f.jsonl --format md > $$f.report.md; \
	  grep -q "Memory (compiled program)" $$f.report.md; \
	  grep -q "Comms (XLA program audit)" $$f.report.md; \
	done
	@echo "audit-smoke OK: census + memory + comms sections on all 4 layouts"

# split-backward end-to-end: 1 CPU epoch each for pp4 gpipe and pp4
# pipedream with --backward-split --audit (train.py aborts nonzero if the
# split program's collective census violates the layout contract), plus an
# UNSPLIT twin of each — then assert the xla_audit census is clean, the
# pipeline_program record is backward_split with a weighted bubble strictly
# below the unsplit twin's, the report renders the weighted-bubble row, and
# the final model hash EQUALS the unsplit run's (the bitwise-parity
# contract), exit 0 (needs data, like metrics-smoke)
split-smoke:
	rm -f /tmp/split_gpipe.jsonl /tmp/split_pd.jsonl \
	    /tmp/split_gpipe_ref.jsonl /tmp/split_pd_ref.jsonl \
	    /tmp/split_gpipe.out /tmp/split_gpipe_ref.out \
	    /tmp/split_pd.out /tmp/split_pd_ref.out
	set -o pipefail; $(CPU_MESH) python train.py --epochs 1 --no-eval \
	    --audit --pp 4 --schedule gpipe --backward-split \
	    --metrics-out /tmp/split_gpipe.jsonl | tee /tmp/split_gpipe.out
	set -o pipefail; $(CPU_MESH) python train.py --epochs 1 --no-eval \
	    --pp 4 --schedule gpipe \
	    --metrics-out /tmp/split_gpipe_ref.jsonl | tee /tmp/split_gpipe_ref.out
	set -o pipefail; $(CPU_MESH) python train.py --epochs 1 --no-eval \
	    --audit --pp 4 --schedule pipedream --backward-split \
	    --metrics-out /tmp/split_pd.jsonl | tee /tmp/split_pd.out
	set -o pipefail; $(CPU_MESH) python train.py --epochs 1 --no-eval \
	    --pp 4 --schedule pipedream \
	    --metrics-out /tmp/split_pd_ref.jsonl | tee /tmp/split_pd_ref.out
	set -e; for f in /tmp/split_gpipe /tmp/split_pd; do \
	  split_h=$$(grep -o 'final model hash: [0-9a-f]*' $$f.out); \
	  ref_h=$$(grep -o 'final model hash: [0-9a-f]*' $${f}_ref.out); \
	  test -n "$$split_h" && test "$$split_h" = "$$ref_h" \
	    || { echo "$$f: HASH MISMATCH split [$$split_h] vs unsplit [$$ref_h]"; exit 1; }; \
	  echo "$$f: split hash == unsplit hash"; \
	  python -c "import json,sys; p=sys.argv[1]; recs=[json.loads(l) for l in open(p+'.jsonl') if l.strip()]; a=[r for r in recs if r.get('kind')=='xla_audit']; assert a, p+': no xla_audit record'; assert all(r.get('census_ok') for r in a), p+': census mismatch'; prog=[r for r in recs if r.get('kind')=='event' and r.get('name')=='pipeline_program'][-1]; assert prog['backward_split'], p+': program not split'; ref=[json.loads(l) for l in open(p+'_ref.jsonl') if l.strip()]; rprog=[r for r in ref if r.get('kind')=='event' and r.get('name')=='pipeline_program'][-1]; assert not rprog['backward_split']; assert prog['weighted_bubble_fraction'] < rprog['weighted_bubble_fraction'], p+': weighted bubble did not shrink (%.3f vs unsplit %.3f)' % (prog['weighted_bubble_fraction'], rprog['weighted_bubble_fraction']); print(p+': split census clean, weighted bubble %.1f%% < unsplit %.1f%%' % (100*prog['weighted_bubble_fraction'], 100*rprog['weighted_bubble_fraction']))" $$f; \
	  python -m shallowspeed_tpu.observability.report $$f.jsonl --format md > $$f.report.md; \
	  grep -q "weighted bubble" $$f.report.md; \
	done
	@echo "split-smoke OK: bitwise hash parity + clean census + weighted-bubble row on gpipe and pipedream"

# tensor-parallelism end-to-end (docs/performance.md "--tp"): 1 CPU epoch
# each for tp2 and dp2 x tp2 with --audit — train.py aborts nonzero if the
# compiled census violates the per-axis contract (the tp axis demands the
# Megatron all-reduce floor) — then assert the census landed clean with a
# tp axis + a mesh_layout provenance event, the report renders the per-axis
# Comms breakdown (tp next to dp/pp), the tp2 loss equals the sequential
# reference's within the documented cross-layout float tolerance (the tp
# psums reassociate split contractions — same tolerance class as a dp-width
# change, so HASH equality is deliberately NOT claimed across tp), and the
# tp=1 anchor holds EXACTLY: --dp 2 --tp 1 hashes byte-identically to the
# historical --dp 2 program (needs data, like metrics-smoke)
tp-smoke:
	rm -f /tmp/tp_seq.jsonl /tmp/tp_tp2.jsonl /tmp/tp_dp2tp2.jsonl \
	    /tmp/tp_seq.out /tmp/tp_tp2.out /tmp/tp_anchor1.out /tmp/tp_anchor2.out
	set -o pipefail; $(CPU_MESH) python train.py --epochs 1 --no-eval \
	    --metrics-out /tmp/tp_seq.jsonl | tee /tmp/tp_seq.out
	set -o pipefail; $(CPU_MESH) python train.py --epochs 1 --no-eval \
	    --audit --tp 2 --metrics-out /tmp/tp_tp2.jsonl | tee /tmp/tp_tp2.out
	$(CPU_MESH) python train.py --epochs 1 --no-eval --audit --dp 2 --tp 2 \
	    --metrics-out /tmp/tp_dp2tp2.jsonl
	set -o pipefail; $(CPU_MESH) python train.py --epochs 1 --no-eval --dp 2 \
	    | tee /tmp/tp_anchor1.out
	set -o pipefail; $(CPU_MESH) python train.py --epochs 1 --no-eval --dp 2 \
	    --tp 1 | tee /tmp/tp_anchor2.out
	set -e; for f in /tmp/tp_tp2 /tmp/tp_dp2tp2; do \
	  python -c "import json,sys; p=sys.argv[1]; recs=[json.loads(l) for l in open(p) if l.strip()]; a=[r for r in recs if r.get('kind')=='xla_audit']; assert a, p+': no xla_audit record'; assert all(r.get('census_ok') for r in a), p+': census mismatch'; tp=[r['expected']['axes'].get('tp') for r in a if r.get('name')=='epoch_program'][-1]; assert tp and tp['hlo_min_all_reduce_ops']==tp['sites_fwd']+tp['sites_bwd']>0, p+': no tp axis in the contract'; ml=[r for r in recs if r.get('kind')=='event' and r.get('name')=='mesh_layout']; assert ml and ml[-1]['layout'] in ('topology-aware','order-preserving'), p+': no mesh_layout provenance'; print(p+': tp census clean (%d Megatron sites, %s placement)' % (tp['hlo_min_all_reduce_ops'], ml[-1]['layout']))" $$f.jsonl; \
	  python -m shallowspeed_tpu.observability.report $$f.jsonl --format md > $$f.report.md; \
	  grep -q "Comms (XLA program audit)" $$f.report.md; \
	  grep -q "tp all_reduce" $$f.report.md; \
	done
	python -c "import json,re,sys; loss=lambda p: [r for r in (json.loads(l) for l in open(p) if l.strip()) if r.get('kind')=='event' and r.get('name')=='epoch'][-1]['loss']; s, t = loss('/tmp/tp_seq.jsonl'), loss('/tmp/tp_tp2.jsonl'); rel=abs(s-t)/max(abs(s),1e-12); assert rel < 1e-3, 'tp2 loss %r vs sequential %r (rel %g)' % (t, s, rel); print('tp2 loss == sequential reference within float tolerance (rel %.2e)' % rel)"
	set -e; h1=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/tp_anchor1.out); \
	  h2=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/tp_anchor2.out); \
	  test -n "$$h1" && test "$$h1" = "$$h2" \
	    || { echo "tp=1 ANCHOR BROKEN: --dp 2 [$$h1] vs --dp 2 --tp 1 [$$h2]"; exit 1; }; \
	  echo "tp=1 anchor holds: --tp 1 hash == historical 2-axis hash"
	@echo "tp-smoke OK: census-clean tp2 + dp2xtp2 with per-axis Comms, sequential-reference loss parity, tp=1 byte-anchor"

# fault-tolerant recovery end-to-end (docs/robustness.md): on a dp2 and a
# gpipe-pp4 layout, run an uninterrupted twin, then KILL a checkpointing run
# with a SIGKILL injected at step 11 via the fault harness
# (SHALLOWSPEED_FAULTS), resume it with --resume auto, and assert the final
# weight hash is BITWISE identical to the twin's. Then concatenate the
# killed + resumed telemetry and assert the report CLI renders the
# Reliability section with the recovery verdict and the measured
# steps-lost-to-replay (11 trained - resume@8 = 3), exit 0. Uses a tiny
# synthetic dataset (8 batches/epoch) so the whole smoke is CPU-fast.
recovery-smoke:
	rm -rf /tmp/rsmoke; mkdir -p /tmp/rsmoke
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/rsmoke/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	set -e; for lay in dp2 pp4; do \
	  if [ $$lay = dp2 ]; then LFLAGS="--dp 2 --mubatches 2"; \
	  else LFLAGS="--pp 4 --schedule gpipe --mubatches 4"; fi; \
	  COMMON="--data-dir /tmp/rsmoke/data --epochs 2 --global-batch-size 32 --no-eval"; \
	  $(CPU_MESH) python train.py $$COMMON $$LFLAGS \
	      > /tmp/rsmoke/$$lay.twin.out; \
	  $(CPU_MESH) env SHALLOWSPEED_FAULTS="die@step=11:mode=sigkill" \
	      python train.py $$COMMON $$LFLAGS \
	      --checkpoint-dir /tmp/rsmoke/ck_$$lay --checkpoint-every-steps 4 \
	      --metrics-out /tmp/rsmoke/$$lay.killed.jsonl \
	      > /tmp/rsmoke/$$lay.killed.out 2>&1 && \
	      { echo "$$lay: injected SIGKILL did not fire"; exit 1; } || true; \
	  test -f /tmp/rsmoke/ck_$$lay/step-00000008.npz \
	      || { echo "$$lay: no step-8 checkpoint survived the kill"; exit 1; }; \
	  $(CPU_MESH) python train.py $$COMMON $$LFLAGS \
	      --checkpoint-dir /tmp/rsmoke/ck_$$lay --checkpoint-every-steps 4 \
	      --resume auto --metrics-out /tmp/rsmoke/$$lay.resumed.jsonl \
	      > /tmp/rsmoke/$$lay.resumed.out; \
	  grep -q "resumed at epoch" /tmp/rsmoke/$$lay.resumed.out \
	      || { echo "$$lay: resume auto did not restore"; exit 1; }; \
	  twin_h=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/rsmoke/$$lay.twin.out); \
	  res_h=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/rsmoke/$$lay.resumed.out); \
	  test -n "$$twin_h" && test "$$twin_h" = "$$res_h" \
	      || { echo "$$lay: HASH MISMATCH resumed [$$res_h] vs twin [$$twin_h]"; exit 1; }; \
	  echo "$$lay: killed-and-resumed hash == uninterrupted twin hash"; \
	  cat /tmp/rsmoke/$$lay.killed.jsonl /tmp/rsmoke/$$lay.resumed.jsonl \
	      > /tmp/rsmoke/$$lay.combined.jsonl; \
	  python -m shallowspeed_tpu.observability.report \
	      /tmp/rsmoke/$$lay.combined.jsonl --format md \
	      > /tmp/rsmoke/$$lay.report.md; \
	  grep -q "## Reliability" /tmp/rsmoke/$$lay.report.md; \
	  grep -q "recovery: resumed from" /tmp/rsmoke/$$lay.report.md; \
	  grep -q "steps lost to replay: 3" /tmp/rsmoke/$$lay.report.md; \
	done
	@# the ASYNC leg (one layout keeps the smoke bounded; the in-suite
	@# fuzz lattice covers dp2/pp4/tp2): SIGKILL injected INSIDE the
	@# background writer's write/verify/rename window (die@save=2 fires
	@# after the temp file is durable, before the rename) — discovery
	@# must see only fully-verifying snapshots, resume must finish on
	@# the twin's exact bits, and the report must show the async saves
	set -e; \
	  $(CPU_MESH) env SHALLOWSPEED_FAULTS="die@save=2:mode=sigkill" \
	      python train.py --data-dir /tmp/rsmoke/data --epochs 2 \
	      --global-batch-size 32 --no-eval --dp 2 --mubatches 2 \
	      --checkpoint-dir /tmp/rsmoke/ck_async --checkpoint-every-steps 4 \
	      --async-checkpoint \
	      --metrics-out /tmp/rsmoke/async.killed.jsonl \
	      > /tmp/rsmoke/async.killed.out 2>&1 && \
	      { echo "async: injected in-window SIGKILL did not fire"; exit 1; } || true; \
	  python -c "import sys; sys.path.insert(0, '.'); from shallowspeed_tpu.checkpoint import find_latest_good, list_step_checkpoints; steps=[g for g,_ in list_step_checkpoints('/tmp/rsmoke/ck_async')]; assert steps==[4,8], 'visible snapshots %r (save 2 = step 12 must never rename)' % steps; p,_,skipped=find_latest_good('/tmp/rsmoke/ck_async'); assert p is not None and p.name=='step-00000008.npz' and skipped==[], 'discovery saw a torn/unverified snapshot: %r %r' % (p, skipped); print('async kill window: only fully-verifying snapshots discoverable (latest %s)' % p.name)"; \
	  $(CPU_MESH) python train.py --data-dir /tmp/rsmoke/data --epochs 2 \
	      --global-batch-size 32 --no-eval --dp 2 --mubatches 2 \
	      --checkpoint-dir /tmp/rsmoke/ck_async --checkpoint-every-steps 4 \
	      --async-checkpoint --resume auto \
	      --metrics-out /tmp/rsmoke/async.resumed.jsonl \
	      > /tmp/rsmoke/async.resumed.out; \
	  twin_h=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/rsmoke/dp2.twin.out); \
	  res_h=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/rsmoke/async.resumed.out); \
	  test -n "$$twin_h" && test "$$twin_h" = "$$res_h" \
	      || { echo "async: HASH MISMATCH resumed [$$res_h] vs twin [$$twin_h]"; exit 1; }; \
	  echo "async: SIGKILL-mid-save + resume auto == uninterrupted twin hash"; \
	  cat /tmp/rsmoke/async.killed.jsonl /tmp/rsmoke/async.resumed.jsonl \
	      > /tmp/rsmoke/async.combined.jsonl; \
	  python -m shallowspeed_tpu.observability.report \
	      /tmp/rsmoke/async.combined.jsonl --format md \
	      > /tmp/rsmoke/async.report.md; \
	  grep -q "async checkpointing: " /tmp/rsmoke/async.report.md; \
	  grep -q "recovery: resumed from" /tmp/rsmoke/async.report.md
	@echo "recovery-smoke OK: kill-at-step-11 + resume auto is bitwise identical to the uninterrupted twin on dp2 and gpipe-pp4 (plus SIGKILL-mid-async-save), Reliability section rendered"

# Numerics-provenance end-to-end (docs/numerics.md "Divergence
# debugging"): on dp2 and gpipe-pp4, train twin runs with --digests and
# assert the divergence CLI exits 0 (streams bitwise-equal), then inject
# a deterministic single-bit param flip (SHALLOWSPEED_FAULTS flip@step=11
# — finite, invisible to loss/health) and assert the CLI exits 2 naming
# EXACTLY (step 11, layer 0, W), that --bisect restores the last agreeing
# per-step snapshot, replays ONE step with the flip re-armed, and
# reproduces the same attribution with ULP evidence, and that the report
# CLI renders the Divergence section. Exit 0.
diverge-smoke:
	rm -rf /tmp/dsmoke; mkdir -p /tmp/dsmoke
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/dsmoke/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	set -e; for lay in dp2 pp4; do \
	  if [ $$lay = dp2 ]; then LFLAGS="--dp 2 --mubatches 2"; \
	  else LFLAGS="--pp 4 --schedule gpipe --mubatches 4"; fi; \
	  COMMON="--data-dir /tmp/dsmoke/data --epochs 2 --global-batch-size 32 --no-eval --digests --checkpoint-every-steps 1 --keep 20"; \
	  $(CPU_MESH) python train.py $$COMMON $$LFLAGS \
	      --checkpoint-dir /tmp/dsmoke/ck_$${lay}_a \
	      --metrics-out /tmp/dsmoke/$$lay.a.jsonl > /tmp/dsmoke/$$lay.a.out; \
	  $(CPU_MESH) python train.py $$COMMON $$LFLAGS \
	      --checkpoint-dir /tmp/dsmoke/ck_$${lay}_b \
	      --metrics-out /tmp/dsmoke/$$lay.b.jsonl > /tmp/dsmoke/$$lay.b.out; \
	  python -m shallowspeed_tpu.observability.divergence \
	      /tmp/dsmoke/$$lay.a.jsonl /tmp/dsmoke/$$lay.b.jsonl \
	      > /tmp/dsmoke/$$lay.twin.cmp; \
	  grep -q "IDENTICAL" /tmp/dsmoke/$$lay.twin.cmp \
	      || { echo "$$lay: twin streams not identical"; exit 1; }; \
	  echo "$$lay: twin digest streams bitwise-equal (exit 0)"; \
	  $(CPU_MESH) env SHALLOWSPEED_FAULTS="flip@step=11" \
	      python train.py $$COMMON $$LFLAGS \
	      --checkpoint-dir /tmp/dsmoke/ck_$${lay}_f \
	      --metrics-out /tmp/dsmoke/$$lay.f.jsonl > /tmp/dsmoke/$$lay.f.out; \
	  rc=0; python -m shallowspeed_tpu.observability.divergence \
	      /tmp/dsmoke/$$lay.a.jsonl /tmp/dsmoke/$$lay.f.jsonl \
	      > /tmp/dsmoke/$$lay.flip.cmp || rc=$$?; \
	  test $$rc -eq 2 \
	      || { echo "$$lay: flip compare exit $$rc, wanted 2"; exit 1; }; \
	  grep -q "first divergence: step 11 layer 0 tensor W" \
	      /tmp/dsmoke/$$lay.flip.cmp \
	      || { echo "$$lay: flip not attributed to (step 11, layer 0, W)"; \
	           cat /tmp/dsmoke/$$lay.flip.cmp; exit 1; }; \
	  echo "$$lay: injected flip named at exactly (step 11, layer 0, W) (exit 2)"; \
	  rc=0; $(CPU_MESH) python -m shallowspeed_tpu.observability.divergence \
	      /tmp/dsmoke/$$lay.a.jsonl /tmp/dsmoke/$$lay.f.jsonl \
	      --bisect /tmp/dsmoke/ck_$${lay}_a /tmp/dsmoke/ck_$${lay}_f \
	      > /tmp/dsmoke/$$lay.bisect.out || rc=$$?; \
	  test $$rc -eq 2 \
	      || { echo "$$lay: bisect exit $$rc, wanted 2"; exit 1; }; \
	  grep -q "divergence is INSIDE step 11" /tmp/dsmoke/$$lay.bisect.out \
	      || { echo "$$lay: bisect did not isolate step 11"; \
	           cat /tmp/dsmoke/$$lay.bisect.out; exit 1; }; \
	  grep -q "replay attribution MATCHES" /tmp/dsmoke/$$lay.bisect.out \
	      || { echo "$$lay: replay attribution mismatch"; \
	           cat /tmp/dsmoke/$$lay.bisect.out; exit 1; }; \
	  grep -q "max ulp 1" /tmp/dsmoke/$$lay.bisect.out \
	      || { echo "$$lay: expected a 1-ulp flip in the replay diff"; exit 1; }; \
	  echo "$$lay: bisect replay reproduced the flip (1 ulp at layer 0 W)"; \
	  python -m shallowspeed_tpu.observability.report \
	      /tmp/dsmoke/$$lay.f.jsonl --format md > /tmp/dsmoke/$$lay.report.md; \
	  grep -q "## Divergence" /tmp/dsmoke/$$lay.report.md \
	      || { echo "$$lay: report missing Divergence section"; exit 1; }; \
	done
	@echo "diverge-smoke OK: twin streams identical (exit 0), flip@step=11 named at (step 11, layer 0, W) (exit 2), bisect replay reproduces the 1-ulp flip, Divergence section rendered, on dp2 and gpipe-pp4"

# inference serving end-to-end (docs/serving.md): on a CPU dp2 and a
# gpipe-pp4 layout, drive 200 seeded Poisson requests through the serving
# engine with --verify (every response bitwise-equal to a direct predict()
# of the same rows) and --audit (every compiled inference program's
# collective census verified against the forward-only serving contract
# before it serves), assert zero dropped/incorrect responses and that the
# schema-v5 request/serving records landed, render the report CLI's
# Serving section with an SLO verdict, then emit the bench_serving
# offered-load sweep JSON (p50/p99 latency, goodput, queue depth,
# saturation knee), exit 0 (needs data, like metrics-smoke)
serve-smoke:
	rm -f /tmp/serve_dp.jsonl /tmp/serve_pp.jsonl /tmp/serve_tp.jsonl \
	    /tmp/serve_bench.json
	$(CPU_MESH) python -m shallowspeed_tpu.serving --dp 2 \
	    --requests 200 --rate 300 --seed 0 --slo-ms 2000 --verify --audit \
	    --metrics-out /tmp/serve_dp.jsonl
	$(CPU_MESH) python -m shallowspeed_tpu.serving --pp 4 --schedule gpipe \
	    --requests 200 --rate 300 --seed 0 --slo-ms 2000 --verify --audit \
	    --metrics-out /tmp/serve_pp.jsonl
	$(CPU_MESH) python -m shallowspeed_tpu.serving --tp 2 \
	    --requests 200 --rate 300 --seed 0 --slo-ms 2000 --verify --audit \
	    --metrics-out /tmp/serve_tp.jsonl
	set -e; for f in /tmp/serve_dp /tmp/serve_pp /tmp/serve_tp; do \
	  python -c "import json,sys; p=sys.argv[1]; recs=[json.loads(l) for l in open(p) if l.strip()]; reqs=[r for r in recs if r.get('kind')=='request']; assert len(reqs)==200, p+': %d request records' % len(reqs); assert all(r['name']=='ok' for r in reqs), p+': dropped/failed requests'; srv=[r for r in recs if r.get('kind')=='serving']; assert srv, p+': no serving summary'; a=[r for r in recs if r.get('kind')=='xla_audit']; assert a and all(r.get('census_ok') for r in a), p+': serving census not clean'; print(p+': 200 ok requests, clean serving census')" $$f.jsonl; \
	  python -m shallowspeed_tpu.observability.report $$f.jsonl --format md \
	      --slo-ms 2000 > $$f.report.md; \
	  grep -q "## Serving" $$f.report.md; \
	  grep -q "SLO" $$f.report.md; \
	done
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving --dp 2 \
	    --rates 100,300 --requests 40 --seed 0 --slo-ms 2000 \
	    --out /tmp/serve_bench.json
	python -c "import json; rec=json.load(open('/tmp/serve_bench.json')); assert rec['bench']=='serving' and rec['bench_version']==1; rows=rec['sweep']; assert len(rows)==2 and all(r['p50_latency_s'] and r['p99_latency_s'] is not None and r['queue_depth_max'] is not None and r['goodput_rps'] is not None for r in rows), rows; print('bench_serving: %d-rate sweep, knee=%s' % (len(rows), rec['knee_rps']))"
	@echo "serve-smoke OK: 200 bitwise-verified Poisson requests on dp2, gpipe-pp4 and tp2, Serving section + SLO verdict rendered, bench_serving sweep recorded"

# serving-layer fault tolerance end-to-end (docs/robustness.md "Serving
# faults"): on a CPU dp2 and a gpipe-pp4 layout, train a short run that
# leaves step checkpoints behind, then serve its step-8 snapshot under a
# seeded chaos soak — error (dispatch raises -> re-queue + retry), slow
# (latency spike), die (dispatch-loop crash, operator re-enters), nan
# (poisoned weights -> unhealthy verdicts -> breaker -> breaker-triggered
# reload) — plus one mid-traffic WATCHER hot reload onto the newer step-16
# weights. Asserts zero silently-lost requests (every submitted id reaches
# a terminal verdict), bitwise parity of every "ok" response vs a direct
# predict() under the weights active at its dispatch, >=1 breaker trip
# with >=2 reloads and a measured recovery, ZERO recompiles across the hot
# swaps, and the report CLI rendering the Degradation subsection. Exit 0.
chaos-smoke:
	rm -rf /tmp/chaos; mkdir -p /tmp/chaos
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/chaos/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	set -e; for lay in dp2 pp4; do \
	  if [ $$lay = dp2 ]; then LFLAGS="--dp 2 --mubatches 2"; SFLAGS="--dp 2"; \
	  else LFLAGS="--pp 4 --schedule gpipe --mubatches 4"; SFLAGS="--pp 4 --schedule gpipe"; fi; \
	  $(CPU_MESH) python train.py --data-dir /tmp/chaos/data --epochs 2 \
	      --global-batch-size 32 --no-eval $$LFLAGS \
	      --checkpoint-dir /tmp/chaos/ck_$$lay --checkpoint-every-steps 8 \
	      > /tmp/chaos/$$lay.train.out; \
	  test -f /tmp/chaos/ck_$$lay/step-00000008.npz \
	      || { echo "$$lay: no step-8 checkpoint to serve"; exit 1; }; \
	  test -f /tmp/chaos/ck_$$lay/step-00000016.npz \
	      || { echo "$$lay: no step-16 checkpoint to hot-reload"; exit 1; }; \
	  $(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving $$SFLAGS \
	      --data-dir /tmp/chaos/data --global-batch-size 32 \
	      --checkpoint /tmp/chaos/ck_$$lay/step-00000008.npz \
	      --chaos "error@dispatch=2,slow@dispatch=3:ms=20,die@dispatch=4,nan@dispatch=6" \
	      --reload-dir /tmp/chaos/ck_$$lay --reload-at 5 --breaker 2 \
	      --retry-budget 2 --max-slots 2 --requests 60 --rates 300 \
	      --slo-ms 2000 --seed 0 \
	      --chaos-out /tmp/chaos/$$lay.chaos.json \
	      --metrics-out /tmp/chaos/$$lay.jsonl; \
	  python -c "import json,sys; p=sys.argv[1]; rec=json.load(open(p)); assert rec['bench']=='serving_chaos'; assert rec['silently_lost']==[], p+': LOST '+str(rec['silently_lost']); assert rec['parity_mismatches']==0, p+': parity mismatches'; assert rec['crashes_recovered']==1, p+': die leg did not fire/recover'; assert rec['breaker_trips']>=1 and rec['reloads']>=2, p+': no breaker-then-reload (%s trips, %s reloads)' % (rec['breaker_trips'], rec['reloads']); assert rec['recovery_s'] is not None and not rec['degraded_at_exit'], p+': did not recover'; assert rec['recompiles']==0 and rec['predict_cache_stable'], p+': hot reload recompiled'; assert rec['faults_unfired']==0, p+': unfired chaos faults'; v=rec['verdicts']; assert v.get('ok',0)>0, p+': nothing served'; print(p+': %d submitted, verdicts %s, availability %.1f%%, recovery %.0f ms' % (rec['submitted'], v, 100*rec['availability'], 1e3*rec['recovery_s']))" /tmp/chaos/$$lay.chaos.json; \
	  python -m shallowspeed_tpu.observability.report /tmp/chaos/$$lay.jsonl \
	      --format md --slo-ms 2000 > /tmp/chaos/$$lay.report.md; \
	  grep -q "### Degradation" /tmp/chaos/$$lay.report.md; \
	  grep -q "breaker: 1 trip" /tmp/chaos/$$lay.report.md; \
	  grep -q "availability" /tmp/chaos/$$lay.report.md; \
	done
	@echo "chaos-smoke OK: die/slow/nan/error + hot reload survived on dp2 and gpipe-pp4 — zero lost, bitwise parity, breaker recovered, zero recompiles, Degradation rendered"

# live-telemetry end-to-end (docs/observability.md "Live telemetry &
# alerting"): train a short dp2 run that leaves step checkpoints, then
# soak its step-8 snapshot under the seeded chaos schedule WITH a live
# background watcher tailing the metrics file as it is written. Asserts
# the injected breaker trip fires the breaker_open alert rule and that
# the SAME rule resolves after the breaker-triggered hot reload recovers
# (firing strictly before resolved in the stream); that rollup records
# stream alongside; that the live watcher's final --follow snapshot
# equals the --once snapshot over the finished file BYTE FOR BYTE (the
# determinism contract: windows close on record ts, never wall clock);
# that a chaos-free twin soak fires ZERO alerts (no false positives)
# while still emitting rollups + the sweep summary record; that --once
# on a missing run exits 1; and that the report CLI renders the Alerts
# section with a clean false-alert verdict. Exit 0.
alerts-smoke:
	rm -rf /tmp/alerts; mkdir -p /tmp/alerts
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/alerts/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	$(CPU_MESH) python train.py --data-dir /tmp/alerts/data --epochs 2 \
	    --global-batch-size 32 --no-eval --dp 2 --mubatches 2 \
	    --checkpoint-dir /tmp/alerts/ck --checkpoint-every-steps 8 \
	    > /tmp/alerts/train.out
	test -f /tmp/alerts/ck/step-00000008.npz \
	    || { echo "no step-8 checkpoint to serve"; exit 1; }
	set -e; \
	python -m shallowspeed_tpu.observability.watch \
	    '/tmp/alerts/chaos.jsonl*' --follow --format json \
	    --interval 0.2 --idle-exit 30 --max-wall 600 \
	    > /tmp/alerts/follow.json & WATCH=$$!; \
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving --dp 2 \
	    --data-dir /tmp/alerts/data --global-batch-size 32 \
	    --checkpoint /tmp/alerts/ck/step-00000008.npz \
	    --chaos "error@dispatch=2,slow@dispatch=3:ms=20,die@dispatch=4,nan@dispatch=6" \
	    --reload-dir /tmp/alerts/ck --reload-at 5 --breaker 2 \
	    --retry-budget 2 --max-slots 2 --requests 60 --rates 300 \
	    --slo-ms 2000 --seed 0 \
	    --chaos-out /tmp/alerts/chaos.json \
	    --metrics-out /tmp/alerts/chaos.jsonl; \
	wait $$WATCH
	python -c "from shallowspeed_tpu.observability.metrics import read_jsonl; recs=read_jsonl('/tmp/alerts/chaos.jsonl'); alerts=[r for r in recs if r['kind']=='alert']; br=[(a['state'],a['t']) for a in alerts if a['name']=='breaker_open']; assert br, 'breaker tripped but no breaker_open alert fired: '+str([(a['name'],a['state']) for a in alerts]); states=[s for s,_ in br]; assert states[0]=='firing' and 'resolved' in states, 'breaker_open never resolved after hot reload: '+str(br); assert states.index('firing')<states.index('resolved'); rolls=[r for r in recs if r['kind']=='rollup']; assert rolls, 'no rollup records streamed'; assert any(r['name']=='serving' for r in rolls); print('chaos soak: %d alert transitions (%s), %d rollup windows' % (len(alerts), ','.join(sorted({a['name'] for a in alerts})), len(rolls)))"
	python -m shallowspeed_tpu.observability.watch '/tmp/alerts/chaos.jsonl*' \
	    --once --format json > /tmp/alerts/once.json
	cmp /tmp/alerts/follow.json /tmp/alerts/once.json \
	    || { echo "--follow and --once snapshots diverge"; exit 1; }
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving --dp 2 \
	    --data-dir /tmp/alerts/data --global-batch-size 32 \
	    --checkpoint /tmp/alerts/ck/step-00000008.npz \
	    --requests 60 --rates 300 --slo-ms 2000 --seed 0 \
	    --out /tmp/alerts/clean_bench.json \
	    --metrics-out /tmp/alerts/clean.jsonl
	python -c "from shallowspeed_tpu.observability.metrics import read_jsonl; recs=read_jsonl('/tmp/alerts/clean.jsonl'); alerts=[r for r in recs if r['kind']=='alert']; assert alerts==[], 'clean twin fired FALSE alerts: '+str([(a['name'],a['state']) for a in alerts]); rolls=[r for r in recs if r['kind']=='rollup']; assert rolls, 'clean twin emitted no rollups'; sweeps=[r for r in recs if r['kind']=='serving' and r['name']=='sweep']; assert sweeps and 'knee_rps' in sweeps[0], 'no sweep summary record'; print('clean twin: 0 alerts, %d rollup windows, sweep knee=%s' % (len(rolls), sweeps[0]['knee_rps']))"
	python -m shallowspeed_tpu.observability.watch /tmp/alerts/clean.jsonl \
	    --once --format json > /tmp/alerts/clean_watch.json
	python -c "import json; s=json.load(open('/tmp/alerts/clean_watch.json')); assert s['alerts']['fired']==0 and s['alerts']['active']==[], s['alerts']; assert s['records']>0 and s['malformed']==0"
	! python -m shallowspeed_tpu.observability.watch \
	    /tmp/alerts/nonexistent.jsonl --once --format json > /dev/null 2>&1
	python -m shallowspeed_tpu.observability.report /tmp/alerts/chaos.jsonl \
	    --format md --slo-ms 2000 > /tmp/alerts/report.md
	grep -q "## Alerts" /tmp/alerts/report.md
	grep -q "every fired rule is backed by fault evidence" /tmp/alerts/report.md
	@echo "alerts-smoke OK: breaker_open fired and resolved under live watch, clean twin fired zero alerts, --follow == --once byte-for-byte, Alerts section rendered with clean false-alert verdict"

# serving-fleet end-to-end (docs/serving.md "Fleet", docs/robustness.md
# "Fleet failover"): train a short run that leaves step checkpoints, then
# serve its step-8 snapshot through a 3-replica fleet (separate worker
# processes, each its own JAX runtime, ladders warmed before traffic)
# under seeded Poisson load — and SIGKILL the busiest replica mid-soak.
# Asserts zero silently-lost requests (every admitted id reaches exactly
# one terminal verdict), zero worker-verified bitwise-parity mismatches,
# >=1 failover with its in-flight re-queued, a replacement scaled up from
# the newest good snapshot (ready time measured) without degrading the
# quorum, and the report CLI rendering the Fleet section from the merged
# parent + .r{replica_id} shard stream. Then the serve CLI's fleet path:
# a 2-replica clean run exits 0 with worker-side bitwise parity. Exit 0.
fleet-smoke:
	rm -rf /tmp/fleet; mkdir -p /tmp/fleet
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/fleet/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	$(CPU_MESH) python train.py --data-dir /tmp/fleet/data --epochs 2 \
	    --global-batch-size 32 --no-eval \
	    --checkpoint-dir /tmp/fleet/ck --checkpoint-every-steps 8 \
	    > /tmp/fleet/train.out
	test -f /tmp/fleet/ck/step-00000008.npz \
	    || { echo "no step-8 checkpoint to serve"; exit 1; }
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving --fleet 3 \
	    --data-dir /tmp/fleet/data --global-batch-size 32 \
	    --checkpoint /tmp/fleet/ck/step-00000008.npz \
	    --reload-dir /tmp/fleet/ck --kill-after 15 \
	    --requests 120 --rates 300 --slo-ms 2000 --seed 0 \
	    --fleet-out /tmp/fleet/FLEET_CHAOS.json \
	    --metrics-out /tmp/fleet/fleet.jsonl
	python -c "import json,sys; rec=json.load(open('/tmp/fleet/FLEET_CHAOS.json')); assert rec['bench']=='serving_fleet_chaos'; assert rec['silently_lost']==[], 'LOST '+str(rec['silently_lost']); assert rec['parity_mismatches']==0, 'parity mismatches'; assert rec['killed_replica'] is not None and rec['replicas_dead']>=1, 'SIGKILL never fired'; assert rec['failovers']>=1 or rec['killed_inflight']==0, 'kill destroyed in-flight work but no failover ran'; assert rec['scale_ups']==1 and rec['scale_up_s'] is not None, 'no measured scale-up'; assert rec['initial_ready_s_mean'] is not None, 'no cold ready baseline'; assert rec['recovery_s'] is not None, 'no measured recovery'; assert not rec['degraded_at_exit'], 'fleet degraded at exit'; v=rec['verdicts']; assert v.get('ok',0)>0, 'nothing served'; print('fleet chaos: %d submitted, verdicts %s, availability %.1f%%, kill stall %.1f ms, replacement ready in %.2f s (initial replicas: %.2f s mean)' % (rec['submitted'], v, 100*rec['availability'], 1e3*rec['kill_stall_s'], rec['scale_up_s'], rec['initial_ready_s_mean']))"
	ls /tmp/fleet/fleet.jsonl.r0 /tmp/fleet/fleet.jsonl.r1 \
	    /tmp/fleet/fleet.jsonl.r2 > /dev/null
	python -m shallowspeed_tpu.observability.report '/tmp/fleet/fleet.jsonl*' \
	    --format md --slo-ms 2000 > /tmp/fleet/report.md
	grep -q "## Fleet" /tmp/fleet/report.md
	grep -q "SIGKILL injected" /tmp/fleet/report.md
	grep -q "failover: " /tmp/fleet/report.md
	grep -q "elasticity: 1 scale-up(s)" /tmp/fleet/report.md
	grep -q "availability" /tmp/fleet/report.md
	$(CPU_MESH) python -m shallowspeed_tpu.serving --fleet 2 \
	    --data-dir /tmp/fleet/data --global-batch-size 32 \
	    --checkpoint /tmp/fleet/ck/step-00000008.npz \
	    --requests 60 --rate 300 --seed 0 --slo-ms 2000 --verify \
	    --metrics-out /tmp/fleet/serve_fleet.jsonl
	@echo "fleet-smoke OK: 3-replica fleet survived a mid-soak SIGKILL — zero lost, worker-verified parity, failover + measured scale-up recovery, Fleet section rendered"

# distributed request tracing end-to-end (docs/observability.md § Tracing):
# a 2-replica fleet soak under seeded Poisson load with one injected
# SIGKILL — every terminal request must leave a COMPLETE, clock-aligned
# span chain across the parent + .r{replica_id} shards (zero
# orphan/unclosed chains: the soak record's trace_problems field and an
# independent strict re-verification both gate it), and the report CLI
# must render the Tracing section (aggregate + p99-conditional phase
# attribution, per-replica clock alignment with uncertainty, worst-k
# request waterfalls). Exit 0.
trace-smoke:
	rm -rf /tmp/tsmoke; mkdir -p /tmp/tsmoke
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/tsmoke/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving --fleet 2 \
	    --data-dir /tmp/tsmoke/data --global-batch-size 32 \
	    --kill-after 10 --requests 80 --rates 300 --slo-ms 2000 --seed 0 \
	    --fleet-out /tmp/tsmoke/FLEET_TRACE.json \
	    --metrics-out /tmp/tsmoke/trace.jsonl
	python -c "import json; rec=json.load(open('/tmp/tsmoke/FLEET_TRACE.json')); assert rec['silently_lost']==[], 'LOST '+str(rec['silently_lost']); assert rec['killed_replica'] is not None, 'SIGKILL never fired'; assert rec['trace_chains'] and rec['trace_chains']>0, 'no span chains recorded'; assert rec['trace_problems']==[], 'INCOMPLETE CHAINS: %s' % rec['trace_problems'][:5]; print('soak record: %d span chains, zero orphan/unclosed across the kill' % rec['trace_chains'])"
	python -c "from shallowspeed_tpu.observability.metrics import read_jsonl; from shallowspeed_tpu.observability import tracing; recs=read_jsonl('/tmp/tsmoke/trace.jsonl*'); chains=tracing.assemble_chains(recs); tracing.verify_terminal_chains(recs, chains, strict=True); offs=tracing.clock_offsets(recs); assert set(offs), 'no clock_offset records'; fo=[c for c in chains.values() if any(s['name']=='failover.requeue' for s in c.spans)]; att=tracing.attribution(chains, slo_ms=2000); assert att and att['phases_mean'], 'no attribution'; print('strict re-verify: %d chains complete, %d replicas aligned (max +/-%.2f ms), %d failover-linked chain(s)' % (len(chains), len(offs), 1e3*max(o['uncertainty_s'] for o in offs.values()), len(fo)))"
	python -m shallowspeed_tpu.observability.report '/tmp/tsmoke/trace.jsonl*' \
	    --format md --slo-ms 2000 > /tmp/tsmoke/trace.report.md
	grep -q "## Tracing" /tmp/tsmoke/trace.report.md
	grep -q "all terminal requests traced end to end" /tmp/tsmoke/trace.report.md
	grep -q "clock alignment: " /tmp/tsmoke/trace.report.md
	grep -q "phase attribution (mean): " /tmp/tsmoke/trace.report.md
	grep -q "p99-conditional" /tmp/tsmoke/trace.report.md
	grep -q "slowest requests:" /tmp/tsmoke/trace.report.md
	@echo "trace-smoke OK: 2-replica kill-injected soak left a complete clock-aligned span chain for every terminal request, Tracing attribution + waterfalls rendered"

# capacity scoreboard end-to-end (docs/serving.md "Autoscaling & the
# capacity scoreboard", ROADMAP item 4): measure the single-replica
# saturation knee with the SAME engine knobs the autoscaler is armed with
# (--max-slots 4 --dispatch-floor-ms 40 — on this 1-core CPU host the
# service-time floor is what makes fleet capacity scale with replica
# count; on accelerators the model forward provides the floor natively),
# then replay ONE seeded compressed-diurnal trace (flash-crowd spike
# included) three ways — static fleet, autoscaled, autoscaled + SIGKILL
# chaos — and score every leg against the offline oracle. bench_replay
# itself exits 1 if any scoreboard verdict fails (autoscaled must beat
# static on BOTH SLO-violation minutes and wasted replica-hours, chaos
# must flap zero times); on top the target asserts the flash crowd
# provoked a scale_out inside the spike window, the trough a scale_in,
# the report CLI renders the Capacity section with the flap count, and
# the watch CLI folds the fleet size + latest autoscale decision. Exit 0.
replay-smoke:
	rm -rf /tmp/rpsmoke; mkdir -p /tmp/rpsmoke
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/rpsmoke/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',2048),('val',256))]"
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving --dp 1 \
	    --data-dir /tmp/rpsmoke/data --global-batch-size 32 \
	    --rates 40,80,120,160,240 --requests 120 --seed 0 --slo-ms 250 \
	    --max-slots 4 --dispatch-floor-ms 40 --out /tmp/rpsmoke/sweep.json
	python -c "import json; rec=json.load(open('/tmp/rpsmoke/sweep.json')); assert rec['knee_rps'] is not None, 'sweep found no saturation knee'; print('sweep: knee at %s rps/replica' % rec['knee_rps'])"
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_replay \
	    --data-dir /tmp/rpsmoke/data --global-batch-size 32 \
	    --max-slots 4 --dispatch-floor-ms 40 \
	    --knee-from /tmp/rpsmoke/sweep.json --day-s 40 \
	    --out /tmp/rpsmoke/AUTOSCALE_r01.json \
	    --metrics-out /tmp/rpsmoke/replay.jsonl
	python -c "import json; rec=json.load(open('/tmp/rpsmoke/AUTOSCALE_r01.json')); assert rec['bench']=='autoscale_scoreboard'; assert all(rec['verdicts'].values()), 'verdicts failed: %s' % [k for k,ok in rec['verdicts'].items() if not ok]; spike=rec['config']['trace']['spikes'][0]; a=rec['legs']['autoscaled']['decisions']; outs=[d for d in a if d['decision']=='scale_out']; ins=[d for d in a if d['decision']=='scale_in']; assert outs and ins, 'autoscaled leg missing scale_out/scale_in'; hit=[d for d in outs if spike['start']-2.0 <= d['t'] <= spike['start']+spike['duration']+2.0]; assert hit, 'no scale_out inside the flash-crowd window %r (outs at %r)' % (spike, [d['t'] for d in outs]); assert rec['legs']['chaos']['flaps']==0, 'chaos leg flapped'; print('scoreboard: flash crowd at t=%.1fs answered by scale_out at t=%.1fs, %d scale_in(s) on slack, chaos flaps=0' % (spike['start'], hit[0]['t'], len(ins)))"
	python -m shallowspeed_tpu.observability.report '/tmp/rpsmoke/replay.jsonl*' \
	    --format md --slo-ms 250 > /tmp/rpsmoke/report.md
	grep -q "## Capacity" /tmp/rpsmoke/report.md
	grep -q "flap count: 0" /tmp/rpsmoke/report.md
	python -m shallowspeed_tpu.observability.watch '/tmp/rpsmoke/replay.jsonl*' \
	    --once > /tmp/rpsmoke/watch.out
	grep -q "fleet: " /tmp/rpsmoke/watch.out
	@echo "replay-smoke OK: one seeded diurnal trace, three legs — every verdict true (autoscaled beat the static fleet on violation minutes AND wasted replica-hours), spike-window scale_out + slack scale_in, zero chaos flaps, Capacity section + watch fleet line rendered"

# MPMD runtime end-to-end (ROADMAP item 1, docs/performance.md "The MPMD
# runtime"): gpipe-pp4 + pipedream-pp4 + interleaved-pp2xV2 epochs under
# --runtime mpmd --audit — final weights HASH-EQUAL to the lockstep twin
# on every layout, the deadlock proof consulted before dispatch
# (static_analysis record, deadlock pass), every per-stage program's
# census clean (xla_audit mpmd_stage_program records, zero mismatches,
# no collective-permute)
mpmd-smoke:
	rm -rf /tmp/msmoke; mkdir -p /tmp/msmoke
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/msmoke/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	set -e; for lay in gpipe pipedream interleaved; do \
	  if [ $$lay = interleaved ]; then \
	    LFLAGS="--pp 2 --schedule interleaved --virtual-stages 2 --mubatches 4"; \
	  else LFLAGS="--pp 4 --schedule $$lay --mubatches 4"; fi; \
	  COMMON="--data-dir /tmp/msmoke/data --epochs 2 --global-batch-size 32 --no-eval"; \
	  $(CPU_MESH) python train.py $$COMMON $$LFLAGS \
	      > /tmp/msmoke/$$lay.lock.out; \
	  $(CPU_MESH) python train.py $$COMMON $$LFLAGS --runtime mpmd --audit \
	      --metrics-out /tmp/msmoke/$$lay.mpmd.jsonl \
	      > /tmp/msmoke/$$lay.mpmd.out; \
	  lock_h=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/msmoke/$$lay.lock.out); \
	  mpmd_h=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/msmoke/$$lay.mpmd.out); \
	  test -n "$$lock_h" && test "$$lock_h" = "$$mpmd_h" \
	      || { echo "$$lay: HASH MISMATCH mpmd [$$mpmd_h] vs lockstep [$$lock_h]"; exit 1; }; \
	  echo "$$lay: mpmd hash == lockstep twin hash"; \
	  python -c "import json,sys; lay='$$lay'; recs=[json.loads(l) for l in open('/tmp/msmoke/'+lay+'.mpmd.jsonl')]; sa=[r for r in recs if r.get('kind')=='static_analysis' and 'deadlock' in (r.get('passes') or [])]; assert sa and all(r.get('findings')==0 for r in sa), lay+': deadlock proof missing or found findings'; audits=[r for r in recs if r.get('kind')=='xla_audit' and r.get('name')=='mpmd_stage_program']; assert len(audits) >= 8, lay+': only %d stage-program audits' % len(audits); bad=[r for r in audits if r.get('census_ok') is not True]; assert not bad, lay+': census mismatches %r' % [b.get('mismatches') for b in bad][:3]; perm=[r for r in audits if (r.get('census') or {}).get('collective_permute',{}).get('count',0)]; assert not perm, lay+': a stage program lowered a collective-permute'; print(lay+': deadlock proof consulted, %d stage programs census-clean, zero relays in-program' % len(audits))"; \
	done
	@echo "mpmd-smoke OK: three schedules hash-equal to lockstep twins under --runtime mpmd --audit, deadlock proof consulted, per-stage census clean"

# activation recompute end-to-end (docs/lowering.md "Recompute ticks"):
# 1 CPU epoch each for gpipe-pp4 and the split-backward pipedream-pp4
# with --recompute --audit vs their stashed twins — final hashes BITWISE
# equal (recompute is a memory knob, not a numerics knob), census clean,
# the pipeline_program record's measured stash peak strictly below the
# stashed twin's, the tick-table lifetime proof re-run standalone, and
# the report CLI's Memory section rendering the two peaks side by side
recompute-smoke:
	rm -rf /tmp/recsmoke; mkdir -p /tmp/recsmoke
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/recsmoke/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	set -e; for lay in gpipe pipedream; do \
	  if [ $$lay = pipedream ]; then SPLIT="--backward-split"; else SPLIT=""; fi; \
	  COMMON="--data-dir /tmp/recsmoke/data --epochs 1 --global-batch-size 32 --no-eval --pp 4 --mubatches 4 --schedule $$lay"; \
	  $(CPU_MESH) python train.py $$COMMON $$SPLIT \
	      > /tmp/recsmoke/$$lay.stashed.out; \
	  $(CPU_MESH) python train.py $$COMMON $$SPLIT --recompute --audit \
	      --metrics-out /tmp/recsmoke/$$lay.rec.jsonl \
	      > /tmp/recsmoke/$$lay.rec.out; \
	  st_h=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/recsmoke/$$lay.stashed.out); \
	  rec_h=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/recsmoke/$$lay.rec.out); \
	  test -n "$$st_h" && test "$$st_h" = "$$rec_h" \
	      || { echo "$$lay: HASH MISMATCH recompute [$$rec_h] vs stashed [$$st_h]"; exit 1; }; \
	  echo "$$lay: recompute hash == stashed twin hash"; \
	  python -c "import json,sys; lay='$$lay'; recs=[json.loads(l) for l in open('/tmp/recsmoke/'+lay+'.rec.jsonl')]; a=[r for r in recs if r.get('kind')=='xla_audit']; assert a and all(r.get('census_ok') for r in a), lay+': census mismatch'; prog=[r for r in recs if r.get('kind')=='event' and r.get('name')=='pipeline_program'][-1]; assert prog['recompute'], lay+': program not recompute'; peak, twin = prog['stash_bytes_peak'], prog['stash_bytes_peak_stashed_twin']; assert peak < twin, lay+': stash peak %d not below stashed twin %d' % (peak, twin); print(lay+': census clean, stash peak %d B < stashed twin %d B (%.0f%% smaller)' % (peak, twin, 100*(1-peak/twin)))"; \
	  python -m shallowspeed_tpu.observability.report \
	      /tmp/recsmoke/$$lay.rec.jsonl --format md \
	      > /tmp/recsmoke/$$lay.report.md; \
	  grep -q "activation stash" /tmp/recsmoke/$$lay.report.md; \
	done
	python -c "from shallowspeed_tpu import schedules as S; from shallowspeed_tpu.parallel.lowering import lower_schedule; from shallowspeed_tpu.analysis.stash import assert_recompute_peak_drop; [print(n, assert_recompute_peak_drop(lower_schedule(c, 4, 4, backward_split=b), lower_schedule(c, 4, 4, backward_split=b, recompute=True))) for n, c, b in (('gpipe', S.GPipeSchedule, False), ('pipedream-split', S.PipeDreamFlushSchedule, True))]"
	@echo "recompute-smoke OK: recompute hashes bitwise-equal to stashed twins on gpipe + split pipedream, census clean, measured stash peak strictly below the stashed twin's, Memory section rendered"

# ZeRO-2/3 end-to-end: CPU epochs at --zero 2 and --zero 3 with --audit
# (train.py aborts nonzero if the compiled census violates the per-stage
# comms contract — per-tick reduce-scatter, ZeRO-3's JIT gather floor),
# the fixed-layout hash pin (--zero 2 final hash == --zero 1 at
# --mubatches 1: one scatter contribution per shard element, so the
# per-tick psum_scatter value IS the psum chunk), and the report's
# ZeRO-forecast row rendering per-stage headroom + the stage ladder
zero-smoke:
	rm -rf /tmp/zsmoke; mkdir -p /tmp/zsmoke
	python -c "import numpy as np; from pathlib import Path; d=Path('/tmp/zsmoke/data'); d.mkdir(parents=True); rng=np.random.RandomState(0); [(np.save(d/('x_'+s+'.npy'), rng.rand(n,784).astype(np.float32)), np.save(d/('y_'+s+'.npy'), np.eye(10,dtype=np.float32)[rng.randint(0,10,n)])) for s,n in (('train',256),('val',96))]"
	set -e; COMMON="--data-dir /tmp/zsmoke/data --epochs 1 --global-batch-size 32 --no-eval --dp 2 --pp 2 --schedule gpipe --optimizer momentum"; \
	$(CPU_MESH) python train.py $$COMMON --mubatches 1 --zero 1 \
	    > /tmp/zsmoke/z1.out; \
	$(CPU_MESH) python train.py $$COMMON --mubatches 1 --zero 2 \
	    > /tmp/zsmoke/z2pin.out; \
	$(CPU_MESH) python train.py $$COMMON --mubatches 4 --zero 2 --audit \
	    --metrics-out /tmp/zsmoke/z2.jsonl > /tmp/zsmoke/z2.out; \
	$(CPU_MESH) python train.py $$COMMON --mubatches 4 --zero 3 --audit \
	    --metrics-out /tmp/zsmoke/z3.jsonl > /tmp/zsmoke/z3.out; \
	h1=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/zsmoke/z1.out); \
	h2=$$(grep -o 'final model hash: [0-9a-f]*' /tmp/zsmoke/z2pin.out); \
	test -n "$$h1" && test "$$h1" = "$$h2" \
	    || { echo "zero2 HASH MISMATCH [$$h2] vs zero1 [$$h1] at mubatches=1"; exit 1; }; \
	echo "zero2 hash == zero1 hash at the fixed layout (mubatches=1)"; \
	for f in /tmp/zsmoke/z2 /tmp/zsmoke/z3; do \
	  python -c "import json,sys; p=sys.argv[1]; recs=[json.loads(l) for l in open(p) if l.strip()]; a=[r for r in recs if r.get('kind')=='xla_audit']; assert a, p+': no xla_audit record'; assert all(r.get('census_ok') for r in a), p+': census mismatch'; exp=[r for r in a if r.get('name')=='epoch_program'][-1]['expected']; zf=exp['zero_forecast']['stages']; assert zf['2']['total_bytes'] < zf['1']['total_bytes'], p+': stage-2 forecast not below stage-1'; dp=exp['axes']['dp']; assert dp['scatter_schedule']=='per_tick', p+': no per-tick scatter schedule'; print(p+': census clean, zero stage '+str(dp['zero'])+' per-tick scatter contract enforced')" $$f.jsonl; \
	  python -m shallowspeed_tpu.observability.report $$f.jsonl --format md \
	      > $$f.report.md; \
	  grep -q "ZeRO forecast" $$f.report.md; \
	  grep -q "headroom" $$f.report.md; \
	  grep -q "stage ladder" $$f.report.md; \
	done
	grep -q "ZeRO stage 2" /tmp/zsmoke/z2.report.md
	grep -q "JIT param gather" /tmp/zsmoke/z3.report.md
	@echo "zero-smoke OK: zero2/zero3 census clean, mubatches=1 hash pin holds, ZeRO forecast + stage ladder + per-stage comms rendered"

# the ZeRO memory scoreboard (same-window zero1/zero2/zero3 epochs on the
# compute-bound flagship zoo model at dp2 and dp2 x pp2, measured
# peak_hbm_bytes ladder + analytical forecast + the mubatches=1 hash
# pin) — writes ZERO_r01.json at the repo root
bench-zero:
	$(CPU_MESH) python scripts/bench_zero.py

# the MPMD-vs-lockstep scoreboard (same-window epoch pair, serving burst
# p99) — writes MPMD_r01.json on the flagship data
bench-mpmd:
	$(CPU_MESH) python scripts/bench_mpmd.py

# the full offered-load sweep on the default layouts (see docs/serving.md)
bench-serving:
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving --dp 2 \
	    --slo-ms 100
	$(CPU_MESH) python -m shallowspeed_tpu.serving.bench_serving --pp 4 \
	    --schedule gpipe --slo-ms 100

data:
	python prepare_data.py

train:
	python train.py --epochs 5

train-mesh:
	$(CPU_MESH) python train.py --dp 2 --pp 4 --schedule gpipe --epochs 2

bench:
	python bench.py

bench-scaling:
	$(CPU_MESH) python scripts/bench_scaling.py

# the production-path-stall scoreboard (PR 12): step-time checkpoint
# overhead sync vs async (same-window interleaved legs) — writes
# CKPT_AOT_r01.json
bench-ckpt-aot:
	$(CPU_MESH) python scripts/bench_ckpt_aot.py

schedules:
	$(CPU_MESH) python scripts/show_schedule.py --all

clean:
	rm -rf .pytest_cache */__pycache__ __pycache__ tests/__pycache__
