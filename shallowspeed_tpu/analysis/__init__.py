"""Static program analysis: machine-checked contracts for the invariants
this repo used to enforce by convention.

Two coordinated halves (docs/static-analysis.md):

- **program-level passes** (``progcheck``/``stash``): run over LOWERED
  tick tables at lowering/compile time, before the first dispatch. The
  lowering simulator (parallel/lowering.py) *constructs* programs it
  believes are well-formed; these passes independently *prove* the
  properties the ROADMAP item-1 MPMD runtime will depend on — every
  ``SendActivations`` has a consuming recv on the peer stage, the
  happens-before graph stays acyclic WITHOUT the lockstep barrier (so
  per-stage streams dispatched asynchronously can never deadlock, even
  under bounded mailboxes), and every stash slot is written before read,
  freed by program end, with the measured peak equal to the allocated
  ``n_stash_slots``/``n_gstash_slots``. The simulator stays the spec;
  the analyzer is the proof that a given artifact satisfies it.
- **a house-rule AST linter** (``rules``/``lint``; stdlib ``ast``, zero
  new deps): ``python -m shallowspeed_tpu.analysis.lint`` encodes the
  rules generic linters can't — justified broad excepts, strict-JSON
  metrics writes, the one-atomic-write discipline, the donation
  whitelist, the metrics schema-kind registry, and lock discipline on
  lock-owning classes. ``make lint`` runs it repo-wide (exit 2 on
  findings, ``--format json`` for machines) and a tier-1 test keeps
  HEAD clean.

The third static check — the HLO dispatch-safety pass that refuses
serving-path programs that donate their buffers — lives in
``observability/program_audit.py`` next to the collective census it
extends (``parse_input_output_aliases`` / ``verify_dispatch_safety``).
"""

from shallowspeed_tpu.analysis.progcheck import (
    ProgramAnalysisError,
    analyze_program,
    check_deadlock_free,
    check_send_recv,
)
from shallowspeed_tpu.analysis.stash import check_stash_lifetime

__all__ = [
    "ProgramAnalysisError",
    "analyze_program",
    "check_deadlock_free",
    "check_send_recv",
    "check_stash_lifetime",
]
