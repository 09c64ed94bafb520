"""cephck — project-specific static analysis (the lint gate).

The reference gates merges on exactly this class of tooling: lockdep
(src/common/lockdep.cc) catches lock-order cycles, ceph-dencoder +
ceph-object-corpus pin wire encodings, and a battery of tree-specific
checks (src/script/) runs before anything ships.  cephck is this
repo's analogue: an AST-based rule engine whose rules encode *bugs we
actually shipped* (a pgmeta omap mutation outside its owning
transaction, a wire encode that silently diverged from its registered
version) plus the JAX-specific hazards that invalidate perf claims
(timing a dispatch instead of a compute, unhashable jit static args).

Run it from the repo root::

    python -m ceph_tpu.analysis ceph_tpu/ tests/ scripts/ chip_smoke.py

Exit 0 means no unsuppressed findings.  Suppressions live in
``.cephck-baseline.json`` at the repo root and every entry MUST carry
a one-line ``reason`` — a baseline without justification is just a
blindfold.  See README "Static analysis & sanitizers".
"""
from .engine import Engine, Finding, load_baseline, main  # noqa: F401
from .rules import ALL_RULES  # noqa: F401
