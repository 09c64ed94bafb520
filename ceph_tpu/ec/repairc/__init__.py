"""repairc: the repair-schedule compiler.

Lowers a plugin's repair plan for one concrete erasure *signature*
(code, failed-shard set, survivor set, per-helper sub-chunk extents)
into a single fused repair *program*: gather the survivor planes into
one dense array, run one grouped GF(2^8) matmul against a
probe-derived repair matrix, scatter the rebuilt shard streams back
out.  Programs are cached per signature in a cost-weighted LRU
(`RepairProgramCache`, generalizing the decode-*matrix* cache of
ceph_tpu/ec/matrix_code.py to repair-*programs*), so steady-state
recovery never re-derives or re-compiles the schedule.

Plugins contribute plans through the `repair_schedule(erasures,
available)` interface hook (ceph_tpu/ec/interface.py); `None` means
"no partial plan for this signature" and callers fall back to
wholesale full-chunk recovery.  The lrc plugin's client path lowers
its encode (`compile_encode`) and its degraded-read decodes the same
way, into the same cache, and so does the clay plugin's: its encode
and degraded-read decodes, the latter through the same d-helper repair
plans recovery runs.

Motivated by schedule-level XOR program compilation (arxiv
2108.02692) and the LRC rebuild-time results of arxiv 1906.08602.
"""
from .plan import RepairPlan
from .compiler import (RepairProgram, compile_encode, compile_program,
                       interpret_plan)
from .cache import RepairProgramCache, batch_program, program_for, cache_of

__all__ = ["RepairPlan", "RepairProgram", "RepairProgramCache",
           "batch_program", "compile_encode", "compile_program",
           "interpret_plan", "program_for", "cache_of"]
