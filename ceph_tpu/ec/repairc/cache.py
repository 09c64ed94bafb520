"""Per-signature repair-program cache.

Generalizes the decode-matrix LRU (matrix_code.DecodeTableCache —
cost-weighted, thread-safe) from decode *matrices* to compiled repair
*programs*: the LRU stores RepairProgram objects weighted by their
matrix footprint, and a per-signature compile counter provides the
"exactly one compile per erasure signature" evidence the repair bench
and jaxguard gates assert.

One cache per plugin instance (a daemon shares one plugin instance
per profile across all its PGs, so this is also one cache per
profile), attached lazily via `cache_of(ec)`.
"""
from __future__ import annotations

from ...common.lockdep import make_lock
from ..matrix_code import DecodeTableCache
from .compiler import compile_encode, compile_program
from .plan import RepairPlan

#: default capacity in matrix bytes — ~256 full double-erasure
#: programs of a wide code; single-signature steady state uses one
DEFAULT_CAPACITY = 1 << 20

_attach_lock = make_lock("ec.repairc.attach")


class RepairProgramCache:
    """Cost-weighted LRU of compiled repair programs + compile stats."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lru = DecodeTableCache(capacity)
        self._lock = make_lock("ec.repairc.stats")
        self._build = make_lock("ec.repairc.build")
        self._compiles: dict[str, int] = {}
        self._hits = 0

    def __len__(self) -> int:
        return len(self._lru)

    def total_cost(self) -> int:
        return self._lru.total_cost()

    def get_or_build(self, key: str, build) -> tuple:
        """(program under `key`, whether this call built it): the
        cached one, else `build()`'s, stored.  One build at a time: a
        second miss on a key waits for the first's program, and no
        two probe runs share the plugin's interpreted state."""
        prog = self._lru.get(key)
        if prog is None:
            with self._build:
                prog = self._lru.get(key)
                if prog is None:
                    prog = build()
                    self._lru.put(key, prog, cost=prog.cost())
                    with self._lock:
                        self._compiles[key] = \
                            self._compiles.get(key, 0) + 1
                    return prog, True
        with self._lock:
            self._hits += 1
        return prog, False

    def get_or_compile(self, ec, plan: RepairPlan):
        return self.get_or_build(plan.signature(),
                                 lambda: compile_program(ec, plan))[0]

    def stats(self) -> dict:
        """{"hits", "compiles": {sig: count}} — the compile-once gate
        reads this: every signature's count must be exactly 1 (an
        evicted-then-recompiled signature legitimately exceeds it, so
        gates size the capacity above their working set)."""
        with self._lock:
            return {"hits": self._hits,
                    "compiles": dict(self._compiles)}


def cache_of(ec) -> RepairProgramCache:
    """The plugin instance's program cache (lazily attached)."""
    cache = getattr(ec, "_repairc_cache", None)
    if cache is None:
        with _attach_lock:
            cache = getattr(ec, "_repairc_cache", None)
            if cache is None:
                cache = RepairProgramCache()
                ec._repairc_cache = cache
    return cache


def batch_program(ec, plan: RepairPlan | None = None) -> tuple:
    """(program, whether this call built it) of a plugin's batched
    device API, from its cache, with its device kernel: the generator
    (`plan` None, key "encode", compile_encode) or the decode of
    `plan`'s signature (compile_program)."""
    def build():
        prog = compile_encode(ec) if plan is None \
            else compile_program(ec, plan)
        prog.kernel                 # the device kernel, with it
        return prog
    return cache_of(ec).get_or_build(
        "encode" if plan is None else plan.signature(), build)


def program_for(ec, plan: RepairPlan):
    """Compiled program for this plugin + plan, through the cache."""
    return cache_of(ec).get_or_compile(ec, plan)
