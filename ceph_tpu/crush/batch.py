"""Batched CRUSH mapper — vmapped straw2 placement on device.

The TPU-native replacement for the reference's bulk placement paths
(OSDMapMapping/ParallelPGMapper src/osd/OSDMapMapping.h:18, CrushTester
src/crush/CrushTester.cc:477, osdmaptool --test-map-pgs): instead of
sharding PGs over a thread pool, the CRUSH map is compiled to flat arrays
and `do_rule` becomes a pure jittable function of the PG seed `x`,
vmapped over millions of seeds.

Semantics are bit-exact with the scalar engine (ceph_tpu.crush.mapper,
itself validated against the reference C core src/crush/mapper.c):

- straw2 (bucket_straw2_choose, mapper.c:361): 16-bit rjenkins hash →
  fixed-point crush_ln (mapper.c:248) → truncating s64 division by
  weight → first-max argmax.  crush_ln's `(x*RH)>>48` product exceeds
  s64 range, so it is computed in split 32-bit limbs (int64-safe).
- choose_firstn (mapper.c:460): the reject/collision retry cascade is
  re-expressed as a flat state machine per replica: descend on type
  mismatch, collide-retry *in the same bucket* while
  `flocal <= local_retries`, re-descend from the take bucket while
  `ftotal < tries`, else skip the replica; invalid items skip the
  replica immediately (mapper.c:540,553).
- choose_indep (mapper.c:655): already a bounded, positionally-stable
  loop (`ftotal < tries`, holes = CRUSH_ITEM_NONE) — mapped to
  `lax.while_loop` over rounds with a masked in-round replica sweep,
  including the observable out2 staleness quirks of the C code.
- chooseleaf recursion (both variants) is a bounded one-replica leaf
  descent with `recurse_tries`; `vary_r`/`stable` honored.

Restrictions of the batch path (compile_map raises BatchUnsupported;
callers fall back to the scalar engine):
- straw2 buckets only (the modern default).  uniform/list/tree/straw
  need stateful permutation buffers or build-time straws that do not
  vectorize the same way.
- choose_local_fallback_tries == 0 (jewel default; the perm-fallback
  path mapper.c:519 is inherently stateful/sequential).
- rjenkins1 hash only (the only hash the reference defines).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..common.log import dout
from ._ln_tables import RH_LH_TBL, LL_TBL
from .hashes import _mix
from .types import (
    CRUSH_BUCKET_STRAW2, CRUSH_HASH_RJENKINS1, CRUSH_ITEM_NONE,
    CRUSH_ITEM_UNDEF, CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP, CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP, CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSELEAF_STABLE, CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_VARY_R,
    CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES, CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE, CrushMap,
)

# All 64-bit straw2/ln arithmetic runs inside a scoped
# `jax.enable_x64(True)` (map_batch) so the global dtype-promotion
# config of the host program (and the EC int8/uint8 kernels) is never
# mutated.  Module constants are plain Python ints / numpy arrays so
# their dtype is resolved at trace time inside that scope.
S64_MIN = -(1 << 62)  # below any real draw (draws are > -2^49)
U16 = 0xFFFF
LN_BIAS = 0x1000000000000

# numpy scalars, not jnp: a jnp constant here would start the device
# backend at import (mon/mgr processes import this module)
_SEED = np.uint32(1315423911)
_X0 = np.uint32(231232)
_Y0 = np.uint32(1232)

# descend outcome codes
_HIT, _EMPTY, _BAD = 0, 1, 2


class BatchUnsupported(ValueError):
    """Raised when a map/rule cannot run on the batch path."""


# ---------------------------------------------------------------------------
# rjenkins1 in jnp (uint32 wraparound; ref: src/crush/hash.c:12-113).
# The 9-step hashmix is shared with the scalar engine (hashes._mix is
# operator-generic and tracer-safe).

def _u32(v):
    return jnp.asarray(v).astype(jnp.int64).astype(jnp.uint32)


def jhash2(a, b):
    a, b = _u32(a), _u32(b)
    h = _SEED ^ a ^ b
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(_X0, a, h)
    b, y, h = _mix(b, _Y0, h)
    return h


def jhash3(a, b, c):
    a, b, c = _u32(a), _u32(b), _u32(c)
    h = _SEED ^ a ^ b ^ c
    x = _X0
    y = _Y0
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


# ---------------------------------------------------------------------------
# fixed-point ln (ref: src/crush/mapper.c:247-289), int64-safe

# kept as numpy so the int64 dtype survives regardless of the global
# x64 flag; they become constants at trace time (inside the x64 scope)
_RH_LH = np.asarray(RH_LH_TBL, dtype=np.int64)
_LL = np.asarray(LL_TBL, dtype=np.int64)


def crush_ln_vec(u):
    """2^44*log2(u+1) fixed point, elementwise over int arrays."""
    x = (u.astype(jnp.int64) + 1) & 0xFFFFFFFF
    x17 = x & 0x1FFFF
    # bit_length(x17) via unrolled comparisons (x17 <= 0x1FFFF)
    bl = jnp.zeros_like(x17)
    for k in range(17):
        bl = bl + (x17 >= (1 << k)).astype(jnp.int64)
    bits = 16 - bl
    need = (x & 0x18000) == 0
    xn = jnp.where(need, x << jnp.clip(bits, 0, 16), x)
    iexpon = jnp.where(need, 15 - bits, 15)
    index1 = (xn >> 8) << 1
    rh_lh = jnp.asarray(_RH_LH)
    RH = rh_lh[index1 - 256]
    LH = rh_lh[index1 + 1 - 256]
    # (xn * RH) >> 48 without u64: split RH into 32-bit limbs
    p_lo = xn * (RH & 0xFFFFFFFF)
    p_hi = xn * (RH >> 32)
    xl64 = ((p_lo + ((p_hi & 0xFFFF) << 32)) >> 48) + (p_hi >> 16)
    index2 = xl64 & 0xFF
    LL = jnp.asarray(_LL)[index2]
    return (iexpon << 44) + ((LH + LL) >> 4)


def _build_ln16_table() -> np.ndarray:
    """crush_ln over the FULL 16-bit straw2 domain, precomputed host-
    side with the same fixed-point arithmetic (numpy int64).

    straw2 only ever evaluates ln on `hash & 0xFFFF` (mapper.c:377), so
    the whole function collapses to one 65536-entry device gather —
    measured 3x faster than the normalize/multiply/double-gather chain
    on v5e (the int64-emulated multiplies dominate there)."""
    x = (np.arange(65536, dtype=np.int64) + 1) & 0xFFFFFFFF
    x17 = x & 0x1FFFF
    bl = np.zeros_like(x17)
    for k in range(17):
        bl += (x17 >= (1 << k)).astype(np.int64)
    bits = 16 - bl
    need = (x & 0x18000) == 0
    xn = np.where(need, x << np.clip(bits, 0, 16), x)
    iexpon = np.where(need, 15 - bits, 15)
    index1 = (xn >> 8) << 1
    RH = _RH_LH[index1 - 256]
    LH = _RH_LH[index1 + 1 - 256]
    p_lo = xn * (RH & 0xFFFFFFFF)
    p_hi = xn * (RH >> 32)
    xl64 = ((p_lo + ((p_hi & 0xFFFF) << 32)) >> 48) + (p_hi >> 16)
    LL = _LL[xl64 & 0xFF]
    return (iexpon << 44) + ((LH + LL) >> 4)


#: ln(u+1) for every u in [0, 0xFFFF] — the straw2 hot-path table
_LN16 = _build_ln16_table()


def crush_ln16(u):
    """Table form of crush_ln_vec for 16-bit inputs (the straw2 path)."""
    return jnp.asarray(_LN16)[u]


# crush_ln is monotone in u EXCEPT at the very top: u=65535 normalizes
# x=u+1=0x10000 with iexpon capped at 15, so its value dips BELOW
# ln(65534) (and sits above ln(65533)).  The weight-class straw2 path
# relies on monotonicity, so it orders hashes through a key space that
# swaps that single pair.  Verified against the table here; if a
# regenerated table ever breaks differently, the class path disables
# itself rather than silently diverging.
_LN16_DIPS = np.nonzero(np.diff(_LN16.astype(np.int64)) < 0)[0]
LN16_MONO_BY_SWAP = (
    len(_LN16_DIPS) == 0
    or (len(_LN16_DIPS) == 1 and int(_LN16_DIPS[0]) == 65534
        and _LN16[65533] <= _LN16[65535]))


def _mono_key(u):
    """Involution mapping u-space <-> a space where ln16 is monotone
    (swaps 65534 and 65535; identity elsewhere, incl. the -1 dead
    sentinel)."""
    return jnp.where(u == 65534, jnp.int64(65535),
                     jnp.where(u == 65535, jnp.int64(65534), u))


#: ln16 in key order (the _mono_key swap), for host-side table work
_LN16_KEYED = _LN16.copy()
_LN16_KEYED[[65534, 65535]] = _LN16[[65535, 65534]]
#: spans probed for the tie bound; a weight whose ties could be wider
#: than this falls back to the full 16-probe search
_TIE_SPAN_MAX = 32
#: _TIE_GAP[j-1] = the smallest ln16 rise over j consecutive keys (key
#: space).  Nondecreasing in j: a longer span contains a shorter one.
_TIE_GAP = np.array([(_LN16_KEYED[j:] - _LN16_KEYED[:-j]).min()
                     for j in range(1, _TIE_SPAN_MAX + 1)])


def tie_bound(w: int) -> int:
    """Upper bound on how far below the class winner's key the first
    key of equal draw can lie at weight w (the tie floor of _straw2).

    Keys a < b draw equal only if their |ln| differ by less than w, and
    they differ by at least _TIE_GAP[b-a-1]; so b - a never exceeds the
    number of spans whose smallest rise is below w.  The bound is
    monotone in w, so a map's largest class weight bounds all of its
    classes."""
    n = int(np.searchsorted(_TIE_GAP, w, side="left"))
    return U16 if n >= _TIE_SPAN_MAX else n


def _div_trunc(a, b):
    """C truncating signed division, b > 0."""
    q = jnp.abs(a) // jnp.maximum(b, 1)
    return jnp.where(a < 0, -q, q)


# ---------------------------------------------------------------------------
# compiled map

@dataclass(frozen=True)
class _StaticCfg:
    """Everything _do_rule_one decides at TRACE time, as a hashable
    key.  The compiled executable is cached module-wide on this (plus
    jit's own shape keying), so a new CompiledCrushMap for every
    osdmap epoch — same topology shape, same rules — reuses the
    executable instead of paying a fresh XLA compile (the reference's
    mgr calls calc_pg_upmaps every tick; a ~40 s recompile per epoch
    would dwarf the mapping itself)."""
    steps: tuple          # ((op, arg1, arg2, take_ok), ...)
    result_max: int
    tries: int            # choose_total_tries + 1
    local_retries: int
    vary_r: int
    stable: int
    descend_once: int
    max_devices: int
    max_buckets: int
    n_positions: int
    max_depth: int
    n_class_max: int
    use_classes: bool
    tie_probes: int
    first_valid: int


@dataclass
class _CmView:
    """The array half of a compiled map, rebuilt inside the jitted
    function from ARGUMENTS (not closure constants) so the weights/
    items tables are runtime inputs.  Field names mirror
    CompiledCrushMap — every choose helper works on either."""
    items: object
    ids: object
    weights: object
    sizes: object
    btypes: object
    valid: object
    class_of: object
    class_w: object
    static: object

    @property
    def max_buckets(self):
        return self.static.max_buckets

    @property
    def max_depth(self):
        return self.static.max_depth

    @property
    def n_positions(self):
        return self.static.n_positions

    @property
    def n_class_max(self):
        return self.static.n_class_max

    @property
    def use_classes(self):
        return self.static.use_classes

    @property
    def tie_probes(self):
        return self.static.tie_probes

    @property
    def max_devices(self):
        return self.static.max_devices


#: module-wide executable cache: _StaticCfg -> jitted vmapped rule fn
_RULE_JIT: dict = {}


#: class-path cutoff: with more distinct weights per bucket than this,
#: the masked per-class max (I x C compares per draw) costs more than
#: the ln gathers it saves and the engine keeps the direct path
CLASS_PATH_MAX = 16


@dataclass
class CompiledCrushMap:
    """CrushMap flattened to arrays for the batch engine."""
    map_: CrushMap
    items: jnp.ndarray        # (B, I) int32 — bucket members (pad 0)
    ids: jnp.ndarray          # (B, I) int32 — straw2 hash ids (choose_args)
    weights: jnp.ndarray      # (P, B, I) int64 — per-position 16.16 weights
    sizes: jnp.ndarray        # (B,) int32
    btypes: jnp.ndarray       # (B,) int32
    valid: jnp.ndarray        # (B,) bool
    max_devices: int
    max_buckets: int
    n_positions: int
    max_depth: int            # longest bucket chain (static descend bound)
    #: weight-class tables (see _straw2): class_of (P, B, I) int32 with
    #: -1 for zero-weight/pad lanes; class_w (P, B, C) int64
    class_of: jnp.ndarray | None = None
    class_w: jnp.ndarray | None = None
    n_class_max: int = 0
    use_classes: bool = False
    #: binary-search steps of the class path's tie floor: the bit length
    #: of tie_bound at the map's largest class weight (at most 16; 0
    #: on the direct path)
    tie_probes: int = 0
    #: id of any non-empty bucket (safe target for masked lanes)
    first_valid: int = -1

    # -- public API ---------------------------------------------------------
    def map_batch(self, xs, weight, ruleno=0, result_max=None,
                  return_counts=False):
        """Map a batch of inputs.  xs: (N,) int seeds; weight: (D,) int
        16.16 reweight vector (device in/out/partial).  Returns
        (N, result_max) int32 placements (CRUSH_ITEM_NONE holes),
        optionally with per-row result counts."""
        if not (0 <= ruleno < len(self.map_.rules)) or \
                self.map_.rules[ruleno] is None:
            raise BatchUnsupported(f"no rule {ruleno}")
        rule = self.map_.rules[ruleno]
        choose_ops = (CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSE_INDEP,
                      CRUSH_RULE_CHOOSELEAF_FIRSTN,
                      CRUSH_RULE_CHOOSELEAF_INDEP)
        if result_max is None:
            # a choose step with arg1 <= 0 means numrep = result_max
            # (mapper.c:972-976): no sensible default exists
            if any(s.op in choose_ops and s.arg1 <= 0 for s in rule.steps):
                raise BatchUnsupported(
                    f"rule {ruleno} has a choose step with numrep <= 0 "
                    "(numrep = result_max - pass result_max explicitly, "
                    "e.g. k+m for an EC rule)")
            # upper bound on emitted results: chained choose steps
            # multiply, emits accumulate
            wmax = 0
            total = 0
            for s in rule.steps:
                if s.op == CRUSH_RULE_TAKE:
                    wmax = 1
                elif s.op in choose_ops:
                    wmax *= s.arg1
                elif s.op == CRUSH_RULE_EMIT:
                    total += wmax
                    wmax = 0
            result_max = max(total, 1)
        m = self.map_
        steps = tuple(
            (st.op, st.arg1, st.arg2,
             bool((0 <= st.arg1 < m.max_devices)
                  or (st.arg1 < 0 and m.bucket(st.arg1) is not None))
             if st.op == CRUSH_RULE_TAKE else False)
            for st in rule.steps)
        static = _StaticCfg(
            steps=steps, result_max=int(result_max),
            tries=m.choose_total_tries + 1,
            local_retries=m.choose_local_tries,
            vary_r=m.chooseleaf_vary_r, stable=m.chooseleaf_stable,
            descend_once=m.chooseleaf_descend_once,
            max_devices=m.max_devices, max_buckets=self.max_buckets,
            n_positions=self.n_positions, max_depth=self.max_depth,
            n_class_max=self.n_class_max,
            use_classes=self.use_classes,
            tie_probes=self.tie_probes,
            first_valid=self.first_valid)
        with jax.enable_x64(True):
            fn = _RULE_JIT.get(static)
            if fn is None:
                def one(arrays, x, weight, static=static):
                    cm = _CmView(*arrays, static)
                    return _do_rule_one(cm, static, x, weight)
                fn = jax.jit(jax.vmap(one, in_axes=(None, 0, None)))
                _RULE_JIT[static] = fn
            arrays = (self.items, self.ids, self.weights, self.sizes,
                      self.btypes, self.valid, self.class_of,
                      self.class_w)
            xs = jnp.asarray(xs, dtype=jnp.int64)
            weight = jnp.asarray(weight, dtype=jnp.int64)
            # the placement tables were staged once at compile_map;
            # under CEPH_TPU_JAXGUARD an implicit transfer inside the
            # batched mapping dispatch is an error
            from ..common import jaxguard
            with jaxguard.guard_transfers():
                res, cnt = fn(arrays, xs, weight)
        if return_counts:
            return res, cnt
        return res


def compile_map(map_: CrushMap, choose_args=None,
                class_path: bool | None = None) -> CompiledCrushMap:
    """Flatten a CrushMap for the batch engine (straw2-only).

    class_path: None = auto (on when every bucket has at most
    CLASS_PATH_MAX distinct positive weights per position); True/False
    force it — tests use this to pin each straw2 formulation."""
    if isinstance(choose_args, str):
        choose_args = map_.choose_args.get(choose_args)
    choose_args = choose_args or {}
    B = map_.max_buckets
    I = 1
    P = 1
    for b in map_.buckets:
        if b is None:
            continue
        if b.alg != CRUSH_BUCKET_STRAW2:
            raise BatchUnsupported(
                f"bucket {b.id}: alg {b.alg} not batchable (straw2 only)")
        if b.hash != CRUSH_HASH_RJENKINS1:
            raise BatchUnsupported(f"bucket {b.id}: non-rjenkins hash")
        I = max(I, b.size)
        arg = choose_args.get(b.id)
        if arg is not None and arg.weight_set is not None:
            P = max(P, len(arg.weight_set))
    if map_.choose_local_fallback_tries:
        raise BatchUnsupported("choose_local_fallback_tries > 0")
    # validate item references: the scalar oracle fails loudly on a
    # dangling bucket id; the batch engine must not silently diverge
    for b in map_.buckets:
        if b is None:
            continue
        for it in b.items:
            if it < 0 and (
                    -1 - it >= B or map_.buckets[-1 - it] is None):
                raise BatchUnsupported(
                    f"bucket {b.id} references missing bucket {it}")
    # longest bucket chain = static bound for the descend loops;
    # also rejects cyclic maps (the scalar engine would not terminate)
    depth_memo: dict[int, int] = {}

    def bdepth(bi: int, stack: set) -> int:
        if bi in stack:
            raise BatchUnsupported(f"bucket cycle through {-1 - bi}")
        if bi in depth_memo:
            return depth_memo[bi]
        stack.add(bi)
        d = 1
        for it in map_.buckets[bi].items:
            if it < 0:
                d = max(d, 1 + bdepth(-1 - it, stack))
        stack.remove(bi)
        depth_memo[bi] = d
        return d

    max_depth = max(
        (bdepth(bi, set()) for bi, b in enumerate(map_.buckets)
         if b is not None), default=1)

    items = np.zeros((B, I), dtype=np.int32)
    ids = np.zeros((B, I), dtype=np.int32)
    weights = np.zeros((P, B, I), dtype=np.int64)
    sizes = np.zeros((B,), dtype=np.int32)
    btypes = np.zeros((B,), dtype=np.int32)
    valid = np.zeros((B,), dtype=bool)
    for bi, b in enumerate(map_.buckets):
        if b is None:
            continue
        n = b.size
        valid[bi] = True
        sizes[bi] = n
        btypes[bi] = b.type
        items[bi, :n] = b.items
        arg = choose_args.get(b.id)
        ids[bi, :n] = (arg.ids if arg is not None and arg.ids is not None
                       else b.items)
        for p in range(P):
            if arg is not None and arg.weight_set is not None:
                ws = arg.weight_set[min(p, len(arg.weight_set) - 1)]
            else:
                ws = b.item_weights
            weights[p, bi, :n] = ws
    # -- weight classes (the straw2 argmax shortcut, see _straw2) -------
    # group each bucket's items by their exact weight; per draw the
    # engine takes a masked max of the raw 16-bit hashes per class and
    # evaluates ln only on the C class winners instead of all I items
    class_lists: dict[tuple[int, int], list[int]] = {}
    cmax = 1
    wmax = 0
    for bi, b in enumerate(map_.buckets):
        if b is None:
            continue
        for p in range(P):
            # dict preserves first-occurrence order with O(1)
            # membership (a list scan here was O(I*C) per bucket
            # per position on every compile)
            seen = {int(w): None for w in weights[p, bi, :b.size]
                    if w > 0}
            class_lists[(p, bi)] = list(seen)
            cmax = max(cmax, len(seen))
            wmax = max([wmax, *seen])
    use_classes = (cmax <= CLASS_PATH_MAX if class_path is None
                   else class_path) and LN16_MONO_BY_SWAP
    # 0 on the direct path, so its executable never keys on weights
    tie_probes = tie_bound(wmax).bit_length() if use_classes else 0
    dout("crush", 10).write(
        "compile_map: use_classes %s n_class_max %d tie_probes %d",
        use_classes, cmax, tie_probes)
    class_of = np.full((P, B, I), -1, dtype=np.int32)
    class_w = np.ones((P, B, cmax), dtype=np.int64)
    for (p, bi), seen in class_lists.items():
        class_w[p, bi, :len(seen)] = seen
        lut = {w: c for c, w in enumerate(seen)}
        n = map_.buckets[bi].size
        for i in range(n):
            w = int(weights[p, bi, i])
            if w > 0:
                class_of[p, bi, i] = lut[w]
    with jax.enable_x64(True):  # weights table must stay int64
        return CompiledCrushMap(
            map_=map_, items=jnp.asarray(items), ids=jnp.asarray(ids),
            weights=jnp.asarray(weights), sizes=jnp.asarray(sizes),
            btypes=jnp.asarray(btypes), valid=jnp.asarray(valid),
            max_devices=map_.max_devices, max_buckets=B, n_positions=P,
            max_depth=max_depth, class_of=jnp.asarray(class_of),
            class_w=jnp.asarray(class_w), n_class_max=cmax,
            use_classes=use_classes, tie_probes=tie_probes,
            first_valid=next(
                (-1 - bi for bi, b in enumerate(map_.buckets)
                 if b is not None and b.size > 0), -1))


# ---------------------------------------------------------------------------
# core choose primitives (single-x; vmapped by map_batch)

def _straw2(cm: CompiledCrushMap, bidx, x, r, position):
    """bucket_straw2_choose (mapper.c:361-390) for dense bucket bidx.

    Two bit-identical formulations:

    * **class path** (default): `crush_ln` is monotonically
      nondecreasing and `draw = trunc(ln(u)/w)` is monotone in ln for
      fixed w > 0, so WITHIN a weight class the winning item is simply
      the one with the max 16-bit hash — no ln, no division.  The
      engine takes a masked max of the raw hashes per class (compile
      time grouped, C classes) and evaluates ln/div only on the C
      class winners; a uniform bucket (C=1) pays ONE ln per draw
      instead of I.  This is the TPU answer to the reference's
      per-item serial ln loop (mapper.c:377): the 64Ki-table gather
      was the placement wall (~5/6 of a draw pass), and it shrinks by
      I/C.  Ties keep C semantics: first index wins (argmax picks the
      first in-class max; cross-class ties resolve to the smallest
      item index, matching the strict `>` update in
      bucket_straw2_choose).
    * **direct path**: per-item ln gather — kept for maps with more
      than CLASS_PATH_MAX distinct weights in a bucket, where the
      (I x C) class masking would outgrow the gather it saves.
    """
    ids = cm.ids[bidx]
    pos = jnp.minimum(position, cm.n_positions - 1)
    u = jhash3(x, ids, r).astype(jnp.int64) & U16
    I = cm.items.shape[1]
    lane_ok = jnp.arange(I) < cm.sizes[bidx]
    if cm.use_classes:
        cls = cm.class_of[pos, bidx]                   # (I,) -1 = dead
        cw = cm.class_w[pos, bidx]                     # (C,)
        ue = jnp.where(lane_ok & (cls >= 0), u, jnp.int64(-1))
        uk = _mono_key(ue)          # ln16 is monotone in key space
        cmask = cls[None, :] == jnp.arange(cm.n_class_max)[:, None]
        kc = jnp.where(cmask, uk[None, :], jnp.int64(-1))   # (C, I)
        kmax = kc.max(axis=1)
        umax = _mono_key(kmax)      # back to u-space for the table
        # the class draw: ln(u)-LN_BIAS is always negative for 16-bit
        # u, so trunc(ln_val/w) = -(|ln_val| // w)
        absln = LN_BIAS - crush_ln16(jnp.maximum(umax, 0))
        k = absln // cw
        draws = jnp.where(kmax >= 0, -k, S64_MIN)      # (C,)
        # tie floor: the truncating division collapses a contiguous
        # key range onto the winning draw — the C core's strict->
        # update means the FIRST index in that range wins, not the
        # max-key one.  kk = min{key : ln16(unkey) >= thr}, found by
        # binary search in key space (C lanes, not I).  The floor lies
        # at most tie_bound(w) <= 2^tie_probes - 1 keys below kmax, so
        # tie_probes halvings of that window reach it
        x_thr = LN_BIAS - (k + 1) * cw + 1
        lo = jnp.maximum(kmax - ((1 << cm.tie_probes) - 1), 0)
        hi = jnp.maximum(kmax, 0)
        for _ in range(cm.tie_probes):
            mid = (lo + hi) >> 1
            ok = crush_ln16(_mono_key(mid)) >= x_thr
            hi = jnp.where(ok, mid, hi)
            lo = jnp.where(ok, lo, mid + 1)
        # first item index whose draw equals the class draw
        idx_c = jnp.where(cmask & (uk[None, :] >= hi[:, None]),
                          jnp.arange(I)[None, :], I).min(axis=1)
        best = draws.max()
        idx = jnp.where(draws == best, idx_c, I).min()
        idx = jnp.where(best == S64_MIN, 0, idx)       # all-dead bucket
        return cm.items[bidx, idx]
    w = cm.weights[pos, bidx]
    ln = crush_ln16(u) - LN_BIAS
    draws = jnp.where(w > 0, _div_trunc(ln, w), S64_MIN)
    draws = jnp.where(lane_ok, draws, S64_MIN - 1)
    return cm.items[bidx, jnp.argmax(draws)]


def _item_type(cm: CompiledCrushMap, item):
    bidx = jnp.clip(-1 - item, 0, cm.max_buckets - 1)
    return jnp.where(item < 0, cm.btypes[bidx], 0)


def _bucket_ok(cm: CompiledCrushMap, item):
    """item is a loadable bucket id."""
    inb = (item < 0) & ((-1 - item) < cm.max_buckets)
    bidx = jnp.clip(-1 - item, 0, cm.max_buckets - 1)
    return inb & cm.valid[bidx]


def _is_out(cm: CompiledCrushMap, weight, item, x):
    """Probabilistic reweight rejection (mapper.c:424-441)."""
    D = weight.shape[0]
    idx = jnp.clip(item, 0, D - 1)
    w = weight[idx]
    oob = item >= D
    return oob | ((w < 0x10000) & (
        (w == 0) | ((jhash2(x, item).astype(jnp.int64) & U16) >= w)))


def _descend(cm: CompiledCrushMap, x, r, start_item, target_type, position):
    """Straw2-walk from bucket `start_item` down until an item of
    target_type or a dead end.  Returns (item, parent, code):
    parent = bucket the item was chosen from (for in-bucket retries);
    code = _HIT | _EMPTY (a size-0 bucket was reached) | _BAD (invalid
    item id / non-bucket of wrong type, mapper.c:540,553).

    Mirrors the `retry_bucket` type-mismatch descent inside both
    crush_choose_firstn (mapper.c:546-556) and crush_choose_indep
    (mapper.c:744-773); the same r is used at every level.
    """
    def cond(st):
        cur, item, code, done, depth = st
        return (~done) & (depth < cm.max_depth)

    def body(st):
        cur, item, code, done, depth = st
        bidx = -1 - cur
        empty = cm.sizes[bidx] == 0
        nxt = _straw2(cm, bidx, x, r, position)
        ntype = _item_type(cm, nxt)
        bad = (nxt >= cm.max_devices) | \
              ((ntype != target_type) & ~_bucket_ok(cm, nxt))
        hit = (ntype == target_type) & (nxt < cm.max_devices)
        code2 = jnp.where(empty, _EMPTY,
                          jnp.where(bad, _BAD,
                                    jnp.where(hit, _HIT, code)))
        done2 = empty | bad | hit
        cur2 = jnp.where(done2, cur, nxt)
        item2 = jnp.where(hit & ~empty, nxt, item)
        return (cur2, item2, code2, done2, depth + 1)

    cur, item, code, done, _ = lax.while_loop(
        cond, body,
        (start_item, jnp.int32(0), jnp.int32(_BAD), jnp.bool_(False),
         jnp.int32(0)))
    # depth exhaustion counts as BAD (cannot happen on well-formed maps)
    code = jnp.where(done, code, _BAD)
    return item, cur, code


def _firstn_rep(cm, x, take_item, weight, rep, parent_r, target_type,
                out_arr, outpos, tries, local_retries, vary_r, stable,
                recurse_tries, recurse_to_leaf, out2_arr, result_max):
    """One replica of crush_choose_firstn (mapper.c:460-645): descend,
    reject/collide retry cascade.  Returns (item, leaf, skipped)."""
    pos_idx = jnp.arange(result_max)

    def cond(st):
        in_item, ftotal, flocal, item, leaf, done, skipped = st
        return ~done

    def body(st):
        in_item, ftotal, flocal, item, leaf, done, skipped = st
        r = rep + parent_r + ftotal
        item_n, parent, code = _descend(cm, x, r, in_item, target_type,
                                        outpos)
        bad = code == _BAD          # → skip this replica (no retry)
        empty = code == _EMPTY      # → reject (retry path)
        ok = code == _HIT
        collide = ok & jnp.any((pos_idx < outpos) & (out_arr == item_n))
        if recurse_to_leaf:
            sub_r = (r >> (vary_r - 1)) if vary_r else jnp.int32(0)
            rep_eff = jnp.int32(0) if stable else outpos
            leaf_n, leaf_ok = _leaf_firstn(
                cm, x, item_n, weight, rep_eff, sub_r, recurse_tries,
                local_retries, out2_arr, outpos, result_max)
            leaf_ok = leaf_ok | (item_n >= 0)
            leaf_n = jnp.where(item_n >= 0, item_n, leaf_n)
        else:
            leaf_n, leaf_ok = jnp.int32(0), jnp.bool_(True)
        reject = empty | (ok & ~collide & (
            ~leaf_ok |
            ((_item_type(cm, item_n) == 0) &
             _is_out(cm, weight, item_n, x))))
        fail = reject | collide
        ftotal2 = ftotal + fail
        flocal2 = flocal + fail
        local_retry = collide & (flocal2 <= local_retries)
        redescent = fail & ~local_retry & (ftotal2 < tries)
        succ = ok & ~fail
        done2 = succ | bad | (fail & ~local_retry & ~redescent)
        skipped2 = bad | (fail & done2)
        in_next = jnp.where(local_retry, parent, take_item)
        flocal3 = jnp.where(local_retry, flocal2, 0)
        return (in_next, ftotal2, flocal3,
                jnp.where(succ, item_n, item),
                jnp.where(succ, leaf_n, leaf),
                done2, skipped2)

    st0 = (take_item, jnp.int32(0), jnp.int32(0), jnp.int32(0),
           jnp.int32(0), jnp.bool_(False), jnp.bool_(False))
    _, _, _, item, leaf, _, skipped = lax.while_loop(cond, body, st0)
    return item, leaf, skipped


def _leaf_firstn(cm, x, bucket_item, weight, rep_eff, parent_r, tries,
                 local_retries, out2_arr, outpos, result_max):
    """Inner chooseleaf descent (mapper.c:566-595 → one-replica recursive
    crush_choose_firstn with type 0, no further recursion).
    Returns (leaf, success)."""
    pos_idx = jnp.arange(result_max)

    def cond(st):
        in_item, ftotal, flocal, item, done, succ = st
        return ~done

    def body(st):
        in_item, ftotal, flocal, item, done, succ = st
        r = rep_eff + parent_r + ftotal
        item_n, parent, code = _descend(cm, x, r, in_item, 0, outpos)
        bad = code == _BAD
        empty = code == _EMPTY
        ok = code == _HIT
        collide = ok & jnp.any((pos_idx < outpos) & (out2_arr == item_n))
        reject = empty | (ok & ~collide & _is_out(cm, weight, item_n, x))
        fail = reject | collide
        ftotal2 = ftotal + fail
        flocal2 = flocal + fail
        local_retry = collide & (flocal2 <= local_retries)
        redescent = fail & ~local_retry & (ftotal2 < tries)
        s = ok & ~fail
        done2 = s | bad | (fail & ~local_retry & ~redescent)
        in_next = jnp.where(local_retry, parent, bucket_item)
        flocal3 = jnp.where(local_retry, flocal2, 0)
        return (in_next, ftotal2, flocal3,
                jnp.where(s, item_n, item), done2, s)

    st0 = (bucket_item, jnp.int32(0), jnp.int32(0), jnp.int32(0),
           jnp.bool_(False), jnp.bool_(False))
    _, _, _, item, _, succ = lax.while_loop(cond, body, st0)
    return item, succ


def _choose_firstn(cm, x, take_item, weight, numrep, target_type,
                   count0, tries, recurse_tries, local_retries,
                   recurse_to_leaf, vary_r, stable, result_max):
    """crush_choose_firstn over all replicas of one take segment.  The
    C core hands each take item a fresh output segment (o+osize, j=0,
    mapper.c:1038-1043), so the segment always starts at position 0 and
    `rep = 0 .. numrep-1` regardless of the stable tunable.  Returns
    (seg_out, seg_out2, got)."""
    pos_idx = jnp.arange(result_max)
    out = jnp.zeros((result_max,), dtype=jnp.int32)
    out2 = jnp.zeros((result_max,), dtype=jnp.int32)
    outpos = jnp.int32(0)
    count = count0
    for rep_off in range(numrep):
        active = count > 0
        item, leaf, skipped = _firstn_rep(
            cm, x, take_item, weight, jnp.int32(rep_off), jnp.int32(0),
            target_type, out, outpos, tries, local_retries, vary_r,
            stable, recurse_tries, recurse_to_leaf, out2, result_max)
        write = active & ~skipped
        out = jnp.where(write & (pos_idx == outpos), item, out)
        if recurse_to_leaf:
            out2 = jnp.where(write & (pos_idx == outpos), leaf, out2)
        outpos = outpos + write
        count = count - write
    return out, out2, outpos


def _leaf_indep(cm, x, bucket_item, weight, numrep, parent_r, tries,
                rep):
    """Inner chooseleaf descent for indep (mapper.c:781-790 → one-slot
    recursive crush_choose_indep, type 0).  Returns leaf or NONE."""
    def cond(st):
        ft, leaf, done = st
        return (~done) & (ft < tries)

    def body(st):
        ft, leaf, done = st
        r = rep + parent_r + numrep * ft
        item, parent, code = _descend(cm, x, r, bucket_item, 0, rep)
        ok = code == _HIT
        hard = code == _BAD
        reject = ok & _is_out(cm, weight, item, x)
        good = ok & ~reject
        # hard failure fills the slot with NONE permanently
        leaf2 = jnp.where(good, item,
                          jnp.where(hard, jnp.int32(CRUSH_ITEM_NONE), leaf))
        return (ft + 1, leaf2, good | hard)

    _, leaf, done = lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.int32(CRUSH_ITEM_NONE), jnp.bool_(False)))
    return leaf


def _choose_indep(cm, x, take_item, weight, left0, numrep, target_type,
                  tries, recurse_tries, recurse_to_leaf, result_max):
    """crush_choose_indep (mapper.c:655-830) over one take segment
    (segment-relative positions, see _choose_firstn): breadth-first,
    positionally stable; holes become CRUSH_ITEM_NONE.
    Returns (seg_out, seg_out2) with slots [0, left0) filled."""
    pos_idx = jnp.arange(result_max)
    in_range = pos_idx < left0
    out = jnp.where(in_range, CRUSH_ITEM_UNDEF, 0).astype(jnp.int32)
    out2 = jnp.where(in_range, CRUSH_ITEM_UNDEF, 0).astype(jnp.int32)
    endpos = left0
    outpos = jnp.int32(0)

    def round_body(st):
        out, out2, left, ftotal = st

        def slot(carry, rep_off):
            out, out2, left = carry
            rep = rep_off.astype(jnp.int32)
            slot_val = out[jnp.minimum(rep, result_max - 1)]
            todo = (rep < endpos) & (slot_val == CRUSH_ITEM_UNDEF)
            rr = rep + numrep * ftotal
            item, parent, code = _descend(cm, x, rr, take_item,
                                          target_type, outpos)
            ok = code == _HIT
            hard = code == _BAD  # → NONE immediately (mapper.c:731,758)
            collide = ok & jnp.any(in_range & (out == item))
            if recurse_to_leaf:
                leaf = jnp.where(
                    item < 0,
                    _leaf_indep(cm, x, item, weight, numrep, rr,
                                recurse_tries, rep),
                    item)
                leaf_fail = (item < 0) & (leaf == CRUSH_ITEM_NONE)
            else:
                leaf = jnp.int32(0)
                leaf_fail = jnp.bool_(False)
            reject = ok & ((_item_type(cm, item) == 0) &
                           _is_out(cm, weight, item, x))
            good = ok & ~collide & ~leaf_fail & ~reject
            sel = pos_idx == rep
            out = jnp.where(todo & sel & good, item, out)
            out = jnp.where(todo & sel & hard,
                            jnp.int32(CRUSH_ITEM_NONE), out)
            if recurse_to_leaf:
                # C writes out2[rep] before the is_out check, so a
                # rejected device leaves a stale out2 entry
                # (mapper.c:791-793); and a failed bucket recursion
                # leaves out2[rep] = NONE.  Replicate both.
                stale = todo & sel & ok & ~collide & (
                    ((item >= 0) & reject) | leaf_fail)
                out2 = jnp.where(todo & sel & good, leaf, out2)
                out2 = jnp.where(stale, jnp.where(leaf_fail,
                                                  jnp.int32(CRUSH_ITEM_NONE),
                                                  item), out2)
                out2 = jnp.where(todo & sel & hard,
                                 jnp.int32(CRUSH_ITEM_NONE), out2)
            left = left - (todo & (good | hard))
            return (out, out2, left), None

        (out, out2, left), _ = lax.scan(
            slot, (out, out2, left), jnp.arange(result_max))
        return out, out2, left, ftotal + 1

    def round_cond(st):
        _, _, left, ftotal = st
        return (left > 0) & (ftotal < tries)

    out, out2, left, _ = lax.while_loop(
        round_cond, round_body, (out, out2, left0, jnp.int32(0)))
    out = jnp.where(in_range & (out == CRUSH_ITEM_UNDEF),
                    CRUSH_ITEM_NONE, out)
    out2 = jnp.where(in_range & (out2 == CRUSH_ITEM_UNDEF),
                     CRUSH_ITEM_NONE, out2)
    return out, out2


# ---------------------------------------------------------------------------
# rule interpreter (steps are static; state is traced)

def _do_rule_one(cm, static: _StaticCfg, x, weight):
    """do_rule (mapper.c:900-1105) for one input x.  cm is a _CmView
    (arrays are traced jit arguments); every rule decision comes from
    the static config so the executable caches across map epochs."""
    result_max = static.result_max
    tries = static.tries
    leaf_tries = 0
    local_retries = static.local_retries
    vary_r = static.vary_r
    stable = static.stable

    x = jnp.asarray(x, dtype=jnp.int64)
    result = jnp.full((result_max,), CRUSH_ITEM_NONE, dtype=jnp.int32)
    rcount = jnp.int32(0)
    w_items = jnp.zeros((result_max,), dtype=jnp.int32)
    w_count = jnp.int32(0)
    w_max = 0  # static upper bound on w_count
    pos_idx = jnp.arange(result_max)
    safe_bucket = jnp.int32(static.first_valid)

    for op, arg1, arg2, take_ok in static.steps:
        if op == CRUSH_RULE_TAKE:
            if take_ok:
                w_items = w_items.at[0].set(arg1)
                w_count = jnp.int32(1)
                w_max = 1
        elif op == CRUSH_RULE_SET_CHOOSE_TRIES:
            if arg1 > 0:
                tries = arg1
        elif op == CRUSH_RULE_SET_CHOOSELEAF_TRIES:
            if arg1 > 0:
                leaf_tries = arg1
        elif op == CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES:
            if arg1 >= 0:
                local_retries = arg1
        elif op == CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES:
            if arg1 > 0:
                raise BatchUnsupported("set_choose_local_fallback_tries > 0")
        elif op == CRUSH_RULE_SET_CHOOSELEAF_VARY_R:
            if arg1 >= 0:
                vary_r = arg1
        elif op == CRUSH_RULE_SET_CHOOSELEAF_STABLE:
            if arg1 >= 0:
                stable = arg1
        elif op in (CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_CHOOSE_INDEP,
                    CRUSH_RULE_CHOOSELEAF_FIRSTN,
                    CRUSH_RULE_CHOOSELEAF_INDEP):
            firstn = op in (CRUSH_RULE_CHOOSE_FIRSTN,
                            CRUSH_RULE_CHOOSELEAF_FIRSTN)
            recurse = op in (CRUSH_RULE_CHOOSELEAF_FIRSTN,
                             CRUSH_RULE_CHOOSELEAF_INDEP)
            numrep = arg1
            if numrep <= 0:
                numrep += result_max
            o = jnp.zeros((result_max,), dtype=jnp.int32)
            c = jnp.zeros((result_max,), dtype=jnp.int32)
            osize = jnp.int32(0)
            if firstn:
                if leaf_tries:
                    recurse_tries = leaf_tries
                elif static.descend_once:
                    recurse_tries = 1
                else:
                    recurse_tries = tries
            else:
                recurse_tries = leaf_tries if leaf_tries else 1
            # numrep <= 0 after adjustment skips every take item but the
            # o/w swap still empties w (mapper.c:1010-1015,1077-1081)
            for wi in (range(w_max) if numrep > 0 else ()):
                wi_item = w_items[wi]
                wi_ok = (jnp.int32(wi) < w_count) & _bucket_ok(cm, wi_item)
                # masked execution: run the choose from a safe bucket
                # unconditionally, discard results when wi is invalid.
                # each take item writes a fresh segment spliced at osize
                # (C passes o+osize with j=0, mapper.c:1038-1070)
                take = jnp.where(wi_ok, wi_item, safe_bucket)
                if firstn:
                    seg_o, seg_c, got = _choose_firstn(
                        cm, x, take, weight, numrep, arg2,
                        result_max - osize, tries, recurse_tries,
                        local_retries, recurse, vary_r, stable,
                        result_max)
                else:
                    got = jnp.minimum(jnp.int32(numrep),
                                      result_max - osize)
                    seg_o, seg_c = _choose_indep(
                        cm, x, take, weight, got, numrep, arg2,
                        tries, recurse_tries, recurse, result_max)
                got = jnp.where(wi_ok, got, 0)
                seg_idx = jnp.clip(pos_idx - osize, 0, result_max - 1)
                mask = (pos_idx >= osize) & (pos_idx < osize + got)
                o = jnp.where(mask, seg_o[seg_idx], o)
                c = jnp.where(mask, seg_c[seg_idx], c)
                osize = osize + got
            if recurse:
                o = jnp.where(pos_idx < osize, c, o)
            w_items = o
            w_count = osize
            w_max = (min(result_max, max(w_max * numrep, 1))
                     if numrep > 0 else 0)
        elif op == CRUSH_RULE_EMIT:
            # gather formulation (result[p] = w[p - rcount] for the
            # emitted range) rather than a scatter with computed
            # indices: the scatter form miscompiles on the TPU backend
            # when o/c are dead after this step (wrong operand survives
            # fusion/DCE); the gather form is also cheaper
            src_idx = jnp.clip(pos_idx - rcount, 0, result_max - 1)
            emit = (pos_idx >= rcount) & ((pos_idx - rcount) < w_count)
            result = jnp.where(emit, w_items[src_idx], result)
            rcount = jnp.minimum(rcount + w_count, result_max)
            w_items = jnp.zeros((result_max,), dtype=jnp.int32)
            w_count = jnp.int32(0)
            w_max = 0
    return result, rcount
