"""EC stripe math + per-shard integrity hashes — the ECUtil analogue.

Three pieces (ref: src/osd/ECUtil.{h,cc}):

* `StripeInfo` — the logical<->chunk offset algebra of `stripe_info_t`
  (ECUtil.h:27-79), verbatim semantics (pure integer math).
* `encode` / `decode` / `decode_concat` — stripe-batched plugin
  dispatch.  Where the reference loops stripe-by-stripe through the
  plugin (ECUtil.cc:120-159 encode, :9/:47 decode), the TPU build
  reshapes the whole buffer to (stripes, planes, chunk / planes) and
  runs ONE batched device dispatch (`encode_batch`/`decode_batch`) when
  the plugin has that API: `tpu`, `lrc` whose layers are byte matrix
  codes (the chunk mapping is honoured), and `clay` over a byte matrix
  code, whose chunks are q^t sub-chunk planes.  A decode stages the
  planes the plugin's own minimum_to_decode names of the survivors it
  picks: whole chunks, or a clay helper's repair planes.  Plugins
  without that API (isa, jerasure, shec, and profiles no byte probe can
  lower) take the per-stripe loop.
* `HashInfo` — cumulative per-shard crc32c (ECUtil.cc:161 append), the
  xattr-stored integrity metadata ECBackend checks on every sub-read.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterable, Mapping

import numpy as np

from ..common import jaxguard
from ..common.crc32c import crc32c


class StripeInfo:
    """Offset algebra between the logical object stream and per-shard
    chunk space (ref: ECUtil.h:27-79 stripe_info_t).

    stripe_size = k (data chunk count), stripe_width = k * chunk_size.
    """

    def __init__(self, stripe_size: int, stripe_width: int):
        if stripe_width % stripe_size != 0:
            raise ValueError("stripe_width must be divisible by stripe_size")
        self.stripe_width = stripe_width
        self.chunk_size = stripe_width // stripe_size

    def logical_offset_is_stripe_aligned(self, logical: int) -> bool:
        return logical % self.stripe_width == 0

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return ((offset + self.stripe_width - 1) // self.stripe_width) \
            * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset - rem + self.stripe_width if rem else offset

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def aligned_offset_len_to_chunk(
            self, off_len: tuple[int, int]) -> tuple[int, int]:
        off, length = off_len
        return (self.aligned_logical_offset_to_chunk_offset(off),
                self.aligned_logical_offset_to_chunk_offset(length))

    def offset_len_to_stripe_bounds(
            self, off_len: tuple[int, int]) -> tuple[int, int]:
        off, length = off_len
        start = self.logical_to_prev_stripe_offset(off)
        full_len = self.logical_to_next_stripe_offset((off - start) + length)
        return (start, full_len)


def batchable(ec) -> bool:
    """Whether encode/decode take the one-dispatch device path: the
    plugin has a batched API (a profile that cannot be lowered sets it
    to None)."""
    return getattr(ec, "encode_batch", None) is not None


def decode_extents(ec, missing, avail) -> dict[int, list[tuple[int, int]]]:
    """The survivors a batched decode of `missing` stages, each with
    its (sub-chunk offset, count) extents: the plugin's
    minimum_to_decode over `avail` (for a matrix code its first k
    whole, for lrc the layered walk's, e.g. one local group, for clay
    one lost chunk's d helpers' repair planes)."""
    return ec.minimum_to_decode(set(missing), set(avail))


def staged_planes(extents: Mapping[int, list[tuple[int, int]]]) -> int:
    """Sub-chunk planes per stripe a decode over `extents` stages."""
    return sum(c for ext in extents.values() for _, c in ext)


#: the regions of one timed EC call, in order.  On the batched path:
#: stage (host gather into the dispatch layout), program (only where
#: the plugin builds a program per signature and this call missed its
#: cache: probes and kernel construction), h2d (jax.device_put until
#: ready), device (dispatch until ready), d2h (np.asarray), unstage
#: (per-shard tobytes, decode_concat's stack).  The per-stripe path of
#: host-native plugins has no device: it reports one `kernel`.
STAGES = ("stage", "program", "h2d", "device", "d2h", "unstage", "kernel")


class _Stages:
    """Times the consecutive regions of one EC call into `timings`.

    With `timings` None every method does nothing, so an untimed call
    issues the same transfers and dispatches in the same order; a timed
    one adds only the waits that end its h2d and device regions.  A
    caller may put a "trace_id" into `timings`: the device region then
    also runs inside a profiler annotation `ec_device` carrying it, so
    a device trace shows each call's dispatch beside the device ops it
    launched, and the same interval on both clocks."""

    __slots__ = ("timings", "last")

    def __init__(self, timings: dict | None):
        self.timings = timings
        self.last = time.monotonic() if timings is not None else 0.0

    def mark(self, name: str, ready=None) -> None:
        """Close region `name` now (after `ready` is ready)."""
        if self.timings is None:
            return
        if ready is not None:
            import jax
            jax.block_until_ready(ready)
        now = time.monotonic()
        self.timings[name] = (self.last, now)
        self.last = now

    @contextlib.contextmanager
    def device(self):
        if self.timings is None:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(
                "ec_device", trace_id=self.timings.get("trace_id", "")):
            self.last = time.monotonic()
            yield


def _program(ec, st: _Stages, *signature) -> None:
    """Build the program of this call's signature ahead of staging it
    to the device, where the plugin builds one per signature
    (`batch_program`); a cache miss becomes the `program` region."""
    build = getattr(ec, "batch_program", None)
    if build is not None and build(*signature)[1]:
        st.mark("program")


def encode(sinfo: StripeInfo, ec, data: bytes,
           want: Iterable[int] | None = None,
           timings: dict | None = None) -> dict[int, bytes]:
    """Encode a stripe-aligned logical buffer into per-shard chunk
    streams (ref: ECUtil.cc:120-159).

    Returns {shard: bytes} where each shard's buffer is the
    concatenation of that shard's chunk from every stripe.  One batched
    device dispatch for matrix plugins; per-stripe plugin.encode
    otherwise.  `timings`, when passed, receives a monotonic interval
    per region of STAGES the call ran (see _Stages).
    """
    k = ec.get_data_chunk_count()
    m = ec.get_coding_chunk_count()
    n = k + m
    if want is None:
        want = range(n)
    want = set(want)
    if len(data) % sinfo.stripe_width != 0:
        raise ValueError("logical size must be stripe-aligned")
    if not data:
        return {}
    nstripes = len(data) // sinfo.stripe_width
    cs = sinfo.chunk_size

    if batchable(ec):
        import jax
        st = _Stages(timings)
        sub = ec.get_sub_chunk_count()
        arr = np.frombuffer(data, dtype=np.uint8).reshape(nstripes, k, cs)
        st.mark("stage")
        _program(ec, st)
        # the one legal host->device crossing of the encode path is
        # this explicit staging (of the planes: a free view); under
        # CEPH_TPU_JAXGUARD any IMPLICIT transfer inside the dispatch
        # is an error
        dev = jax.device_put(arr.reshape(nstripes, k * sub, cs // sub))
        st.mark("h2d", dev)
        with st.device():
            with jaxguard.guard_transfers():
                parity_dev = ec.encode_batch(dev)
            st.mark("device", parity_dev)
        parity = np.asarray(parity_dev).reshape(nstripes, m, cs)
        st.mark("d2h")
        out: dict[int, bytes] = {}
        # data chunk i sits at shard chunk_index(i), coding row j at
        # chunk_index(k + j); tobytes() emits C-order bytes from a
        # strided view directly — an ascontiguousarray here would copy
        # each shard slice twice
        for i in range(n):
            shard = ec.chunk_index(i)
            if shard in want:
                out[shard] = (arr[:, i, :] if i < k
                              else parity[:, i - k, :]).tobytes()
        st.mark("unstage")
        return out

    # general path: per-stripe plugin encode (handles chunk remapping)
    t0 = time.monotonic()
    parts: dict[int, list] = {i: [] for i in want}
    for s in range(nstripes):
        stripe = data[s * sinfo.stripe_width:(s + 1) * sinfo.stripe_width]
        encoded = ec.encode(want, stripe)
        for i in want:
            chunk = encoded[i]
            assert len(chunk) == cs
            # this per-stripe fallback only serves host-native (numpy)
            # plugins; batchable device plugins take the one-dispatch
            # path above, so no device boundary is crossed here
            # cephck: ignore[host-sync-hot-path] — host-native plugin path
            parts[i].append(np.asarray(chunk, dtype=np.uint8))
    out = {i: np.concatenate(parts[i]).tobytes() for i in want}
    if timings is not None:
        timings["kernel"] = (t0, time.monotonic())
    return out


def decode_concat(sinfo: StripeInfo, ec,
                  to_decode: Mapping[int, bytes],
                  timings: dict | None = None) -> bytes:
    """Rebuild the logical stream from the shards' chunk streams
    (ref: ECUtil.cc:9 decode -> decode_concat per stripe).

    `timings`, when passed, receives a monotonic interval per region
    of STAGES the call ran (see _Stages): the host survivor staging,
    both transfers and the device decode when data shards are missing,
    and `unstage` for the per-shard tobytes plus the stack into the
    logical stream (a read with every data shard runs only that)."""
    if not to_decode:
        raise ValueError("decode of no shards")
    lengths = {len(v) for v in to_decode.values()}
    if len(lengths) != 1:
        raise ValueError("shard buffers differ in length")
    total = lengths.pop()
    if total % sinfo.chunk_size != 0:
        raise ValueError("shard length not chunk-aligned")
    if total == 0:
        return b""
    k = ec.get_data_chunk_count()
    nstripes = total // sinfo.chunk_size
    cs = sinfo.chunk_size

    if batchable(ec):
        # data chunk i is shard chunk_index(i): decode the missing ones,
        # then stack all k in mapping order
        data_shards = [ec.chunk_index(i) for i in range(k)]
        out = decode(sinfo, ec, to_decode, want=data_shards,
                     timings=timings)
        t0 = time.monotonic() if timings is not None else 0.0
        arrs = [np.frombuffer(out[s], dtype=np.uint8).reshape(nstripes, cs)
                for s in data_shards]
        logical = np.ascontiguousarray(
            np.stack(arrs, axis=1)).tobytes()  # (S, k, cs) -> logical
        if timings is not None:
            # one unstage region: decode's tobytes (if it decoded) and
            # this stack
            timings["unstage"] = (timings.get("unstage", (t0,))[0],
                                  time.monotonic())
        return logical

    # general path: the plugin's decode_concat knows the chunk mapping
    # (ref: ECUtil.cc:31 per-stripe ec_impl->decode_concat)
    t0 = time.monotonic()
    views = {i: np.frombuffer(v, dtype=np.uint8)
             for i, v in to_decode.items()}
    parts = []
    for s in range(nstripes):
        chunks = {i: v[s * cs:(s + 1) * cs] for i, v in views.items()}
        stripe = ec.decode_concat(chunks)
        assert len(stripe) == sinfo.stripe_width
        parts.append(stripe)
    if timings is not None:       # per-stripe path: no separate stage
        timings["kernel"] = (t0, time.monotonic())
    return b"".join(parts)


def decode(sinfo: StripeInfo, ec, to_decode: Mapping[int, bytes],
           want: Iterable[int],
           timings: dict | None = None) -> dict[int, bytes]:
    """Reconstruct the `want` shards' chunk streams from available
    shard streams (ref: ECUtil.cc:47 decode(map out)).

    Batched: a single device dispatch reconstructs every stripe's
    missing chunks from the survivor planes decode_extents names.  `timings`
    (optional dict) receives a monotonic interval per region of STAGES
    it ran.
    """
    want = sorted(set(want))
    avail = sorted(to_decode)
    if not to_decode:
        raise ValueError("decode of no shards")
    lengths = {len(v) for v in to_decode.values()}
    if len(lengths) != 1:
        raise ValueError("shard buffers differ in length")
    total = lengths.pop()
    if total == 0:
        return {i: b"" for i in want}
    cs = sinfo.chunk_size
    if total % cs != 0:
        raise ValueError("shard length not chunk-aligned")
    nstripes = total // cs

    have = [i for i in want if i in to_decode]
    missing = [i for i in want if i not in to_decode]

    out: dict[int, bytes] = {i: to_decode[i] for i in have}
    if not missing:
        return out

    if batchable(ec):
        import jax
        st = _Stages(timings)
        sub = ec.get_sub_chunk_count()
        extents = decode_extents(ec, missing, avail)
        decode_index = sorted(extents)
        # (S, planes, cs / sub): each survivor's planes in its extents'
        # order, survivors ascending
        stack = np.empty((nstripes, staged_planes(extents), cs // sub),
                         dtype=np.uint8)
        row = 0
        for i in decode_index:
            planes = np.frombuffer(to_decode[i], dtype=np.uint8) \
                .reshape(nstripes, sub, cs // sub)
            for off, count in extents[i]:
                stack[:, row:row + count] = planes[:, off:off + count]
                row += count
        st.mark("stage")
        _program(ec, st, decode_index, missing)
        dev = jax.device_put(stack)
        st.mark("h2d", dev)
        # the guard makes any implicit transfer inside the dispatch an
        # error under CEPH_TPU_JAXGUARD
        with st.device():
            with jaxguard.guard_transfers():
                rec_dev = ec.decode_batch(decode_index, missing, dev)
            st.mark("device", rec_dev)
        rec = np.asarray(rec_dev).reshape(nstripes, len(missing), cs)
        st.mark("d2h")
        for pos, i in enumerate(missing):
            # tobytes() handles the strided view; rec was synced once
            # above, so this loop is host memcpy only
            out[i] = rec[:, pos, :].tobytes()
        st.mark("unstage")
        return out

    # general path: per-stripe plugin decode
    t0 = time.monotonic()
    parts: dict[int, list] = {i: [] for i in missing}
    for s in range(nstripes):
        chunks = {i: np.frombuffer(v, dtype=np.uint8)[s * cs:(s + 1) * cs]
                  for i, v in to_decode.items()}
        decoded = ec.decode(set(want), chunks, cs)
        for i in missing:
            # only non-batchable (host-native numpy) plugins reach this
            # per-stripe path: the asarray never crosses a device boundary
            # cephck: ignore[host-sync-hot-path] — host-native plugin path
            parts[i].append(np.asarray(decoded[i], dtype=np.uint8))
    for i in missing:
        out[i] = np.concatenate(parts[i]).tobytes()
    if timings is not None:       # per-stripe path: no separate stage
        timings["kernel"] = (t0, time.monotonic())
    return out


# ---------------------------------------------------------------- repair
# Sub-chunk (network-optimal) single-shard repair: regenerating codes
# (clay) rebuild one lost chunk from q^(t-1)-of-q^t sub-chunk ranges
# of d helpers instead of k whole chunks (ref: ErasureCodeClay.cc:364
# get_repair_subchunks; "Fast Product-Matrix Regenerating Codes",
# arxiv 1412.3022).  These helpers translate the plugin's sub-chunk
# plan into byte extents over shard chunk STREAMS (many stripes per
# object) and drive the per-stripe repair decode.


def supports_subchunk_repair(ec) -> bool:
    """True when the plugin can rebuild a single shard from partial
    (sub-chunk) helper reads.  Non-regenerating plugins and
    sub_chunk_count == 1 codes fall back to full-chunk recovery.
    (Plan-driven recovery — repair_plan below — supersedes this gate
    for the OSD paths; it remains the sub-chunk capability probe.)"""
    return (ec.get_sub_chunk_count() > 1
            and hasattr(ec, "is_repair")
            and hasattr(ec, "minimum_to_repair")
            and hasattr(ec, "get_repair_subchunks"))


def repair_plan(ec, lost, avail):
    """The plugin's partial-read repair plan (ec.repair_schedule) for
    this erasure signature, or None — the caller then takes wholesale
    full-chunk recovery.  A plan names the helper shards, each
    helper's sub-chunk extents, and feeds the repair-schedule compiler
    (ceph_tpu.ec.repairc): clay ships q^(t-1)/q^t repair planes of d
    helpers, lrc the l whole chunks of the lost shard's local parity
    group, matrix codes k whole survivor chunks decoded straight to
    the lost shards."""
    from ..ec.interface import ErasureCodeError
    hook = getattr(ec, "repair_schedule", None)
    if hook is None:
        return None
    try:
        return hook(set(lost), set(avail))
    except ErasureCodeError:
        return None


def compiled_repair_streams(ec, plan, chunk_size: int,
                            helper_bufs: Mapping[int, bytes],
                            backend: str | None = None
                            ) -> dict[int, bytes]:
    """Rebuild every lost shard's chunk stream through the plan's
    compiled program (cached per erasure signature): gather the
    helpers' plane bytes, one grouped GF(2^8) matmul, scatter.
    Byte-identical to the interpreted decode path (pinned by the
    tests/test_repairc.py parity sweep)."""
    from ..ec.repairc import program_for
    return program_for(ec, plan).run(helper_bufs, chunk_size,
                                     backend=backend)


def repair_chunk_extents(ec, lost_shard: int,
                         chunk_size: int) -> list[tuple[int, int]]:
    """Byte extents WITHIN ONE CHUNK that helpers must serve to repair
    `lost_shard` (the plugin's sub-chunk plan scaled to bytes).  A
    shard stream repeats these per stripe (see ECSubRead.subchunks)."""
    sub_no = ec.get_sub_chunk_count()
    assert chunk_size % sub_no == 0
    ssz = chunk_size // sub_no
    nu = getattr(ec, "nu", 0)
    lost_node = lost_shard if lost_shard < ec.k else lost_shard + nu
    return [(idx * ssz, cnt * ssz)
            for idx, cnt in ec.get_repair_subchunks(lost_node)]


def expand_stream_extents(extents: list[tuple[int, int]],
                          chunk_size: int,
                          stream_len: int) -> list[tuple[int, int]]:
    """Per-chunk byte extents -> absolute extents over an
    nstripes x chunk_size shard stream."""
    if stream_len % chunk_size != 0:
        raise ValueError("shard stream not chunk-aligned")
    return [(s * chunk_size + off, length)
            for s in range(stream_len // chunk_size)
            for off, length in extents]


def repair_shard_stream(ec, chunk_size: int, lost_shard: int,
                        helper_bufs: Mapping[int, bytes]) -> bytes:
    """Rebuild `lost_shard`'s whole chunk stream from the helpers'
    CONCATENATED repair-plane bytes (one repair_blocksize block per
    stripe, as handle_sub_read assembles them).  Byte-identical to the
    chunk a full-decode + re-encode would produce."""
    extents = repair_chunk_extents(ec, lost_shard, chunk_size)
    rb = sum(length for _, length in extents)   # repair bytes / stripe
    lengths = {len(v) for v in helper_bufs.values()}
    if len(lengths) != 1:
        raise ValueError("helper repair buffers differ in length")
    total = lengths.pop()
    if rb == 0 or total % rb != 0:
        raise ValueError("helper buffer not repair-block aligned")
    nstripes = total // rb
    views = {s: np.frombuffer(v, dtype=np.uint8)
             for s, v in helper_bufs.items()}
    parts = []
    for st in range(nstripes):
        chunks = {s: v[st * rb:(st + 1) * rb] for s, v in views.items()}
        rebuilt = ec.decode({lost_shard}, chunks, chunk_size)
        # sub-chunk repair is the clay (host-native numpy) plugin's
        # path; no device array ever reaches this asarray
        # cephck: ignore[host-sync-hot-path] — host-native plugin path
        parts.append(np.asarray(rebuilt[lost_shard], dtype=np.uint8))
    return b"".join(p.tobytes() for p in parts)


class HashInfo:
    """Cumulative per-shard crc32c of everything ever appended to each
    shard (ref: ECUtil.cc:161 HashInfo::append; stored as an object
    xattr and checked by ECBackend::handle_sub_read ECBackend.cc:1059).

    Seed is -1 per shard (matching the reference's default-constructed
    cumulative_shard_hashes of (uint32_t)-1).
    """

    def __init__(self, num_chunks: int = 0):
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [0xFFFFFFFF] * num_chunks
        self.projected_total_chunk_size = 0

    def has_chunk_hash(self) -> bool:
        return bool(self.cumulative_shard_hashes)

    def append(self, old_size: int, to_append: Mapping[int, bytes]) -> None:
        if old_size != self.total_chunk_size:
            raise ValueError(
                f"append at {old_size} but shard size is "
                f"{self.total_chunk_size}")
        sizes = {len(v) for v in to_append.values()}
        if len(sizes) != 1:
            raise ValueError("shard appends differ in length")
        size_to_append = sizes.pop()
        if self.has_chunk_hash():
            if len(to_append) != len(self.cumulative_shard_hashes):
                raise ValueError("append must cover every shard")
            for shard, buf in to_append.items():
                self.cumulative_shard_hashes[shard] = crc32c(
                    self.cumulative_shard_hashes[shard], buf)
        self.total_chunk_size += size_to_append
        self.projected_total_chunk_size = max(
            self.projected_total_chunk_size, self.total_chunk_size)

    def append_shard(self, shard: int, old_size: int,
                     buf: bytes) -> None:
        """Shard-local cumulative append for the ICI-fabric path: the
        chunk bytes exist only on the shard that fetched them, so each
        shard advances ITS hash; other entries in this copy are never
        consulted on this shard (handle_sub_read and scrub both check
        `get_chunk_hash(self.shard)` only)."""
        if old_size != self.total_chunk_size:
            raise ValueError(
                f"append at {old_size} but shard size is "
                f"{self.total_chunk_size}")
        if self.has_chunk_hash():
            self.cumulative_shard_hashes[shard] = crc32c(
                self.cumulative_shard_hashes[shard], buf)
        self.total_chunk_size += len(buf)
        self.projected_total_chunk_size = max(
            self.projected_total_chunk_size, self.total_chunk_size)

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

    def get_total_chunk_size(self) -> int:
        return self.total_chunk_size

    # xattr codec (JSON-ish dict instead of the reference's binary
    # ENCODE_START framing; ref: ECUtil.cc:181 encode/decode)
    def to_dict(self) -> dict:
        return {"total_chunk_size": self.total_chunk_size,
                "cumulative_shard_hashes": list(
                    self.cumulative_shard_hashes)}

    @classmethod
    def from_dict(cls, d: dict) -> "HashInfo":
        hi = cls()
        hi.total_chunk_size = d["total_chunk_size"]
        hi.cumulative_shard_hashes = list(d["cumulative_shard_hashes"])
        hi.projected_total_chunk_size = hi.total_chunk_size
        return hi

    def __eq__(self, other) -> bool:
        return (isinstance(other, HashInfo)
                and self.total_chunk_size == other.total_chunk_size
                and self.cumulative_shard_hashes
                == other.cumulative_shard_hashes)

    def __repr__(self) -> str:
        hashes = " ".join(hex(h) for h in self.cumulative_shard_hashes)
        return f"HashInfo(tcs={self.total_chunk_size} {hashes})"
