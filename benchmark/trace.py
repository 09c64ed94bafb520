"""From a jax.profiler trace to device busy time, idle gaps and the
device ops that took most time.

`load_dir` reads the .xplane.pb the profiler wrote into plain event
tuples; `reduce` works on those alone, so tests check it on a small
recorded fixture (benchmark/tests/fixtures/).

The profiled interval is the benchmark's own `profiled_window` host
annotation.  Device events are those of the "XLA Ops" line of each
/device:TPU:<n> plane (every line of the plane where it has none).
Busy time is the union of their intervals inside the window, averaged
over the chips used; an idle gap is named by the benchmark host span
(submit, wait, check, update, ...) that overlaps it most.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "profiled_window"
HOST_SPANS = ("submit", "wait", "check", "incremental", "update")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load_dir(path: str) -> dict:
    """Events of the one .xplane.pb under `path`."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane.pb under {path}, "
                           f"found {len(files)}")
    pd = ProfileData.from_file(files[0])
    device: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            device[m.group(1)] = [
                (op_name(ev.name), ev.start_ns,
                 ev.start_ns + ev.duration_ns)
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW or ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return {"device": device, "host": host}


def op_name(text: str) -> str:
    """An HLO op's name without its shapes and operands:
    '%gf_matmul_pallas_grouped.1 = u8[...] custom-call(...)' ->
    'gf_matmul_pallas_grouped.1'."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(events: dict, chips: int = 1, top: int = 10) -> dict | None:
    """busy_s, window_s, the top device ops and the longest idle gaps.
    None when the trace holds no window or no device event in it."""
    wins = [(a, b) for n, a, b in events["host"] if n == WINDOW]
    if not wins:
        return None
    w0, w1 = wins[0][0], wins[0][1]
    per_chip = sorted(events["device"].items())[:chips]
    busy_ns = 0.0
    by_name: dict[str, float] = {}
    gaps: list = []
    for _, evs in per_chip:
        clipped = [(max(a, w0), min(b, w1), n) for n, a, b in evs
                   if b > w0 and a < w1]
        for a, b, n in clipped:
            by_name[n] = by_name.get(n, 0.0) + (b - a)
        merged = union((a, b) for a, b, _ in clipped)
        busy_ns += sum(b - a for a, b in merged)
        edge = w0
        for a, b in merged:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
        if edge < w1:
            gaps.append((edge, w1))
    if busy_ns <= 0:
        return None
    spans = [(n, a, b) for n, a, b in events["host"] if n != WINDOW]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    nchips = max(1, len(per_chip))
    return {
        "busy_s": busy_ns / nchips / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, t / nchips / 1e9] for n, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_name(g, spans), (g[1] - g[0]) / 1e9]
                      for g in longest],
    }


def _name(gap, spans) -> str:
    a, b = gap
    best, best_ov = "no benchmark span", 0
    for n, sa, sb in spans:
        ov = min(b, sb) - max(a, sa)
        if ov > best_ov:
            best, best_ov = n, ov
    return best
