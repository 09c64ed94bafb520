"""The closed loop of `rados bench` (upstream obj_bencher.cc), as
ceph_tpu/tools/rados_cli.py `_bench` copies it, with its faults fixed:

- each op is timed from its own submission to its own completion, not
  when it reaches the head of a FIFO;
- every op's answer is checked by the caller's `finish`;
- failed ops are counted against attempted ones.

`depth` client threads each keep one op in flight: a thread submits,
waits for its op, and submits the next until the window's deadline.
The window ends when the last op in flight has completed, so its
length covers all the work counted in it.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field


@dataclass
class Op:
    index: int
    start: float
    end: float = 0.0
    ok: bool = False
    came: bool = False


@dataclass
class LoopResult:
    ops: list = field(default_factory=list)
    t0: float = 0.0
    t1: float = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def closed_loop(submit, finish, depth: int, seconds: float,
                timeout: float, annotate=None) -> LoopResult:
    """submit(i) -> future with wait(timeout); finish(i, future) -> bool
    says whether op i's answer is right.  Returns every op attempted."""
    counter = itertools.count()
    lock = threading.Lock()
    res = LoopResult()
    go = threading.Event()

    def span(name):
        return annotate(name) if annotate is not None else \
            contextlib.nullcontext()

    def client() -> None:
        go.wait()
        deadline = res.t0 + seconds
        while time.monotonic() < deadline:
            with lock:
                i = next(counter)
            op = Op(i, time.monotonic())
            with lock:
                res.ops.append(op)
            try:
                with span("submit"):
                    fut = submit(i)
                with span("wait"):
                    fut.wait(timeout)
                op.end = time.monotonic()
                op.came = True
            except TimeoutError:
                op.end = time.monotonic()
                continue
            with span("check"):
                op.ok = bool(finish(i, fut))

    threads = [threading.Thread(target=client, name=f"bench-client-{j}",
                                daemon=True) for j in range(depth)]
    for t in threads:
        t.start()
    res.t0 = time.monotonic()
    go.set()
    for t in threads:
        t.join()
    res.t1 = max([res.t0] + [op.end for op in res.ops])
    return res


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile (the sample's own value, no
    interpolation): ceil(q * n)-th smallest."""
    s = sorted(values)
    rank = max(1, -(-int(round(q * 1000)) * len(s) // 1000))
    return s[min(rank, len(s)) - 1]
