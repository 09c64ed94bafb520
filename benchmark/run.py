"""Run one cell of BENCHMARK.json once and print its result line.

    python3 -m benchmark.run --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell names a configuration (benchmark/configs/<config>.json, whose
"driver" names a module of benchmark/drivers/) and a traffic mix
(benchmark/mixes/<traffic>.json); each per-layer metric is read by
benchmark/metrics/<name>.py.  All are found by name, so a later PR adds
a cell, a mix or a metric by adding files and entries only.

Set-up (cluster or map, payloads, warm-up of the cell's own shapes) is
`setup_s`; the window then runs for --seconds; afterwards the result is
checked against the plain references in benchmark/ref/.  With --trace 1
the program's spans are on and a few seconds of the window are
profiled; the line then carries the per-layer metrics.  There is no CPU
fallback: without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

#: host clock at import, the fallback for the process's start time
_IMPORTED = time.time()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def process_start() -> float:
    """Wall-clock start of this process, from /proc where it exists."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return _IMPORTED


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, with its configuration, mix and the
    metrics BENCHMARK.json says it reports."""

    def __init__(self, spec: dict, name: str):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"benchmark: no workload {name!r}")
        self.name = name
        self.entry = cells[name]
        cfg = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.config = load_json(ROOT, cfg["file"])
        self.mix = load_json(HERE, "mixes", self.entry["traffic"] + ".json")
        self.chips = int(self.entry["chips"])
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]

    def driver(self):
        return importlib.import_module(
            "benchmark.drivers." + self.config["driver"])


def metric_reader(name: str):
    """benchmark/metrics/<name>.py's read(run) -> number or None."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_device(chips: int):
    """The devices JAX found; exits when they are not `chips` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); there is no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, "
                         f"JAX found {len(devs)}")
    return devs


def peaks_for(kind: str) -> dict:
    table = load_json(HERE, "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"benchmark: device kind {kind!r} is not in "
                         "benchmark/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts JAX traces and backend compiles while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event in self.counts:
            self.counts[event] += 1

    @property
    def total(self) -> int:
        return sum(self.counts.values())


def run_cell(spec: dict, name: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             config: dict | None = None, mix: dict | None = None,
             started: float | None = None) -> dict:
    """One run of a cell: set-up, window, check.  Returns the result
    object.  Tests pass require_tpu=False and tiny config/mix
    overrides to drive the same path on the CPU."""
    started = process_start() if started is None else started
    cell = Cell(spec, name)
    if config is not None:
        cell.config = config
    if mix is not None:
        cell.mix = mix
    import jax
    if require_tpu:
        devs = require_device(cell.chips)
    else:
        devs = jax.devices()
    from ceph_tpu.common.compile_cache import use_compile_cache
    use_compile_cache()
    # every program goes to the persistent cache, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    kind = devs[0].device_kind
    peaks = peaks_for(kind) if require_tpu else {}
    compiles = CompileCounter()
    drv = cell.driver()
    state = drv.setup(cell.config, cell.mix, seed, trace=trace)
    try:
        setup_s = time.time() - started
        compiles.armed = True
        probe = Probe(trace, cell)
        win = drv.window(state, seconds, probe)
        compiles.armed = False
        mem_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs[:cell.chips])
        checks = drv.check(state)
    finally:
        drv.teardown(state)

    e2e = dict(win["metrics"], setup_s=setup_s)
    out_metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                out_metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": win["attempted"], "failed": win["failed"]}
    breakdown = None
    if trace:
        run = TracedRun(cell, win, probe, peaks)
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if probe.summary is not None:
            device["busy_s"] = probe.summary["busy_s"]
            device["window_s"] = probe.summary["window_s"]
            breakdown = {"device_ops": probe.summary["device_ops"],
                         "idle_gaps": probe.summary["idle_gaps"]}
    result["metrics"] = out_metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["notes"] = dict(win.get("notes", {}),
                           compiles_in_window=compiles.total)
    result["checks"] = checks
    return result


class Probe:
    """The profiler hook a driver's window calls around the part of the
    window it wants traced (untraced runs: no-ops)."""

    def __init__(self, enabled: bool, cell: Cell):
        self.enabled = enabled
        self.cell = cell
        self.summary = None
        self.t0 = self.t1 = None
        self._dir = None
        self._ann = None

    def start(self) -> None:
        if not self.enabled or self._dir is not None:
            return
        import tempfile
        import jax
        self._dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        jax.profiler.start_trace(self._dir)
        self._ann = jax.profiler.TraceAnnotation("profiled_window")
        self._ann.__enter__()
        self.t0 = time.monotonic()

    def stop(self) -> None:
        if not self.enabled or self._ann is None:
            return
        import shutil
        import jax
        from benchmark import trace as tr
        self.t1 = time.monotonic()
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()
        try:
            events = tr.load_dir(self._dir)
            self.summary = tr.reduce(events, chips=self.cell.chips)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def annotate(self, name: str):
        """A host span of the benchmark's own, which names idle gaps."""
        if not self.enabled:
            import contextlib
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


class TracedRun:
    """What a per-layer metric reader sees."""

    def __init__(self, cell: Cell, win: dict, probe: Probe, peaks: dict):
        self.cell = cell.name
        self.config = cell.config
        self.mix = cell.mix
        self.spans = win.get("spans", [])
        self.profiled = win.get("profiled", {})
        self.trace = probe.summary
        self.peaks = peaks


def emit(result: dict) -> None:
    print("notes " + json.dumps(result["notes"]), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    started = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json(ROOT, "BENCHMARK.json")
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), started=started)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
