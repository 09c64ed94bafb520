"""Run a cell under its configuration's control, or under one planted
fault, on several seeds in one process, and print what `correct`
compared.  Each run is expected to come out not correct.

    python3 -m benchmark.control --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--fault answer_altered|state_unchanged|half_batch]

On the chip this runs the cell at its own size; benchmark/tests/ run
the same patches at a tiny size on the CPU.  The benchmark's own runs
never import this module.
"""
from __future__ import annotations

import argparse
import json
import sys

from benchmark import faults, run


def run_under(spec: dict, cell: str, seed: int, seconds: float,
              fault: str | None, require_tpu: bool = True,
              config: dict | None = None, mix: dict | None = None) -> dict:
    c = run.Cell(spec, cell)
    driver = (config or c.config)["driver"]
    plant = faults.FAULTS[driver][fault] if fault else \
        faults.CONTROLS[driver]
    with plant():
        return run.run_cell(spec, cell, seed, seconds, False,
                            require_tpu=require_tpu, config=config,
                            mix=mix)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    caught = 0
    seeds = [int(x) for x in args.seeds.split(",")]
    for seed in seeds:
        res = run_under(spec, args.workload, seed, args.seconds,
                        args.fault)
        caught += not res["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault or "control",
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    print(json.dumps({"runs": len(seeds), "not_correct": caught}))
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
