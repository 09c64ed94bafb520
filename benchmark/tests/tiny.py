"""Tiny sizes of the cells' configurations and mixes, for the CPU."""
from benchmark import run

SPEC = run.load_json(run.ROOT, "BENCHMARK.json")


def tiny(cell: str) -> tuple[dict, dict]:
    c = run.Cell(SPEC, cell)
    cfg, mix = dict(c.config), dict(c.mix)
    if cfg["driver"] == "ec_cluster":
        cfg["pg_num"] = 8
        mix.update(object_bytes=min(mix["object_bytes"], 65536),
                   objects=16, payloads=4, in_flight=4,
                   readback_sample=4)
    else:
        cfg.update(osds=100, pg_num=1000)
        mix.update(sample_pgs=16, sample_moved_pgs=8)
    return cfg, mix


def run_tiny(cell: str, seconds: float = 1.0, trace: bool = False,
             seed: int = 5_000_000_001, spec: dict | None = None,
             cfg=None, mix=None) -> dict:
    tcfg, tmix = tiny(cell) if cfg is None else (cfg, mix)
    return run.run_cell(spec or SPEC, cell, seed, seconds, trace,
                        require_tpu=False, config=tcfg, mix=tmix)
