"""OSD primary: mean self time of osd_op spans, their duration less
the union of their child spans' intervals (EC plugin call, sub-writes
and sub-reads on every shard's OSD)."""


def read(run):
    kids = {}
    for sp in run.spans:
        kids.setdefault(sp["parent"], []).append(sp)
    vals = []
    for sp in run.spans:
        if not sp["name"].startswith("osd_op:"):
            continue
        a0, b0 = sp["start"], sp["end"]
        covered, edge = 0.0, a0
        for a, b in sorted((max(k["start"], a0), min(k["end"], b0))
                           for k in kids.get(sp["span_id"], [])):
            a = max(a, edge)
            if b > a:
                covered += b - a
                edge = b
        vals.append((b0 - a0) - covered)
    return 1e3 * sum(vals) / len(vals) if vals else None
