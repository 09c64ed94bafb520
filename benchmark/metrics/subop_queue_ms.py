"""OSD primary: mean over osd_op spans of the time covered by their
ms_queue:* children, clipped to the op: sub-ops waiting in the shard
OSDs' dispatch queues, and their replies in the primary's.  A program
that records no queue spans gives nothing."""


def read(run):
    kids = {}
    for sp in run.spans:
        if sp["name"].startswith("ms_queue:"):
            kids.setdefault(sp["parent"], []).append(sp)
    if not kids:
        return None
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
        vals.append(covered)
    return 1e3 * sum(vals) / len(vals) if vals else None
