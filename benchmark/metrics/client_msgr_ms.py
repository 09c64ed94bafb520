"""client + messenger: mean over traced client ops of the objecter_op
span's duration less its osd_op child's (client code, queueing and
the messenger both ways; one in-process monotonic clock)."""


def read(run):
    by_parent = {}
    for sp in run.spans:
        if sp["name"].startswith("osd_op:"):
            by_parent.setdefault(sp["parent"], []).append(sp)
    vals = []
    for sp in run.spans:
        if not sp["name"].startswith("objecter_op:"):
            continue
        kids = by_parent.get(sp["span_id"], [])
        if len(kids) == 1:      # resent ops have no single OSD leg
            k = kids[0]
            vals.append((sp["end"] - sp["start"])
                        - (k["end"] - k["start"]))
    return 1e3 * sum(vals) / len(vals) if vals else None
