"""librbd: mean self time of rbd_aio_write spans, their duration less
the union of their objecter_op children's intervals (striping, the
exclusive-lock check, object map, submission under the image lock,
and the completion's wake-up)."""


def read(run):
    kids = {}
    for sp in run.spans:
        if sp["name"].startswith("objecter_op:"):
            kids.setdefault(sp["parent"], []).append(sp)
    vals = []
    for sp in run.spans:
        if sp["name"] != "rbd_aio_write":
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
