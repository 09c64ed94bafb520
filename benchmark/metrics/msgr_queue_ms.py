"""client + messenger: mean over traced client ops of the time the
OSDOp waited in the primary's dispatch queue plus the time the
OSDOpReply waited in the client's: the objecter_op's ms_queue:OSDOp
and ms_queue:OSDOpReply children, for each objecter_op with exactly
one osd_op child (the ops client_msgr_ms reads).  A program that
records no queue spans gives nothing."""

QUEUES = ("ms_queue:OSDOp", "ms_queue:OSDOpReply")


def read(run):
    kids = {}
    for sp in run.spans:
        kids.setdefault(sp["parent"], []).append(sp)
    vals, seen = [], False
    for sp in run.spans:
        if not sp["name"].startswith("objecter_op:"):
            continue
        mine = kids.get(sp["span_id"], [])
        if sum(k["name"].startswith("osd_op:") for k in mine) != 1:
            continue            # resent ops have no single OSD leg
        q = [k["end"] - k["start"] for k in mine if k["name"] in QUEUES]
        seen = seen or bool(q)
        vals.append(sum(q))
    return 1e3 * sum(vals) / len(vals) if seen else None
