"""OSD primary: mean duration of ec_rmw_read spans, one per EC write
that reads its partly covered stripes back before encoding: from the
write's admission to ECBackend, through the wait behind earlier ops on
the object or PG, to the sub-reads' return."""


def read(run):
    vals = [sp["end"] - sp["start"] for sp in run.spans
            if sp["name"] == "ec_rmw_read"]
    return 1e3 * sum(vals) / len(vals) if vals else None
