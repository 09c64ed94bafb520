"""EC plugin: mean duration, on the host clock, of the `device` child
of the EC calls that have one: dispatch until the result is ready.
Compare with the kernel's device time in the trace; the gap is
dispatch and launch cost."""

CALLS = ("ec_encode_kernel", "ec_decode_kernel")


def read(run):
    calls = {sp["span_id"] for sp in run.spans if sp["name"] in CALLS}
    vals = [sp["end"] - sp["start"] for sp in run.spans
            if sp["name"] == "device" and sp["parent"] in calls]
    return 1e3 * sum(vals) / len(vals) if vals else None
