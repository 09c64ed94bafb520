"""EC plugin: mean over the EC calls that ran on the device (those
with a `device` child) of their `h2d` and `d2h` children: the
host->device copy until ready, and the copy of the result back."""

CALLS = ("ec_encode_kernel", "ec_decode_kernel")


def read(run):
    calls = {sp["span_id"] for sp in run.spans if sp["name"] in CALLS}
    xfer: dict = {}
    on_device = set()
    for sp in run.spans:
        if sp["parent"] not in calls:
            continue
        if sp["name"] == "device":
            on_device.add(sp["parent"])
        elif sp["name"] in ("h2d", "d2h"):
            xfer[sp["parent"]] = xfer.get(sp["parent"], 0.0) \
                + sp["end"] - sp["start"]
    vals = [xfer.get(c, 0.0) for c in on_device]
    return 1e3 * sum(vals) / len(vals) if vals else None
