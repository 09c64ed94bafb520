"""EC plugin: mean over ec_encode_kernel / ec_decode_kernel spans of
their `stage` and `unstage` children: host gather into the dispatch
layout (with the copy of the write's segment) and the per-shard
tobytes and stack after it.  A program whose calls record no
`unstage` gives nothing."""

CALLS = ("ec_encode_kernel", "ec_decode_kernel")
HOST = ("stage", "unstage")


def read(run):
    calls = {sp["span_id"] for sp in run.spans if sp["name"] in CALLS}
    host = {c: 0.0 for c in calls}
    seen = False
    for sp in run.spans:
        if sp["parent"] in host and sp["name"] in HOST:
            host[sp["parent"]] += sp["end"] - sp["start"]
            seen = seen or sp["name"] == "unstage"
    return 1e3 * sum(host.values()) / len(host) if seen else None
