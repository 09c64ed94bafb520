"""EC plugin: mean of the `stage` child of ec_decode_kernel, the host
survivor staging (np.stack) ahead of the decode dispatch."""


def read(run):
    dec = {sp["span_id"] for sp in run.spans
           if sp["name"] == "ec_decode_kernel"}
    vals = [sp["end"] - sp["start"] for sp in run.spans
            if sp["name"] == "stage" and sp["parent"] in dec]
    return 1e3 * sum(vals) / len(vals) if vals else None
