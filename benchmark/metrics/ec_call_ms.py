"""EC plugin: mean duration of ec_encode_kernel / ec_decode_kernel
spans.  Despite the names these cover ecutil's host staging, both
host<->device transfers, the device work and tobytes()."""


def read(run):
    vals = [sp["end"] - sp["start"] for sp in run.spans
            if sp["name"] in ("ec_encode_kernel", "ec_decode_kernel")]
    return 1e3 * sum(vals) / len(vals) if vals else None
