"""kernel: the least time the ops served in the profiled window need,
over the device's busy time in it, in percent.  Least time is the HBM
bytes the code's shapes define (encode: stripe bytes x (k+m)/k; decode:
stripe bytes x (k+e)/k), over the HBM peak: the GF(2^8) bit-matmul is
bandwidth-bound at k=8, m=4.  In these cells all device work is EC
work, so busy time is the EC kernels' time plus their transfers."""


def read(run):
    work = run.profiled
    if run.trace is None or not work.get("hbm_bytes"):
        return None
    least_s = work["hbm_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["busy_s"]
