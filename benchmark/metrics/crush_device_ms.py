"""placement: device busy time per remap pass, over the whole passes
the profiled window holds."""


def read(run):
    passes = run.profiled.get("passes")
    if run.trace is None or not passes:
        return None
    return 1e3 * run.trace["busy_s"] / passes
