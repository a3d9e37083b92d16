"""Device time of the merge collective per step, in ms, mean over chips:
the self time of the traced step's events in the ``erp.allreduce`` scope
(the ppermute butterfly over the mesh and its merges), summed over the
chips, over the chips.  None where the trace was cut inside the step,
holds no chip, or has no such scope."""


def read(run):
    if (run.kind != "bank" or not run.trace or run.trace["dropped"]
            or not run.trace.get("chips")):
        return None
    s = run.trace["scope_s"].get("allreduce")
    if s is None:
        return None
    return 1e3 * s / run.trace["chips"]
