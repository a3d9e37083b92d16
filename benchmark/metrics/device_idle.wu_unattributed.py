"""Idle share (%) of the chip over the traced stretch around a served
WU's end that no program span covers: the idle seconds the trace
reduction names ``(no span)``, over the window (mean over chips)."""


def read(run):
    if (run.kind != "wu" or not run.trace or not run.trace.get("chips")
            or run.trace["window_s"] <= 0):
        return None
    idle = sum(s for name, s in run.trace["idle_gaps"] if name == "(no span)")
    return 100.0 * idle / (run.trace["window_s"] * run.trace["chips"])
