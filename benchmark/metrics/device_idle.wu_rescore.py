"""Idle share (%) of the chip over the traced stretch around a served
WU's end that the host spent in the oracle rescore: the idle seconds
whose covering program span starts with ``erp:rescore`` (``rescore``,
``rescore-template``, ``rescore-finalize``), over the window (mean over
chips).  Reads 0 where the program has no such span."""


def read(run):
    if (run.kind != "wu" or not run.trace or not run.trace.get("chips")
            or run.trace["window_s"] <= 0):
        return None
    idle = sum(s for name, s in run.trace["idle_gaps"]
               if name.startswith("erp:rescore"))
    return 100.0 * idle / (run.trace["window_s"] * run.trace["chips"])
