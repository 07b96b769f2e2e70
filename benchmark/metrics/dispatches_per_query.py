"""Compiled programs the device started, per query of the traced passes:
the events of the trace's "XLA Modules" line inside the traced window. (The
engine's own counter, exec_rollup(...)["dispatches"], counts fused-stage
dispatches only and reads 0 on the resident path.)"""


def read(run):
    if not run.trace or not run.trace["programs"]:
        return None
    return run.trace["programs"] / (run.trace["passes"]
                                    * run.queries_per_pass)
