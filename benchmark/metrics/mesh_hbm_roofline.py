"""The least time the mesh's HBM needs for the bytes a pass has to read
(scanbytes.py, spread over the devices of the engine's mesh at each one's
peak) over the mean busy time a device per traced pass
(trace_reduce.reduce_trace averages busy time over the device planes that
ran), in percent: the sharded scan-aggregate's share of its roofline. The
mesh's size is the engine's own (mesh.devices of its phase account); None
on a program that runs no mesh, where query_hbm_roofline is the metric."""
from .phase_account import records


def read(run):
    if not run.trace or not run.peaks or not run.trace["busy_s"]:
        return None
    recs = records(run)
    try:
        devices = {r["mesh"]["devices"] for r in recs}
    except (KeyError, TypeError):
        return None
    if len(devices) != 1:
        return None
    least_s = run.pass_bytes / (devices.pop() * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (run.trace["busy_s"] / run.trace["passes"])
