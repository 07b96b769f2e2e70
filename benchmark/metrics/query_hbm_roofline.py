"""The least time the chip's HBM needs for the bytes a pass has to read
(scanbytes.py) over the device's busy time per traced pass, in percent."""


def read(run):
    if not run.trace or not run.peaks or not run.trace["busy_s"]:
        return None
    least_s = run.pass_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (run.trace["busy_s"] / run.trace["passes"])
