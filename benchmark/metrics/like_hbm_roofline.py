"""The least time the chip's HBM needs for the bytes the string match has
to read (textbytes.match_bytes of the engine's counter) over the device's
time for the match (timers_ns.stringMatchDeviceTime), in percent. None
where either is missing, or no time was stamped."""
import textbytes

from .phase_account import mean_of


def read(run):
    if not run.peaks:
        return None
    counted = mean_of(run, lambda r: r["counters"]["string_match_bytes"])
    match_s = mean_of(run, lambda r: r["timers_ns"]["stringMatchDeviceTime"],
                      1e-9)
    if not counted or not match_s:
        return None
    least_s = textbytes.match_bytes(counted) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / match_s
