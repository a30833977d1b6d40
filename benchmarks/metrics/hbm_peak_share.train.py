"""The fullest device's peak after the window (buffers in use plus the
running program's reserved temporaries, as the driver reads them from
``memory_stats()``) over the device's HBM from the peaks table."""


def compute(run):
    if run.peaks is None:
        return None
    peak = run.device.get("memory_peak_bytes")
    if not peak:
        return None
    return 100.0 * peak / run.peaks["hbm_bytes"]
