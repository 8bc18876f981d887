"""device_idle_share: 1 - (union of the device's op intervals / traced
window), in percent, on device 0 of the profiler trace."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    d0 = run.trace["devices"][0]
    return 100.0 * (1.0 - d0["busy_s"] / d0["window_s"])
