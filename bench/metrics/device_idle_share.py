"""device_idle_share (device): 1 minus the union of the device
operations' intervals over the traced window, in percent."""

from devtrace import busy_and_breakdown


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    bd = busy_and_breakdown(run.trace)
    return 100.0 * (1.0 - bd["busy_s"] / bd["window_s"])
