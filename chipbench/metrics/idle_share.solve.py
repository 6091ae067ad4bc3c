"""Share of the traced window in which no operation ran on the device, in %:
1 - (union of the device-op intervals) / (traced window)."""

from chipbench import trace


def read(view):
    if view.trace is None or not view.trace.devices or not view.window_s:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s(view.trace) / view.window_s)
