"""Share of the window in which no operation ran on the device, in
percent: one minus the union of device-op intervals over the window,
from the profiler trace of the same window. No trace, no value."""


def read(metric: dict, obs):
    t = obs.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
