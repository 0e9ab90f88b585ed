"""A quantile of one host-clock series the driver recorded.

The metric file gives ``series`` (a key of the driver's series, in
seconds), ``q`` (0 to 1) and ``scale`` (1000 for milliseconds). An
empty or missing series gives no value."""
import numpy as np


def read(metric: dict, obs):
    xs = obs.series.get(metric["series"])
    if xs is None or len(xs) == 0:
        return None
    return float(np.quantile(np.asarray(xs, np.float64), metric["q"])) * metric["scale"]
