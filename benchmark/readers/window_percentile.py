"""A percentile of all requests of the window, client side, JSON parse
included (linear interpolation between the two nearest)."""

import numpy as np


def read(run: dict, q: float):
    return float(np.percentile(run["latencies_ms"], q)) if run["latencies_ms"] else None
