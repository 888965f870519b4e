"""90th percentile of the latency of every single-RHS solve in the window,
host clock around ``ctx.solve`` returning numpy."""
import numpy as np


def read(run):
    if run["rhs_columns"] != 1 or not run["latencies_ms"]:
        return None
    return float(np.percentile(run["latencies_ms"], 90))
