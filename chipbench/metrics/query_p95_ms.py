"""95th percentile of the window's query latencies, timed by the client
around ``collect()`` until the answer is host arrays."""
import numpy as np


def read(ctx):
    lat = [q["latency_s"] for q in ctx.queries]
    if not lat:
        return None
    return float(np.percentile(lat, 95)) * 1e3
