"""Median latency (ms) of every request due in the window: from when it
fell due to when its answer was on the host; an unanswered one counts as
infinitely late."""
import numpy as np


def read(ctx):
    if ctx["traffic"]["loop"] != "open":
        return None
    return float(np.percentile(ctx["served"].latency_s(), 50)) * 1e3
