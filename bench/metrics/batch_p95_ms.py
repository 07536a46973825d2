"""Nearest-rank 95th percentile of the batch latency (dispatch to
``block_until_ready``) over every batch of the window, in ms."""

import math


def read(record, reduced, peaks):
    dts = sorted(x["dt"] for x in record["batches"])
    if not dts:
        return None
    return dts[math.ceil(0.95 * len(dts)) - 1] * 1e3
