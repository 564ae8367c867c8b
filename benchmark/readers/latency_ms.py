"""A percentile (nearest rank; 100 is the slowest) of the latencies of ALL
statements of the window, send to last row, on the client's clock."""

import math

from readers._common import completed


def read(ctx, percentile):
    lat = sorted((s["t_done_ns"] - s["t_send_ns"]) / 1e6
                 for s in completed(ctx))
    if not lat:
        return None
    return lat[max(0, math.ceil(percentile / 100 * len(lat)) - 1)]
