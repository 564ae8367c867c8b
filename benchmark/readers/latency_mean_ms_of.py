"""Mean latency, send to last row on the client's clock, of the completed
statements whose template is one of `templates`, in ms.  Nothing where
none of them completed."""

from readers._common import completed


def read(ctx, templates):
    lat = [(s["t_done_ns"] - s["t_send_ns"]) / 1e6 for s in completed(ctx)
           if s["template"] in templates]
    return sum(lat) / len(lat) if lat else None
