"""What the span readers share.  A span is a motrace record: `tid` (its
trace), `sid`, `psid` (its parent), `name`, `thread`, `ts_us`, `dur_us`."""

from xplane import union


def by_trace(spans):
    """{trace id: its spans}."""
    out = {}
    for s in spans:
        out.setdefault(s["tid"], []).append(s)
    return out


def statements(trace):
    """The `statement` spans of one trace that are no other span's child
    (a re-entrant execute nests a second one under the first)."""
    sids = {s["sid"] for s in trace}
    return [s for s in trace
            if s["name"] == "statement" and s["psid"] not in sids]


def children(trace):
    """{sid: the spans whose parent it is}."""
    out = {}
    for s in trace:
        out.setdefault(s["psid"], []).append(s)
    return out


def covered_us(span, others):
    """Microseconds of `span` that the union of `others` covers, clipped
    to `span` (spans of several threads overlap)."""
    lo, hi = span["ts_us"], span["ts_us"] + span["dur_us"]
    return sum(min(b, hi) - max(a, lo)
               for a, b in union([o["ts_us"], o["ts_us"] + o["dur_us"]]
                                 for o in others)
               if b > lo and a < hi)
