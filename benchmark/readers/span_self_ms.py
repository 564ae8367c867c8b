"""Mean self time of a program span per occurrence: its duration less its
direct children named in `minus` (motrace, armed in the traced run)."""


def read(ctx, span, minus):
    spans = ctx["spans"]
    child_us = {}
    for s in spans:
        if s["name"] in minus:
            child_us[s["psid"]] = child_us.get(s["psid"], 0) + s["dur_us"]
    own = [s["dur_us"] - child_us.get(s["sid"], 0)
           for s in spans if s["name"] == span]
    return sum(own) / len(own) / 1e3 if own else None
