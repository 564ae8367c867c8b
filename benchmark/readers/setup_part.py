"""Seconds (or counts) of the set-up, by the benchmark's own clock:
`setup_s` is process start to window start; the parts are generate_s,
load_s, checkpoint_reopen_s, prepare_s (read-back, index build), warm_s,
compiles_in_setup.  Several parts are summed."""


def read(ctx, parts):
    return sum(ctx["setup"][p] for p in parts)
