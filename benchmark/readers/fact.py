"""A fact the configuration's reference worked out from the window's
answers (e.g. `recall_at_k`: mean overlap with the exact top-k)."""


def read(ctx, name):
    return ctx["facts"].get(name)
