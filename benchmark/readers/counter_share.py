"""delta(`of`) / delta(`among`) over the window, e.g. hits over lookups.
Nothing where there was no lookup."""

from readers._common import delta


def read(ctx, of, among):
    total = delta(ctx, among)
    return delta(ctx, of) / total if total > 0 else None
