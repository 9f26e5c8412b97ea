"""Share of the window's validated rows that the sequential fallback
decided (``fallback_validated`` over ``batch_validated +
fallback_validated``)."""

from bench.lib.stats import share


def read(rec):
    b, f = rec.counts.get("batch_validated", 0), rec.counts.get("fallback_validated", 0)
    return share(f, b + f)
