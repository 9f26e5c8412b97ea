"""Sequential fallback per row: total time of the program's
``admit.fallback`` span (the loop over the rows no launch decided) over
the rows it decided (``fallback_validated``)."""


def read(rec):
    span = rec.phases.get("admit.fallback")
    rows = rec.counts.get("fallback_validated", 0)
    if span is None or rows <= 0:
        return None
    return span["total_ns"] / 1e3 / rows
