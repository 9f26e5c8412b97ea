"""Verdicts returned in the window over the window's length: from the
first submission to the return of the last one, which started before
``--seconds`` had gone by."""

from bench.lib.stats import rate


def read(rec):
    return rate(rec.answered, rec.window_s) if rec.window_s > 0 else None
