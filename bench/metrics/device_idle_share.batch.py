"""1 - (union of device-op intervals) / traced window, from the profiler
trace."""


def read(rec):
    return rec.trace.idle_share if rec.trace is not None and rec.trace.window_s > 0 else None
