"""Device time per batched launch: busy time inside the launch program's
module events in the trace, over the number of those events."""


def read(rec):
    t = rec.trace
    if t is None or t.launches == 0:
        return None
    return 1e3 * t.launch_busy_s / t.launches
