"""Share of the batched launch's roofline: the least time the chip could
take for the launches' work (``bench/lib/launch_work.py``, from their
inputs and outputs) over their device time in the trace.  Read only
where the trace holds exactly the launches the window made."""


def read(rec):
    t = rec.trace
    if t is None or rec.peaks is None or t.launches == 0 or t.launches != len(rec.launches):
        return None
    least = sum(w.least_s(rec.peaks) for w in rec.launches)
    return 100.0 * least / t.launch_busy_s if t.launch_busy_s > 0 else None
