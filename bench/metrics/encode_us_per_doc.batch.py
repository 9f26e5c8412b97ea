"""Host encode per row: total time of the ``admit.encode`` phase of the
program's phase profiler (``obs/profile.py``) over the rows that went
into a launch."""


def read(rec):
    phase = rec.phases.get("admit.encode")
    if phase is None or rec.rows_encoded <= 0:
        return None
    return phase["total_ns"] / 1e3 / rec.rows_encoded
