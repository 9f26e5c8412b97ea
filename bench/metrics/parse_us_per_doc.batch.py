"""Engine front end per document: total time of the program's
``serve.parse`` span (endpoint check, byte guard and JSON decode of a
whole submission) over the documents the window submitted."""


def read(rec):
    span = rec.phases.get("serve.parse")
    if span is None or rec.attempted <= 0:
        return None
    return span["total_ns"] / 1e3 / rec.attempted
