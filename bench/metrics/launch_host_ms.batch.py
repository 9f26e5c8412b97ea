"""Host wall per batched launch: total time of the program's
``executor.launch`` span (columns to the device, the launch, its sync and
the results back to numpy) over its calls.  Beside
``launch_device_ms.batch`` it shows what transfer, dispatch and sync add
to the device's time."""


def read(rec):
    span = rec.phases.get("executor.launch")
    if span is None or span["calls"] <= 0:
        return None
    return span["total_ns"] / 1e6 / span["calls"]
