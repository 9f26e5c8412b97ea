"""Set-up: process start to the first timed request (JAX start-up,
registration, traffic generation, warm-up and any compilation)."""


def read(rec):
    return rec.setup_s
