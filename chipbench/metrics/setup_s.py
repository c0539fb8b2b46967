"""Set-up seconds: process start to the first timed request (generation,
registration, warm-up, compiles or compile-cache loads)."""


def read(run):
    return run.setup_s
