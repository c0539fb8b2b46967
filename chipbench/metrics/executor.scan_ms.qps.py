"""Mean engine wall time of the executed (not cached) requests: the
response's ``wall_s``, which for an executed query is the engine's scan."""


def read(run):
    return run.mean_scan_ms()
