"""Megabytes (1e6 bytes) put on the device per device count over the window:
delta of the program's ``engine_h2d_bytes_total`` over delta of the count
of ``engine_scan_phase_seconds{phase=h2d}``; nothing where the program has
no such series."""

KEY = "engine_scan_phase_seconds{phase=h2d}.count"


def read(run):
    n = run.counters.get(KEY, 0.0)
    if n <= 0 or "engine_h2d_bytes_total" not in run.counters:
        return None
    return run.counters["engine_h2d_bytes_total"] / n / 1e6
