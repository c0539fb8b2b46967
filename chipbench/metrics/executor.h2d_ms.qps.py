"""Mean ``scan.h2d`` span of the device counts over the window: the explicit
copy of the count's columns to the device, until they are ready.  Delta of
the sum over delta of the count of the program's
``engine_scan_phase_seconds{phase=h2d}``; nothing where the program has no
such series."""

KEY = "engine_scan_phase_seconds{phase=h2d}"


def read(run):
    n = run.counters.get(KEY + ".count", 0.0)
    if n <= 0:
        return None
    return 1000.0 * run.counters[KEY + ".sum"] / n
