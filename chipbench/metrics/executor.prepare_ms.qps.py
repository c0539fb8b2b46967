"""Mean ``scan.prepare`` span of the device counts over the window: the
pair columns, the window and keep masks and their casts to the kernel's
dtypes.  Delta of the sum over delta of the count of the program's
``engine_scan_phase_seconds{phase=prepare}``; nothing where the program
has no such series."""

KEY = "engine_scan_phase_seconds{phase=prepare}"


def read(run):
    n = run.counters.get(KEY + ".count", 0.0)
    if n <= 0:
        return None
    return 1000.0 * run.counters[KEY + ".sum"] / n
