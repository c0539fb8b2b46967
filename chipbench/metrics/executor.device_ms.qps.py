"""Mean ``scan.device`` span of the device counts over the window: from the
kernel's dispatch until its counts are back on the host, a wait behind
another thread's kernel included.  Delta of the sum over delta of the count
of the program's ``engine_scan_phase_seconds{phase=device}``; nothing where
the program has no such series."""

KEY = "engine_scan_phase_seconds{phase=device}"


def read(run):
    n = run.counters.get(KEY + ".count", 0.0)
    if n <= 0:
        return None
    return 1000.0 * run.counters[KEY + ".sum"] / n
