"""Share of the device counts over the window that read a repository's
pair columns resident on the device, in %: 100 times the delta of the
program's ``engine_scan_resident_total`` over the delta of the count of
``engine_scan_phase_seconds{phase=device}``; nothing where the program has
no such counter or made no device count."""

KEY = "engine_scan_resident_total"
DEVICE = "engine_scan_phase_seconds{phase=device}.count"


def read(run):
    n = run.counters.get(DEVICE, 0.0)
    if n <= 0 or KEY not in run.counters:
        return None
    return 100.0 * run.counters[KEY] / n
