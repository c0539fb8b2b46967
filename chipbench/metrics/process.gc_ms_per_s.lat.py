"""Milliseconds per window second in which Python's garbage collector held
the process: delta of the sum of the program's
``process_gc_pause_seconds`` series, every generation, over the window's
seconds; nothing where the program has no such series."""


def read(run):
    if not any(k.startswith("process_gc_pause_seconds{") for k in run.counters):
        return None
    return 1000.0 * run.delta("process_gc_pause_seconds", "sum") / run.seconds
