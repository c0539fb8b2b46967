"""Mean lane queue wait (submission to worker pickup) over the window, both
lanes: delta of the sum over delta of the count of the program's
``transport_queue_wait_seconds`` histograms."""


def read(run):
    n = run.delta("transport_queue_wait_seconds", "count")
    if n <= 0:
        return None
    return 1000.0 * run.delta("transport_queue_wait_seconds", "sum") / n
