"""Mean ``serve.payload`` span over the window: building an answer (its
lists and payload dict) for every sink.  Delta of the sum over delta of the
count of the program's ``serve_payload_seconds`` series, every sink;
nothing where the program has none."""


def read(run):
    n = run.delta("serve_payload_seconds", "count")
    if n <= 0:
        return None
    return 1000.0 * run.delta("serve_payload_seconds", "sum") / n
