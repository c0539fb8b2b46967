"""Median latency of every request due in the window, from its due time to
its answer; failed or unanswered requests count as infinitely late."""


def read(run):
    return run.latency_ms(50.0)
