"""Requests answered with status 200 inside the window, per second of the
window (host clock)."""


def read(run):
    done = sum(1 for r in run.requests if r.ok and r.done <= run.seconds)
    return done / run.seconds
