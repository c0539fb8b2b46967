"""Time-rank columns the program built over the window: the delta of its
``engine_time_rank_builds_total``.  The fused window test builds one per
state of a log, in set-up; any other reading means the column was rebuilt
inside the timed window.  Nothing where the program has no such counter."""

KEY = "engine_time_rank_builds_total"


def read(run):
    if KEY not in run.counters:
        return None
    return run.counters[KEY]
