"""Share of engine queries served from the result cache over the window:
delta of ``engine_cache_hits_total`` over delta of ``engine_queries_total``."""


def read(run):
    q = run.delta("engine_queries_total")
    if q <= 0:
        return None
    return 100.0 * run.delta("engine_cache_hits_total") / q
