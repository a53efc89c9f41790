"""Programs the persistent compilation cache did not hold, over set-up
(``fastpath.cache.cache_counts()``): 0 in every run but a checkout's first."""


def read(run):
    return run["counters"]["compile_cache_misses_setup"]
