"""Peak of ``kvcache_stats()["pages_in_use"]``, sampled every quarter second
and at every submit inside the window, over the pool's allocatable pages."""


def read(run):
    c = run["counters"]
    if not c.get("kv_pages_pool"):
        return None
    return 100.0 * c["kv_pages_peak"] / c["kv_pages_pool"]
