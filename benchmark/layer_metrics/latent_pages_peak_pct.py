"""Peak of the latent pool's pages in use over its allocatable pages. The
latent pool is the one paged pool of a configuration with latent attention
(``kv_lora_rank``): its pages are the cache's own, so this is the driver's
``kvcache_stats()["pages_in_use"]`` sampled every quarter second and at every
submit inside the window — the number ``kv_pages_peak_pct`` reports, under
the name of what the pages hold here."""


def read(run):
    c = run["counters"]
    if "kv_lora_rank" not in run["cell"].config["model"] \
            or not c.get("kv_pages_pool"):
        return None
    return 100.0 * c["kv_pages_peak"] / c["kv_pages_pool"]
