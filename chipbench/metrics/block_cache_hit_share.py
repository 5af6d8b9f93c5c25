"""Device block-cache hits over lookups (``ExecStats.block_cache_hits`` /
(hits + misses)), in percent."""


def read(ctx):
    st = [q["stats"] for q in ctx.queries if q["stats"]]
    hits = sum(s["block_cache_hits"] for s in st)
    looks = hits + sum(s["block_cache_misses"] for s in st)
    if not looks:
        return None
    return 100.0 * hits / looks
