"""Hot chunks read from CXL through the node page server's fan-out cache,
per burst of concurrent restores: ``HotChunkCache.stats["reads"]`` over the
window, over its bursts.  One read per hot chunk when every restore of a
burst attached before the first chunk.  A chunk that a restore reads while
no other is attached goes beside the cache and is not counted; it shows as
a fan-out hit missing from ``fanout_hits.burst``."""


def read(run):
    bursts = run.counters.get("bursts")
    if run.kind != "coldstart" or not bursts:
        return None
    return run.counters["chunk_cache_reads"] / bursts
