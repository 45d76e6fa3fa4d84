"""Hot chunks handed to a second or later restore of a burst without a read
of their own: ``HotChunkCache.stats["fanout_hits"]`` over the window, over
its bursts."""


def read(run):
    bursts = run.counters.get("bursts")
    if run.kind != "coldstart" or not bursts:
        return None
    return run.counters["fanout_hits"] / bursts
