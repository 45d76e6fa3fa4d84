"""Host waits on restore checksum readbacks per restore:
``FusedScatter.stats["verify_syncs"]`` over the window, divided by its
restores.  A program without the counter reads nothing."""


def read(run):
    n = run.counters.get("restores", 0)
    if run.kind != "coldstart" or not n or "verify_syncs" not in run.counters:
        return None
    return run.counters["verify_syncs"] / n
