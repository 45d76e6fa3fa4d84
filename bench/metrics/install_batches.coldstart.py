"""Restore-kernel batches per restore: ``FusedScatter.stats["batches"]``
over the window, divided by its restores."""


def read(run):
    n = run.counters.get("restores", 0)
    if run.kind != "coldstart" or not n or "batches" not in run.counters:
        return None
    return run.counters["batches"] / n
