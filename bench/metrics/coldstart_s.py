"""Restore-to-first-token per cold start: the summed durations of every
invocation of the window over their count."""


def read(run):
    if run.kind != "coldstart" or not run.invocations:
        return None
    return sum(v.latency_s for v in run.invocations) / len(run.invocations)
