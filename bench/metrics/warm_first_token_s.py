"""Time from the call to the first token read back, per warm invocation:
the summed durations of every invocation of the window over their count."""


def read(run):
    if run.kind != "warm" or not run.invocations:
        return None
    return sum(v.latency_s for v in run.invocations) / len(run.invocations)
