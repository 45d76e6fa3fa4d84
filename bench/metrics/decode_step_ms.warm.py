"""Mean time of one decode step of the warm server: every invocation's time
from the call to the first tokens, summed, over the steps they ran (one
per prompt token)."""


def read(run):
    if run.kind != "warm" or not run.invocations:
        return None
    steps = sum(v.length for v in run.invocations)
    return 1000.0 * sum(v.first_token_s for v in run.invocations) / steps
