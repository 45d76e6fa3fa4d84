"""A warm decode step's share of the chip's peak: the least time a step
needs (its FLOPs or its weight reads, whichever bounds it) over the
measured time per step."""


def read(run):
    if run.kind != "warm" or not run.invocations:
        return None
    steps = sum(v.length for v in run.invocations)
    per_step = sum(v.first_token_s for v in run.invocations) / steps
    return 100.0 * run.step.seconds(run.peak) / per_step
