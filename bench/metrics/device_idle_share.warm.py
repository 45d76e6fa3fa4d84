"""Share of the traced warm invocations in which no operation ran on the
device."""


def read(run):
    if run.kind != "warm" or run.reduction is None:
        return None
    return 100.0 * run.reduction.idle_share
