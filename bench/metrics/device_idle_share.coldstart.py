"""Share of the traced cold starts in which no operation ran on the device."""


def read(run):
    if run.kind != "coldstart" or run.reduction is None:
        return None
    return 100.0 * run.reduction.idle_share
