"""Set-up: from the process's start to the end of the warm-up (imports,
weights, publish or resident server, the warm-up invocation, compiles)."""


def read(run):
    return run.setup_s
