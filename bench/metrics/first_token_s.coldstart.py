"""Mean time of the benchmark's span around the prefill of a restored
server, ended by reading the first tokens back."""


def read(run):
    if run.kind != "coldstart" or not run.invocations:
        return None
    return sum(v.first_token_s for v in run.invocations) / len(run.invocations)
