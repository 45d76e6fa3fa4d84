"""Mean time of the benchmark's span around ``restore_server``, ended by
``block_until_ready`` on the restored weights."""


def read(run):
    xs = [v.restore_s for v in run.invocations if v.restore_s is not None]
    return sum(xs) / len(xs) if xs else None
