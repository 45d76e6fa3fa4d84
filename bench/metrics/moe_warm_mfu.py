"""A warm decode step of a model with held routed experts, as a share of
the chip's peak: the least time a batch-1 step needs (every weight it reads
whatever the routing, no routed expert: ``experts.least_step``) over the
measured time per step."""
import experts


def read(run):
    if run.kind != "warm" or not run.invocations or "n_routed_experts" not in run.cell.cfg:
        return None
    steps = sum(v.length for v in run.invocations)
    per_step = sum(v.first_token_s for v in run.invocations) / steps
    return 100.0 * experts.least_step(run).seconds(run.peak) / per_step
