"""The whole cold start of a model with held routed experts, as a share of
the chip's peak: the least time its work needs (the weights written to HBM
once, plus each prefill step's least time with no routed expert read:
``experts.least_step``) over the mean invocation time."""
import experts


def read(run):
    if (run.kind != "coldstart" or not run.invocations
            or "n_routed_experts" not in run.cell.cfg):
        return None
    image_s = run.image_pages * 4096 / run.peak["hbm_bytes_per_s"]
    step_s = experts.least_step(run).seconds(run.peak)
    least = sum(image_s + v.length * step_s for v in run.invocations)
    return 100.0 * least / sum(v.latency_s for v in run.invocations)
