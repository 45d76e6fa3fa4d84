"""The whole cold start's share of the chip's peak: the least time its work
needs (the weights written to HBM once, plus each prefill step's FLOPs or
weight reads, whichever bounds it) over the mean invocation time."""


def read(run):
    if run.kind != "coldstart" or not run.invocations:
        return None
    image_s = run.image_pages * 4096 / run.peak["hbm_bytes_per_s"]
    step_s = run.step.seconds(run.peak)
    least = sum(image_s + v.length * step_s for v in run.invocations)
    return 100.0 * least / sum(v.latency_s for v in run.invocations)
