"""The fused restore kernel's share of its HBM roofline on a chip of a
burst: the least bytes the traced restores had to move on each chip (each
weight page read from its compact chunk and written into the page array:
2 x 4096 bytes a page; a burst restores one instance on each of the cell's
chips) over HBM bandwidth, divided by the kernel's device time in the
trace, which the reduction averages over the chips."""
import work

KERNEL = "jit_fused_restore_pallas"


def read(run):
    red = run.reduction
    if red is None:
        return None
    restores = sum(1 for v in run.invocations if v.traced and v.restore_s is not None)
    kernel_s = red.module_s(KERNEL)
    if not restores or kernel_s <= 0:
        return None
    nbytes = 2 * work.PAGE_BYTES * run.image_pages * restores / run.cell.chips
    return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / kernel_s
