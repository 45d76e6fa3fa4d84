"""The least work the served path must do, from shapes alone: the yardstick
for the roofline and ``mfu`` metrics.

``image_bytes``: the weights as pages (what a restore writes to HBM once).
``step``: one decode step of a batch, the unit the program prefills and
decodes in: the FLOPs of its matrix products (2 per multiply-add; attention
over the cache left out, so the count stays a lower bound) and the bytes of
the weights it must read (every matrix but the embedding table, of which it
reads one row per token).
"""
from __future__ import annotations

import dataclasses
import math

PAGE_BYTES = 4096


@dataclasses.dataclass(frozen=True)
class StepWork:
    flops: float
    bytes: float

    def seconds(self, peak: dict) -> float:
        """The least time: the larger of compute and HBM time."""
        return max(self.flops / peak["bf16_flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def _leaves(template):
    import jax
    from weights import leaf_path

    return [(leaf_path(p), x) for p, x in
            jax.tree_util.tree_flatten_with_path(template)[0]]


def _nbytes(x) -> int:
    return math.prod(x.shape) * x.dtype.itemsize


def image_pages(template) -> int:
    """Pages of the weights, each leaf page-aligned."""
    return sum(-(-_nbytes(x) // PAGE_BYTES) for _, x in _leaves(template))


def step(template, batch: int) -> StepWork:
    flops = 0.0
    nbytes = 0.0
    for path, x in _leaves(template):
        name = path.rsplit("/", 1)[-1]
        if name == "scale":
            nbytes += _nbytes(x)
        elif name == "table":
            nbytes += batch * x.shape[-1] * x.dtype.itemsize
        else:
            flops += 2.0 * batch * math.prod(x.shape)
            nbytes += _nbytes(x)
    return StepWork(flops, nbytes)
