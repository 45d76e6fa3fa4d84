"""The least work of a decode step of a model whose MoE layers hold routed
experts: ``work.step`` less the held experts' stacks (the weight template's
``moe/wi``, ``moe/wg`` and ``moe/wo`` leaves).  A batch-1 step must read
every other weight whatever the routing (attention, dense and shared FFNs,
routers, norms, the head, one embedding row), but only the experts its
token chose, so leaving the stacks out keeps the yardstick a lower bound for
the ``moe_*_mfu`` metrics."""
from __future__ import annotations

import dataclasses
import math

import work

STACKS = ("moe/wi", "moe/wg", "moe/wo")


def held_stacks(program: dict) -> list:
    """The held experts' weight leaves of the configuration's program, by
    shape alone (no weight is made)."""
    import jax

    from repro.launch import serve
    from repro.models.model_zoo import build

    mc = serve.model_config(program["arch"], published_widths=program["published_widths"],
                            layers=program["layers"], dtype=program["dtype"])
    template = jax.eval_shape(build(dataclasses.replace(mc, **program["overrides"])).init,
                              jax.random.PRNGKey(0))
    return [x for path, x in work._leaves(template) if path.endswith(STACKS)]


def least_step(run) -> work.StepWork:
    """``run.step`` without the held experts' FLOPs and bytes."""
    stacks = held_stacks(run.cell.cfg["program"])
    batch = run.cell.mix["batch"]
    return work.StepWork(
        run.step.flops - sum(2.0 * batch * math.prod(x.shape) for x in stacks),
        run.step.bytes - sum(work._nbytes(x) for x in stacks))
