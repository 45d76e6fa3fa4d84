"""Named host spans of the restore path, on the JAX profiler's clock.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` named ``name``, one
of the ``aquifer.*`` constants below; ``spanned(name)`` makes every call of
a function that span.  A span costs one ``TraceMe`` when no profiler runs
and writes an event into the profiler's host plane when one does, so a
trace of a restore shows which host phase was running while the device
waited.  Every span is opened on the caller's thread.  Names are whole
constants and a span takes no arguments, so nothing is formatted per call.
Where JAX is missing the spans are no-ops.

Capture them with ``jax.profiler.trace(dir)`` around a restore, or with
``bench/run.py --trace 1``; docs/ARCHITECTURE.md ("Tracing") lists what
each covers.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, ContextManager

PREFIX = "aquifer."

RESTORE = PREFIX + "restore"                      # ckpt.restore_checkpoint
RESTORE_BORROW = PREFIX + "restore.borrow"        # Orchestrator.restore, to the first install
RESTORE_HOT = PREFIX + "restore.hot"              # RestoreEngine.pre_install_hot
RESTORE_ZERO = PREFIX + "restore.zero"            # install_all_sync: zero ranges
RESTORE_COLD = PREFIX + "restore.cold"            # install_all_sync: cold runs
RESTORE_CXL_READ = PREFIX + "restore.cxl_read"    # one hot chunk's CXL read
RESTORE_RDMA_READ = PREFIX + "restore.rdma_read"  # one cold run's RDMA read
RESTORE_INSTALL = PREFIX + "restore.install"      # Instance.uffd_copy_batch
RESTORE_EXTRACT = PREFIX + "restore.extract"      # named arrays cut from the page array
SCATTER_STAGE = PREFIX + "scatter.stage"          # fused_restore: rows gathered, uploaded
SCATTER_LAUNCH = PREFIX + "scatter.launch"        # kernel dispatch (+ checksum stash, bulk phases)
SCATTER_VERIFY = PREFIX + "scatter.verify"        # checksum readback, compare: per call or bulk phase
SERVE_PREFILL = PREFIX + "serve.prefill"          # ServerInstance.prefill

# ``jax.named_scope`` names inside the model's programs: they prefix the
# names of the operations they trace, not host spans
MLA = PREFIX + "mla"                              # latent attention, a layer
MOE_ROUTE = PREFIX + "moe.route"                  # scores, groups, top-k, weights
MOE_EXPERTS = PREFIX + "moe.experts"              # the held routed experts
MOE_SHARED = PREFIX + "moe.shared"                # the shared expert

try:
    from jax.profiler import TraceAnnotation as _Annotation
except ImportError:      # the host-only pool runs without JAX
    _Annotation = None


def span(name: str) -> ContextManager:
    """The span ``name`` (one of this module's constants) around a
    ``with`` block."""
    if _Annotation is None:
        return contextlib.nullcontext()
    return _Annotation(name)


def spanned(name: str) -> Callable:
    """Decorator: every call of the function is the span ``name``."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap
