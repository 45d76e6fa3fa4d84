"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — the pod axis
carries cross-DCN data parallelism; Aquifer's pool hierarchy maps onto it
(pod-local CXL tier ↔ intra-pod, RDMA tier ↔ cross-pod).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; smoke tests and
benches must keep seeing 1 device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for tests."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(1, data)))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
