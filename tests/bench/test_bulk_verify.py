"""Checksum verification in bulk installs, as the benchmark sees it: the
``verify_syncs.coldstart`` reader, and a recorded CPU trace of one small
checksummed restore through ``Orchestrator`` → ``restore_checkpoint`` whose
hot and cold phases each read their checksums back once, at their end."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import spans  # noqa: E402
import work  # noqa: E402

tracing = harness.tracing


def _run(cell, kind):
    invs = [harness.Invocation(16, 1.0, 0.9 if kind == "coldstart" else None, 0.1, True),
            harness.Invocation(32, 3.0, 2.8 if kind == "coldstart" else None, 0.2, False)]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    return harness.Run(cell, peak, 30.0, 6.0, invs,
                       {"batches": 3000, "restores": 3}, 1000,
                       work.StepWork(2e9, 1e9), 8 << 30, None)


def test_verify_syncs_reader_counts_waits_per_restore():
    """Checksum readbacks a restore waits on: the window's count over its
    restores in a cold-start run; nothing for the warm kind, nor from a
    program without the counter."""
    read = harness.reader("verify_syncs.coldstart")
    cold = _run(harness.load_cell("mistral-coldstart"), "coldstart")
    assert read(cold) is None                       # no such counter
    cold.counters["verify_syncs"] = 6
    assert read(cold) == 2
    warm = _run(harness.load_cell("mistral-warm"), "warm")
    warm.counters["verify_syncs"] = 6
    assert read(warm) is None


def _inside(outer, sps):
    _, a, b = outer
    return [s for s in sps if a <= s[1] and s[2] <= b and s is not outer]


def test_a_checksummed_restore_verifies_once_per_bulk_phase(tmp_path):
    """A restore of a snapshot published with checksums, through the fused
    kernel (in the interpreter) over a device page array: every hot chunk is
    one CXL read, one install and one launched batch, and each bulk phase
    reads its checksums back and compares them once, at its end."""
    import jax.numpy as jnp

    from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
    from repro.core import Catalog, HierarchicalPool, Orchestrator, PoolMaster
    from repro.kernels.snapshot_fuse.ops import FusedScatter, make_fused_publish_fn

    rng = np.random.default_rng(3)
    state = {"params": {"w": jnp.asarray(rng.standard_normal((300, 1024)),
                                         jnp.float32)},
             "opt": {"m": rng.standard_normal((20, 1024)).astype(np.float32),
                     "v": np.zeros((16, 1024), np.float32)}}
    pool = HierarchicalPool(cxl_capacity=64 << 20, rdma_capacity=64 << 20)
    master = PoolMaster(pool, Catalog(),
                        publish_fn=make_fused_publish_fn(use_pallas=False))
    _, pub = save_checkpoint(master, "ck", state, step=1)
    assert pub["hot"] > 256 and pub["cold"] > 0 and pub["zero"] > 0
    scatter = FusedScatter(use_pallas=True, interpret=True)
    orch = Orchestrator("h", pool, master.catalog, scatter_fn=scatter)
    with tracing.record(tmp_path):
        restored, _ = restore_checkpoint(orch, "ck", state)
    orch.close()
    np.testing.assert_array_equal(np.asarray(restored["opt"]["m"]), state["opt"]["m"])
    assert scatter.stats["verify_syncs"] == 2

    sps = spans.flatten(spans.load(tmp_path))
    (restore,) = [s for s in sps if s[0] == "aquifer.restore"]
    inner = _inside(restore, sps)
    hot = [s for s in inner if s[0] == "aquifer.restore.hot"]
    assert len(hot) == 2                                   # both passes
    first = _inside(hot[0], sps)
    chunks = math.ceil(pub["hot"] / 256)
    for name in ("aquifer.restore.cxl_read", "aquifer.restore.install",
                 "aquifer.scatter.launch"):
        assert sum(s[0] == name for s in first) == chunks, name
    assert sum(s[0] == "aquifer.scatter.verify" for s in first) == 1
    assert not [s for s in _inside(hot[1], sps)
                if s[0] in ("aquifer.restore.install", "aquifer.scatter.verify")]
    (cold,) = [s for s in inner if s[0] == "aquifer.restore.cold"]
    in_cold = [s[0] for s in _inside(cold, sps)]
    assert in_cold.count("aquifer.restore.rdma_read") == in_cold.count(
        "aquifer.restore.install") >= 1
    assert in_cold.count("aquifer.scatter.verify") == 1
