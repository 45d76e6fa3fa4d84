"""Fault-tolerance benchmark (ISSUE 8 acceptance).

Four sweeps restore the same fused-published snapshot through the
production serving path (``RestoreEngine.install_all_sync`` with a
checksum-verifying ``FusedScatter``), each under a different deterministic
fault schedule on the REAL tiers:

* **none** — the fault-free baseline, run twice: once with no injector and
  once with an armed-but-EMPTY ``FaultInjector`` (plus the attached
  ``TierHealth`` breakers).  The two per-restore cost ledgers must be
  byte-identical — the headline *fault-free overhead of the fault seam is
  exactly 0 modeled seconds*;
* **rdma_timeouts** — two injected RNIC read timeouts per restore; the
  engine's seeded retry/backoff machinery re-issues and every restore
  still ends bit-identical, with the wasted wire time and backoff charged
  to modeled time;
* **cxl_poison** — one injected per-page poison per restore on a hot
  page's home offset; the checksum mismatch is detected at install time
  and repaired from the (clean) home tier within the repair budget;
* **brownout** — a CXL host-link brownout covering the whole run; the
  breaker opens and every restore completes DEGRADED over the RDMA-only
  path (never fails), at the modeled all-cold cost
  (``strategies.modeled_degraded_restore_s``).

All reported keys are modeled/deterministic under ``VirtualClock`` (fixed
default seed; CI's regression gate holds them to ±10%, booleans exactly).
Results land in ``experiments/fault_bench.json`` (full) or
``fault_bench_quick.json`` (``--quick`` CI smoke).
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import numpy as np

from repro.core import (
    FaultInjector,
    HierarchicalPool,
    Instance,
    PoolMaster,
    RestoreEngine,
    SnapshotReader,
    StateImage,
    TimeLedger,
)
from repro.core.pagestore import PAGE_SIZE
from repro.kernels.snapshot_fuse import FusedScatter, make_fused_publish_fn
from repro.serve.strategies import (
    modeled_concurrent_restore_s,
    modeled_degraded_restore_s,
)
from repro.sim import VirtualClock

OUT = Path(__file__).resolve().parents[1] / "experiments"
SEED = int(os.environ.get("AQUIFER_SIM_SEED", "0"))

SWEEPS = ("none", "rdma_timeouts", "cxl_poison", "brownout")


def make_image(hot_pages: int, cold_pages: int, zero_pages: int,
               seed: int = SEED):
    rng = np.random.default_rng(seed + 7)
    img = StateImage.build({
        "w": rng.integers(1, 255, hot_pages * PAGE_SIZE).astype(np.uint8),
        "cold": rng.integers(1, 255, cold_pages * PAGE_SIZE).astype(np.uint8),
        "z": np.zeros(zero_pages * PAGE_SIZE, np.uint8),
    })
    return img, list(range(hot_pages))


def make_stack(img, ws):
    """Fresh pool + fused publish (so restores carry a checksum table)."""
    pool = HierarchicalPool(cxl_capacity=1 << 30, rdma_capacity=1 << 30)
    master = PoolMaster(pool)
    master.publish("snap", img, ws,
                   publish_fn=make_fused_publish_fn(use_pallas=False))
    borrow = master.catalog.borrow("snap")
    assert borrow is not None
    return pool, master, borrow


def injector_for(sweep: str, r: int, pool, borrow, clock) -> FaultInjector:
    """The per-restore fault schedule.  A FRESH injector per restore keeps
    the counts exact (2 timeouts / 1 poison each) regardless of how a
    previous restore's repairs consumed its windows."""
    inj = FaultInjector(clock=clock, seed=SEED + r)
    if sweep == "rdma_timeouts":
        inj.fail_reads("rdma", 2)
    elif sweep == "cxl_poison":
        probe = SnapshotReader(borrow.regions,
                               pool.host_view(f"probe{r}"), pool.rdma)
        _kind, off = probe.lookup(int(probe.hot_page_indices()[0]))
        inj.poison_reads("cxl", 1, lo=off, hi=off + PAGE_SIZE)
    elif sweep == "brownout":
        inj.brownout("cxl", start_s=0.0, duration_s=1e9)
    return inj


def run_sweep(sweep: str, n_restores: int, img, ws, armed: bool = True):
    """``n_restores`` sequential production restores under one schedule
    kind; returns per-restore modeled seconds + fault/repair accounting."""
    clock = VirtualClock()
    pool, _master, borrow = make_stack(img, ws)
    restore_s, ledgers = [], []
    ok = True
    totals = {"retries": 0, "repairs": 0, "degraded": 0, "injected": 0}
    for r in range(n_restores):
        if armed:
            pool.attach_fault_injector(injector_for(sweep, r, pool, borrow,
                                                    clock))
        led = TimeLedger()
        view = pool.host_view(f"h{r}", led)
        reader = SnapshotReader(borrow.regions, view, pool.rdma)
        reader.invalidate_cxl()
        inst = Instance(StateImage.empty_like(img.manifest), ledger=led,
                        clock=clock)
        eng = RestoreEngine(reader, inst, None, retry_seed=r,
                            scatter_fn=FusedScatter(use_pallas=False),
                            clock=clock)
        eng.install_all_sync(use_batch=True)
        ok = ok and bool(inst.all_present()
                         and np.array_equal(inst.image_bytes(), img.buf))
        restore_s.append(float(led.total()))
        ledgers.append(dict(led.seconds))
        totals["retries"] += len(eng.retry_trace)
        totals["repairs"] += eng.repair_stats["checksum_repairs"]
        totals["degraded"] += int(eng.degraded_cxl)
        if armed:
            fi = pool.fault_injector
            totals["injected"] += (fi.stats["injected_timeouts"]
                                   + fi.stats["injected_poison"]
                                   + fi.stats["brownout_rejections"])
    arr = np.asarray(restore_s)
    bytes_per_restore = img.buf.nbytes
    return {
        "n_restores": n_restores,
        "p50_modeled_ms": float(np.percentile(arr, 50) * 1e3),
        "p99_modeled_ms": float(np.percentile(arr, 99) * 1e3),
        "total_modeled_s": float(arr.sum()),
        "goodput_GBps": float(n_restores * bytes_per_restore
                              / max(arr.sum(), 1e-12) / 1e9),
        "total_retries": totals["retries"],
        "total_repairs": totals["repairs"],
        "n_degraded": totals["degraded"],
        "total_injected": totals["injected"],
        "all_bit_identical": ok,
        "_ledgers": ledgers,
    }


def degraded_model_ms(img, ws) -> dict:
    """The analytic healthy vs degraded restore models over this layout."""
    pool, _master, borrow = make_stack(img, ws)
    reader = SnapshotReader(borrow.regions, pool.host_view("model"),
                            pool.rdma)
    return {
        "healthy_ms": float(modeled_concurrent_restore_s(reader, 1) * 1e3),
        "degraded_ms": float(modeled_degraded_restore_s(reader, 1) * 1e3),
    }


def run(quick: bool = False) -> dict:
    if quick:
        n_restores, hot, cold, zero = 8, 64, 64, 32
    else:
        n_restores, hot, cold, zero = 32, 256, 256, 128
    img, ws = make_image(hot, cold, zero)

    # fault-free overhead: bare stack vs armed-but-empty injector
    bare = run_sweep("none", n_restores, img, ws, armed=False)
    sweeps = {s: run_sweep(s, n_restores, img, ws) for s in SWEEPS}
    fault_free_identical = sweeps["none"]["_ledgers"] == bare["_ledgers"]
    overhead_pct = (
        0.0 if fault_free_identical
        else abs(sweeps["none"]["total_modeled_s"] - bare["total_modeled_s"])
        / max(bare["total_modeled_s"], 1e-12) * 100.0)
    model = degraded_model_ms(img, ws)

    criteria = {
        "fault_free_overhead_zero": bool(fault_free_identical),
        "all_bit_identical": bool(all(sweeps[s]["all_bit_identical"]
                                      for s in SWEEPS)),
        "retries_recovered": bool(sweeps["rdma_timeouts"]["total_retries"] > 0
                                  and sweeps["rdma_timeouts"]
                                  ["all_bit_identical"]),
        "repairs_happened": bool(sweeps["cxl_poison"]["total_repairs"]
                                 == n_restores),
        "brownout_degrades_not_fails": bool(
            sweeps["brownout"]["n_degraded"] == n_restores
            and sweeps["brownout"]["all_bit_identical"]),
        "degraded_costs_more": bool(
            sweeps["brownout"]["p50_modeled_ms"]
            > sweeps["none"]["p50_modeled_ms"]
            and model["degraded_ms"] > model["healthy_ms"]),
        # the degraded path's EXECUTED ledger must track the analytic
        # all-cold model (ISSUE 8: "modeled time matching the strategies
        # module's all-cold cost")
        "degraded_model_within_15pct": bool(
            abs(sweeps["brownout"]["p50_modeled_ms"] - model["degraded_ms"])
            <= 0.15 * model["degraded_ms"]),
    }
    for s in sweeps.values():
        s.pop("_ledgers")
    bare.pop("_ledgers")
    out = {
        "quick": quick, "seed": SEED,
        "workload": {"n_restores": n_restores, "hot_pages": hot,
                     "cold_pages": cold, "zero_pages": zero},
        "fault_free_overhead_pct": overhead_pct,
        "sweeps": sweeps,
        "degraded_model": model,
        "criteria": criteria,
    }
    OUT.mkdir(exist_ok=True)
    name = "fault_bench_quick.json" if quick else "fault_bench.json"
    (OUT / name).write_text(json.dumps(out, indent=2))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke (small snapshot, fewer restores)")
    args = ap.parse_args()
    out = run(quick=args.quick)
    w = out["workload"]
    print(f"workload: {w['n_restores']} restores x "
          f"({w['hot_pages']} hot + {w['cold_pages']} cold + "
          f"{w['zero_pages']} zero) pages, seed {out['seed']}")
    print(f"fault-free overhead of the armed seam: "
          f"{out['fault_free_overhead_pct']:.3f}%")
    for s in SWEEPS:
        r = out["sweeps"][s]
        print(f"  {s:14s} p50 {r['p50_modeled_ms']:8.3f} ms  "
              f"p99 {r['p99_modeled_ms']:8.3f} ms  "
              f"retries {r['total_retries']:3d}  repairs "
              f"{r['total_repairs']:3d}  degraded {r['n_degraded']:3d}  "
              f"{'bit-identical' if r['all_bit_identical'] else 'CORRUPT'}")
    m = out["degraded_model"]
    print(f"analytic restore model: healthy {m['healthy_ms']:.3f} ms vs "
          f"degraded (RDMA-only) {m['degraded_ms']:.3f} ms")
    ok = all(out["criteria"].values())
    print(f"criteria: {out['criteria']}  ->  {'PASS' if ok else 'FAIL'}")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
