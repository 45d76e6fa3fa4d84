"""Content-addressed dedup benchmark (ISSUE 5 acceptance).

A serverless fleet restores many snapshots of near-identical images — the
same base model fine-tuned into N variants (exactly what ``configs/`` +
``models/model_zoo.py`` emulate, shrunk to synthetic pages here).  Without
dedup every publish stores its own copy of the shared base pages, so the
PR-4 ``CXLCapacityManager`` demotes/degrades most of the fleet at any
realistic budget.  With the content-addressed store a variant's marginal
CXL cost is its DELTA pages plus metadata, so the same budget keeps a
multiple of the fleet hot.

Two pods with the SAME CXL budget publish the SAME variant fleet — one with
``dedup=True``, one without.  Reported:

* **effective-capacity multiplier** — snapshots resident with their full
  hot set (never demoted/degraded) under dedup vs baseline; the acceptance
  bar is >= 1.5x;
* **unique-byte ratio** — physical store bytes / logical fleet bytes;
* **bit-identical restores** — every variant in BOTH pods is fully
  restored through the production serving path and byte-compared;
* **modeled publish/restore costs** — ``strategies.dedup_publish_cost_s``
  over the measured unique counts, the analytic restore model over the
  dedup layout, and the ``dedup_economics`` break-even verdict;
* **I6 spot-check** — at the end, each store's refcounts must equal the
  catalog's live offset pointers exactly.

All compared keys are modeled/deterministic (fixed default seed; CI's
regression gate holds them to ±10%).  Results land in
``experiments/dedup_bench.json`` (full) or ``dedup_bench_quick.json``
(``--quick`` CI smoke).
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import numpy as np

from repro.core import (
    HierarchicalPool,
    Instance,
    PoolMaster,
    RestoreEngine,
    SnapshotReader,
    StateImage,
    decode_dedup_offsets,
)
from repro.core.pagestore import PAGE_SIZE
from repro.core.pool import TIER_CXL, TIER_RDMA
from repro.serve.strategies import (
    HOT_CHUNK_PAGES,
    baseline_publish_cost_s,
    dedup_economics,
    dedup_publish_cost_s,
    modeled_concurrent_restore_s,
)

OUT = Path(__file__).resolve().parents[1] / "experiments"
SEED = int(os.environ.get("AQUIFER_SIM_SEED", "0"))


def make_fleet(n_variants: int, hot_pages: int, cold_pages: int,
               zero_pages: int, delta_pages: int, seed: int = SEED):
    """N fine-tuned variants: shared base weights + per-variant delta rows +
    per-variant cold arena (deltas and arenas are variant-unique)."""
    rng = np.random.default_rng(seed)
    base = rng.integers(1, 255, hot_pages * PAGE_SIZE, dtype=np.int64).astype(np.uint8)
    fleet = []
    for v in range(n_variants):
        w = base.copy()
        lo = (v * delta_pages) % hot_pages
        for d in range(delta_pages):
            p = (lo + d) % hot_pages
            w[p * PAGE_SIZE : (p + 1) * PAGE_SIZE] = \
                rng.integers(1, 255, PAGE_SIZE).astype(np.uint8)
        img = StateImage.build({
            "w": w,
            "cold": rng.integers(1, 255, cold_pages * PAGE_SIZE).astype(np.uint8),
            "z": np.zeros(zero_pages * PAGE_SIZE, np.uint8),
        })
        fleet.append(img)
    return fleet


def restore_and_verify(pool, master, name, img):
    """Full production restore (borrow → flush → extent-walk install) and
    byte-compare; returns (bit_identical, executed modeled seconds)."""
    borrow = master.catalog.borrow(name)
    assert borrow is not None, f"borrow of {name} failed"
    try:
        reader = SnapshotReader(borrow.regions, pool.host_view(f"r-{name}"),
                                pool.rdma)
        reader.invalidate_cxl()
        inst = Instance(StateImage.empty_like(img.manifest))
        eng = RestoreEngine(reader, inst, rdma_engine=None)
        eng.install_all_sync()
        ok = bool(inst.all_present()
                  and np.array_equal(inst.image_bytes(), img.buf))
        return ok, float(inst.ledger.total())
    finally:
        borrow.release()


def run_pod(fleet, budget_bytes, dedup: bool):
    """Publish the whole fleet into one budgeted pod; restore + verify all."""
    pool = HierarchicalPool(cxl_capacity=1 << 30, rdma_capacity=1 << 30)
    master = PoolMaster(pool, cxl_budget=budget_bytes, dedup=dedup)
    publishes = []
    for v, img in enumerate(fleet):
        ws = list(range(img.manifest.by_name()["w"].page_count))
        before_hot = pool.dedup_cxl.unique_pages()
        before_cold = pool.dedup_rdma.unique_pages()
        regions = master.publish(f"v{v}", img, ws)
        publishes.append({
            "n_hot": regions.n_hot, "n_cold": regions.n_cold,
            "new_unique_hot": pool.dedup_cxl.unique_pages() - before_hot,
            "new_unique_cold": pool.dedup_rdma.unique_pages() - before_cold,
        })
    full_hot = fleet[0].manifest.by_name()["w"].page_count
    resident = sum(1 for e in master.catalog.entries
                   if e.regions is not None and e.regions.n_hot == full_hot)
    restores_ok, exec_restore_s = [], 0.0
    sample_reader = None
    for v, img in enumerate(fleet):
        ok, t = restore_and_verify(pool, master, f"v{v}", img)
        restores_ok.append(ok)
        exec_restore_s += t
    # analytic restore model over a RESIDENT snapshot's actual layout
    for e in master.catalog.entries:
        if e.regions is not None and e.regions.n_hot == full_hot:
            sample_reader = SnapshotReader(e.regions, pool.host_view("model"),
                                           pool.rdma)
            break
    restore_modeled_s = (modeled_concurrent_restore_s(sample_reader, 1)
                         if sample_reader is not None else 0.0)
    report = master.capacity.report()
    return {
        "pool": pool, "master": master, "publishes": publishes,
        "resident_full_hot": resident,
        "demotions": report["demotions"], "degraded": report["degraded"],
        "shared_skips": report["shared_skips"],
        "budget_in_use": report["in_use"],
        "all_bit_identical": bool(all(restores_ok)),
        "exec_restore_total_s": exec_restore_s,
        "restore_modeled_s": restore_modeled_s,
        "sample_reader": sample_reader,
    }


def i6_spot_check(pool, master) -> bool:
    """Store refcounts == live catalog offset pointers, per tier."""
    regions = [e.regions for e in master.catalog.entries
               if e.regions is not None and e.regions.dedup]
    for store, tag in ((pool.dedup_cxl, TIER_CXL), (pool.dedup_rdma, TIER_RDMA)):
        expected = {}
        for r in regions:
            uniq, counts = np.unique(decode_dedup_offsets(pool, r, tag),
                                     return_counts=True)
            for off, k in zip(uniq, counts):
                expected[int(off)] = expected.get(int(off), 0) + int(k)
        if expected != store.refcounts():
            return False
    return True


def count_extents(reader):
    n_hot_ext = sum(1 for _ in reader.iter_hot_extents(HOT_CHUNK_PAGES))
    n_cold_ext = sum(1 for _ in reader.iter_cold_extents())
    return n_hot_ext, n_cold_ext


def run(quick: bool = False) -> dict:
    if quick:
        n_variants, hot, cold, zero, delta = 8, 64, 32, 16, 4
    else:
        n_variants, hot, cold, zero, delta = 24, 256, 128, 64, 12
    fleet = make_fleet(n_variants, hot, cold, zero, delta)
    # per-snapshot private CXL need ≈ metadata (2 pages) + full hot set;
    # budget fits ~1/3 of the fleet without dedup
    per_snapshot = (2 + hot) * PAGE_SIZE
    budget = (n_variants // 3) * per_snapshot

    ded = run_pod(fleet, budget, dedup=True)
    base = run_pod(fleet, budget, dedup=False)

    pool = ded["pool"]
    cxl_rep = pool.dedup_cxl.report()
    rdma_rep = pool.dedup_rdma.report()
    logical = cxl_rep["logical_bytes"] + rdma_rep["logical_bytes"]
    unique = cxl_rep["unique_bytes"] + rdma_rep["unique_bytes"]

    # modeled publish costs over the measured PER-TIER unique counts
    ded_publish_s = sum(
        dedup_publish_cost_s(p["n_hot"], p["n_cold"],
                             p["new_unique_hot"], p["new_unique_cold"])
        for p in ded["publishes"])
    base_publish_s = sum(baseline_publish_cost_s(p["n_hot"], p["n_cold"])
                         for p in base["publishes"])

    # fragmentation penalty of the dedup layout, from a resident reader
    econ = None
    if ded["sample_reader"] is not None:
        n_hot_ext, n_cold_ext = count_extents(ded["sample_reader"])
        contiguous_hot_ext = -(-hot // HOT_CHUNK_PAGES)
        econ = dedup_economics(
            n_hot=n_variants * hot, n_cold=n_variants * cold,
            n_hot_unique=cxl_rep["unique_pages"],
            n_cold_unique=rdma_rep["unique_pages"],
            n_extra_hot_extents=max(0, n_hot_ext - contiguous_hot_ext),
            n_extra_cold_extents=max(0, n_cold_ext - 1),
            expected_restores=64)

    multiplier = (ded["resident_full_hot"] / base["resident_full_hot"]
                  if base["resident_full_hot"] else float(ded["resident_full_hot"]))
    criteria = {
        "capacity_x_ge_1_5": bool(multiplier >= 1.5),
        "all_restores_bit_identical": bool(ded["all_bit_identical"]
                                           and base["all_bit_identical"]),
        "i6_consistent": i6_spot_check(pool, ded["master"]),
        "dedup_worthwhile": bool(econ is None or econ["worthwhile"]),
    }
    drop = ("pool", "master", "publishes", "sample_reader")
    out = {
        "quick": quick, "seed": SEED,
        "fleet": {"n_variants": n_variants, "hot_pages": hot,
                  "cold_pages": cold, "zero_pages": zero,
                  "delta_pages": delta, "budget_bytes": budget,
                  "per_snapshot_cxl_bytes": per_snapshot},
        "dedup": {**{k: v for k, v in ded.items() if k not in drop},
                  "unique_byte_ratio": unique / logical if logical else 1.0,
                  "unique_bytes": unique, "logical_bytes": logical,
                  "publish_modeled_s": ded_publish_s,
                  "store_cxl": cxl_rep, "store_rdma": rdma_rep,
                  "economics": econ},
        "baseline": {**{k: v for k, v in base.items() if k not in drop},
                     "publish_modeled_s": base_publish_s},
        "effective_capacity_x": multiplier,
        "criteria": criteria,
    }
    OUT.mkdir(exist_ok=True)
    name = "dedup_bench_quick.json" if quick else "dedup_bench.json"
    (OUT / name).write_text(json.dumps(out, indent=2))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI smoke (small fleet)")
    args = ap.parse_args()
    out = run(quick=args.quick)
    f, d, b = out["fleet"], out["dedup"], out["baseline"]
    print(f"fleet: {f['n_variants']} variants x {f['hot_pages']} hot pages, "
          f"budget {f['budget_bytes'] >> 10} KiB")
    print(f"resident with full hot set: dedup {d['resident_full_hot']} vs "
          f"baseline {b['resident_full_hot']} "
          f"-> {out['effective_capacity_x']:.2f}x effective capacity")
    print(f"unique-byte ratio: {d['unique_byte_ratio']:.3f} "
          f"({d['unique_bytes'] >> 10} KiB physical / "
          f"{d['logical_bytes'] >> 10} KiB logical)")
    print(f"publish modeled: dedup {d['publish_modeled_s']*1e3:.3f} ms vs "
          f"baseline {b['publish_modeled_s']*1e3:.3f} ms; restore modeled "
          f"{d['restore_modeled_s']*1e3:.3f} ms vs {b['restore_modeled_s']*1e3:.3f} ms")
    if d["economics"]:
        print(f"economics: net {d['economics']['net_s']:.4f} s over "
              f"{int(d['economics']['expected_restores'])} restores "
              f"({'worthwhile' if d['economics']['worthwhile'] else 'NOT worthwhile'})")
    ok = all(out["criteria"].values())
    print(f"criteria: {out['criteria']}  ->  {'PASS' if ok else 'FAIL'}")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
