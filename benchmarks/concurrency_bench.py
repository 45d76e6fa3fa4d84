"""Co-located restore concurrency benchmark: shared NodePageServer vs the
per-instance engine baseline (ISSUE 3 acceptance bench).

For each sweep point we publish snapshot(s), attach `conc` co-located
restores on ONE host, and drive every restore to full completion (hot
pre-install + zero ranges + background cold-extent prefetch) with REAL byte
movement through the pool emulation.  Two runtimes are compared:

  shared   : one host-wide AsyncRDMAEngine + completion worker + DRR
             prefetch pump for all restores, with hot-chunk / cold-extent
             fan-out across same-snapshot restores (core/nodeserver.py).
  perinst  : the legacy path — a private engine, completion thread and
             prefetcher per restore; each restore registers as its own
             stream on the host link arbiters, so its modeled time sees
             the same fair-share contention model.

Scenarios: `same` (all `conc` restores of ONE snapshot — the fan-out
regime) and `mixed` (each restore its own snapshot).  Per point we report
per-instance modeled restore time (p50/p99), aggregate modeled throughput
(restored bytes / modeled makespan), bit-identity of every restore, and
the worst relative error of the executed modeled time against the analytic
`strategies.modeled_concurrent_restore_s` (`_shared()`-based) model.

Acceptance (checked into the emitted json): at concurrency >= 8 same-
snapshot the shared runtime must show >= 1.5x aggregate modeled throughput
vs the baseline, every restore bit-identical, and executed modeled time
within 15% of the analytic model across the whole sweep.

Results land in experiments/concurrency_bench.json (full sweep) or
experiments/concurrency_bench_quick.json (--quick CI smoke, <= 5 s).
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

from repro.core import (HierarchicalPool, LayoutOrderPolicy, Orchestrator,
                        PoolMaster, StateImage)
from repro.core.pagestore import PAGE_SIZE
from repro.core.profiler import AccessRecorder
from repro.serve.strategies import modeled_concurrent_restore_s

OUT = Path(__file__).resolve().parents[1] / "experiments"

FULL_CONCS_SAME = (1, 2, 4, 8, 16, 32)
FULL_CONCS_MIXED = (1, 2, 4, 8)
QUICK_CONCS_SAME = (1, 8)


def make_restore_image(seed: int = 0, hot_pages: int = 512,
                       cold_pages: int = 1536, zero_pages: int = 2048):
    """Snapshot-shaped image: contiguous hot params + a cold runtime mass
    with a few short hot spans (Fig-4 fragmentation) + a zero arena."""
    rng = np.random.default_rng(seed)
    arrays = {
        "params": rng.standard_normal(hot_pages * PAGE_SIZE // 4).astype(np.float32),
        "runtime": rng.integers(1, 7, (cold_pages * PAGE_SIZE,)).astype(np.uint8),
        "arena": np.zeros(zero_pages * PAGE_SIZE, np.uint8),
    }
    img = StateImage.build(arrays)
    rec = AccessRecorder(img.manifest)
    rec.touch_array("params")
    rt = img.manifest.by_name()["runtime"]
    for s in range(7, cold_pages - 4, max(8, cold_pages // 24)):
        rec.touch_pages(range(rt.first_page + s, rt.first_page + s + 2))
    return img, rec.working_set()


def run_point(conc: int, shared: bool, same_snapshot: bool, images,
              max_extent_pages: int = 64) -> dict:
    pool = HierarchicalPool(cxl_capacity=512 << 20, rdma_capacity=1 << 30)
    master = PoolMaster(pool)
    n_snaps = 1 if same_snapshot else conc
    for i in range(n_snaps):
        img, ws = images[i]
        master.publish(f"snap{i}", img, ws)
    policy = LayoutOrderPolicy(max_extent_pages)
    orch = Orchestrator("host0", pool, master.catalog, use_async_rdma=True,
                        use_node_server=shared, prefetch_policy=policy)
    # attach every restore BEFORE any page movement so all `conc` streams
    # contend for the whole restore window (the load balancer dispatching a
    # co-located burst), then drive them concurrently to completion
    ris = []
    for k in range(conc):
        ri = orch.restore(f"snap{0 if same_snapshot else k}",
                          pre_install=False, prefetch_cold=False)
        assert ri is not None
        ris.append(ri)
    errs = []

    def drive(ri):
        try:
            ri.engine.pre_install_hot()
            ri.engine.install_zero_runs()
            ri.engine.start_prefetcher(policy=policy)
            if not ri.engine.wait_prefetch_idle(120.0):
                raise TimeoutError("prefetch did not complete")
        except Exception as exc:            # pragma: no cover
            errs.append(exc)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=drive, args=(ri,)) for ri in ris]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0
    assert not errs, errs

    groups = 1 if (shared and same_snapshot) else conc
    times, model_errs, identical = [], [], True
    for k, ri in enumerate(ris):
        src = images[0 if same_snapshot else k][0]
        ok = bool(ri.instance.present.all()) and \
            bool(np.array_equal(ri.instance.image_bytes(), src.buf))
        identical = identical and ok
        t_exec = ri.ledger.total()
        t_model = modeled_concurrent_restore_s(ri.engine.reader, groups,
                                               max_extent_pages)
        times.append(t_exec)
        model_errs.append(abs(t_exec - t_model) / t_model)
    bytes_total = sum(images[0 if same_snapshot else k][0].buf.nbytes
                      for k in range(conc))
    makespan = max(times)
    for ri in ris:
        ri.shutdown()
    orch.close()
    times_a = np.asarray(times)
    return {
        "conc": conc,
        "mode": "shared" if shared else "perinst",
        "scenario": "same" if same_snapshot else "mixed",
        "restore_p50_ms": float(np.percentile(times_a, 50) * 1e3),
        "restore_p99_ms": float(np.percentile(times_a, 99) * 1e3),
        "restore_max_ms": float(makespan * 1e3),
        "agg_throughput_GBps": bytes_total / makespan / 1e9,
        "model_err_max": float(max(model_errs)),
        "bit_identical": identical,
        "wall_s": wall_s,
    }


def run(quick: bool = False) -> dict:
    kw = dict(hot_pages=256, cold_pages=512, zero_pages=768) if quick else {}
    concs_same = QUICK_CONCS_SAME if quick else FULL_CONCS_SAME
    concs_mixed = () if quick else FULL_CONCS_MIXED
    n_images = max((1,) + tuple(concs_mixed))
    images = [make_restore_image(seed=i, **kw) for i in range(n_images)]

    rows = []
    for conc in concs_same:
        for shared in (False, True):
            rows.append(run_point(conc, shared, same_snapshot=True, images=images))
    for conc in concs_mixed:
        for shared in (False, True):
            rows.append(run_point(conc, shared, same_snapshot=False, images=images))

    def tput(conc, mode, scen):
        return next(r["agg_throughput_GBps"] for r in rows
                    if r["conc"] == conc and r["mode"] == mode
                    and r["scenario"] == scen)

    gains = {c: tput(c, "shared", "same") / tput(c, "perinst", "same")
             for c in concs_same}
    model_err_max = max(r["model_err_max"] for r in rows)
    criteria = {
        "gain_same_snapshot_by_conc": {str(c): g for c, g in gains.items()},
        "gain_at_conc_ge_8": min((g for c, g in gains.items() if c >= 8),
                                 default=None),
        "gain_ok": all(g >= 1.5 for c, g in gains.items() if c >= 8),
        "model_err_max": model_err_max,
        "model_within_15pct": model_err_max <= 0.15,
        "all_bit_identical": all(r["bit_identical"] for r in rows),
    }
    out = {"rows": rows, "criteria": criteria, "quick": quick}
    OUT.mkdir(exist_ok=True)
    name = "concurrency_bench_quick.json" if quick else "concurrency_bench.json"
    (OUT / name).write_text(json.dumps(out, indent=2))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="2-point same-snapshot smoke (CI fast tier, <=5s)")
    args = ap.parse_args()
    out = run(quick=args.quick)
    hdr = (f"{'conc':>5s} {'scenario':>9s} {'mode':>8s} {'p50(ms)':>9s} "
           f"{'p99(ms)':>9s} {'agg GB/s':>9s} {'model err':>10s}  ok")
    print(hdr)
    for r in out["rows"]:
        print(f"{r['conc']:5d} {r['scenario']:>9s} {r['mode']:>8s} "
              f"{r['restore_p50_ms']:9.2f} {r['restore_p99_ms']:9.2f} "
              f"{r['agg_throughput_GBps']:9.2f} {r['model_err_max']:10.3f}  "
              f"{r['bit_identical']}")
    c = out["criteria"]
    print(f"\nshared-vs-perinst same-snapshot gain: "
          + ", ".join(f"{k}x{v:.2f}" for k, v in
                      c["gain_same_snapshot_by_conc"].items()))
    print(f"gain at conc>=8 >= 1.5x: {c['gain_ok']}   "
          f"model within 15%: {c['model_within_15pct']} "
          f"(max err {c['model_err_max']:.3f})   "
          f"all bit-identical: {c['all_bit_identical']}")
    # CI gate: a corruption or throughput/model regression must fail the job
    if not (c["gain_ok"] and c["model_within_15pct"] and c["all_bit_identical"]):
        sys.exit(1)


if __name__ == "__main__":
    main()
