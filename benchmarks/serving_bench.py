"""Restore-path microbenchmark: per-page vs run-coalesced batched serving.

For each workload we publish the snapshot once, then perform two REAL
restores (actual byte movement through the pool emulation) with fresh
incoherent host views:

  per_page : the strictly page-at-a-time path — one HostView read + one
             lock-acquiring uffd.copy per 4 KiB page, one RDMA read per
             cold page.
  batched  : the run-coalesced path — chunked CXL streaming over the
             compact hot region, one uffd ioctl per guest-contiguous run,
             one RDMA read per cold extent.

Both must produce bit-identical images; the batched path must never model
more time than the per-page path and must install exactly the same bytes.
With ``zstandard`` available the same comparison runs against a
zstd-compressed cold tier.  Results land in experiments/serving_bench.json.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.core import HierarchicalPool, PoolMaster, StateImage
from repro.core.serving import Instance, RestoreEngine
from repro.core.snapshot import SnapshotReader, _zstd
from repro.core.pool import TimeLedger
from .workloads import all_workloads, get_workload

OUT = Path(__file__).resolve().parents[1] / "experiments"


def _one_restore(pool, regions, image, mode: str) -> dict:
    batched = mode == "batched"
    ledger = TimeLedger()
    view = pool.host_view(f"bench-{mode}", ledger)
    reader = SnapshotReader(regions, view, pool.rdma)
    reader.invalidate_cxl()
    inst = Instance(StateImage.empty_like(image.manifest), ledger)
    eng = RestoreEngine(reader, inst, rdma_engine=None)

    t0 = time.perf_counter()
    eng.pre_install_hot(use_batch=batched)
    pre_s = {k: v for k, v in ledger.seconds.items()}
    eng.install_all_sync(use_batch=batched)
    wall_s = time.perf_counter() - t0

    return {
        "preinstall_modeled_s": pre_s.get("cxl_read", 0.0) + pre_s.get("uffd_copy", 0.0),
        "total_modeled_s": ledger.total(),
        "ledger_s": dict(ledger.seconds),
        "wall_s": wall_s,
        "bit_identical": bool(np.array_equal(inst.image_bytes(), image.buf)),
        "bytes_installed": inst.stats["bytes_installed"],
        "cxl_bytes_read": view.stats["bytes_read"],
        "uffd_batches": inst.stats["uffd_batches"],
        "uffd_copies": inst.stats["uffd_copies"],
    }


def bench_workload(name: str, compress_cold: bool = False) -> dict:
    bw = get_workload(name)
    pool = HierarchicalPool(cxl_capacity=1 << 30, rdma_capacity=2 << 30)
    master = PoolMaster(pool)
    regions = master.publish(name, bw.image, bw.profile.working_set,
                             compress_cold=compress_cold)
    modes = {m: _one_restore(pool, regions, bw.image, m)
             for m in ("per_page", "batched")}
    pp, bt = modes["per_page"], modes["batched"]
    row = {
        "workload": name,
        "cold_compressed": bool(regions.cold_compressed),
        "modes": modes,
        "preinstall_speedup": pp["preinstall_modeled_s"] / max(bt["preinstall_modeled_s"], 1e-12),
        "total_speedup": pp["total_modeled_s"] / max(bt["total_modeled_s"], 1e-12),
        "bit_identical_both": pp["bit_identical"] and bt["bit_identical"],
        "bytes_match": pp["bytes_installed"] == bt["bytes_installed"],
        "batched_not_slower": bt["total_modeled_s"] <= pp["total_modeled_s"] + 1e-12,
    }
    return row


def run(workloads=None) -> dict:
    names = list(workloads) if workloads else all_workloads()
    rows = [bench_workload(n) for n in names]
    rows_z = [bench_workload(n, compress_cold=True) for n in names] if _zstd else []
    ok = all(r["bit_identical_both"] and r["bytes_match"] and r["batched_not_slower"]
             for r in rows + rows_z)
    out = {
        "rows": rows,
        "rows_compressed_cold": rows_z,
        "zstd_available": _zstd is not None,
        "all_bit_identical_and_not_slower": ok,
    }
    OUT.mkdir(exist_ok=True)
    (OUT / "serving_bench.json").write_text(json.dumps(out, indent=2))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="chameleon only (CI smoke)")
    ap.add_argument("--workloads", nargs="*", default=None)
    args = ap.parse_args()
    names = ["chameleon"] if args.quick else args.workloads
    out = run(names)
    print(f"{'workload':14s}{'pre pp(ms)':>11s}{'pre bt(ms)':>11s}{'x':>6s}"
          f"{'tot pp(ms)':>11s}{'tot bt(ms)':>11s}{'x':>6s}  ok")
    for r in out["rows"] + out["rows_compressed_cold"]:
        pp, bt = r["modes"]["per_page"], r["modes"]["batched"]
        tag = r["workload"] + ("+z" if r["cold_compressed"] else "")
        print(f"{tag:14s}{pp['preinstall_modeled_s']*1e3:11.2f}"
              f"{bt['preinstall_modeled_s']*1e3:11.2f}{r['preinstall_speedup']:6.2f}"
              f"{pp['total_modeled_s']*1e3:11.2f}{bt['total_modeled_s']*1e3:11.2f}"
              f"{r['total_speedup']:6.2f}  "
              f"{r['bit_identical_both'] and r['bytes_match'] and r['batched_not_slower']}")
    print(f"all bit-identical & batched never slower: {out['all_bit_identical_and_not_slower']}")


if __name__ == "__main__":
    main()
