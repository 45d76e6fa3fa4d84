"""Kernel benchmark + calibration for the snapshot data plane (DESIGN.md §13).

Three layers, cleanly separated so CI can gate what is deterministic:

* **modeled** — roofline byte-math for the piecemeal op sequence vs the fused
  ops at a canonical workload (tier-independent), via
  ``roofline.analysis.movement_roofline``.  Pure arithmetic ⇒ bit-equal
  across runs; these are the keys ``check_regressions.py`` gates at ±10%.
* **measured** — wall-clock with the timing discipline the old bench lacked:
  first call (compile) timed separately, then warm steady-state reps with
  ``jax.block_until_ready``, GB/s reported.  ``--quick`` runs the Pallas
  kernels at tiny shapes (compiled on a TPU; in interpret mode elsewhere,
  the fast CI tier); the
  default tier runs the dispatch path (compiled Pallas on TPU, jit'd oracle
  elsewhere) at large shapes (nightly).  Wall-clock is informational — this
  box is not the target — and is never gated.
* **calibration** — ``--write-calibration`` derives per-page constants from
  the fused ops' *actual* per-invocation traffic at the platform HBM roof
  and writes ``experiments/kernel_calibration.json``; ``serve/strategies.py``
  sources ``CHECKSUM_BW`` / ``PUBLISH_SWEEP_PAGE_S`` / ``PREINSTALL_PAGE_S``
  from the committed copy at import (file-read only, never re-measured).

The bench also asserts fused-vs-piecemeal bit-identity on the shapes it
times (``criteria.bit_identical``) and reports the Python/dispatch overhead
fraction of each path — the tentpole's "both hot paths bandwidth-bound, with
the Python-overhead fraction reported" line.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core.pagestore import PAGE_SIZE
from repro.kernels.backend import on_tpu
from repro.kernels import (
    fused_publish,
    fused_restore,
    page_checksum,
    page_gather,
    page_scatter,
    zero_detect,
)
from repro.roofline.analysis import HBM_BW, movement_roofline

OUT = Path(__file__).resolve().parents[1] / "experiments"

# Canonical modeled workload — tier-independent so the gated modeled keys are
# bit-equal between the quick CI run and the committed baseline: a 256 MiB
# image, 1/3 zero pages, working set = half of the non-zero pages; restore
# pre-installs a 64 MiB hot chunk.
MODEL_N = 65536
MODEL_ZERO = MODEL_N // 3
MODEL_HOT = (MODEL_N - MODEL_ZERO) // 2
MODEL_COLD = MODEL_N - MODEL_ZERO - MODEL_HOT
MODEL_CHUNK = 16384


# -- modeled tier (gated) -----------------------------------------------------
def publish_traffic(n: int, n_hot: int, n_cold: int):
    """(read, written) HBM bytes per op for the piecemeal publish sequence
    that produces the fused op's full output contract (zero bitmap, guest-
    indexed checksum table, compacted hot/cold, dedup hashes), vs the fused
    single sweep.  int32 bitmap and u32 checksums are 4 B/page."""
    p, nz = PAGE_SIZE, n_hot + n_cold
    piecemeal = {
        "zero_detect": (n * p, 4 * n),
        "page_checksum": (n * p, 4 * n),
        "gather_hot": (n_hot * p, n_hot * p),
        "gather_cold": (n_cold * p, n_cold * p),
        "dedup_hash": (nz * p, 4 * nz),
    }
    fused = (n * p, nz * p + 8 * n)
    return piecemeal, fused


def restore_traffic(m: int):
    """Piecemeal pre-install (gather → checksum → scatter) vs the fused
    gather→verify→scatter kernel, per chunk of ``m`` pages."""
    p = PAGE_SIZE
    piecemeal = {
        "page_gather": (m * p, m * p),
        "page_checksum": (m * p, 4 * m),
        "page_scatter": (m * p, m * p),
    }
    fused = (m * p, m * p + 4 * m)
    return piecemeal, fused


def _modeled_pair(piecemeal: dict, fused_rw) -> dict:
    ops = [movement_roofline(k, r, w) for k, (r, w) in piecemeal.items()]
    fused = movement_roofline("fused", *fused_rw)
    piece_s = sum(o["bound_s"] for o in ops)
    speedup = piece_s / fused["bound_s"]
    return {
        "piecemeal_s": piece_s,
        "fused_s": fused["bound_s"],
        "speedup": speedup,
        "speedup_ge_2": bool(speedup >= 2.0),
        "piecemeal_ops": ops,
        "fused": fused,
    }


def modeled_section() -> dict:
    pub = _modeled_pair(*publish_traffic(MODEL_N, MODEL_HOT, MODEL_COLD))
    res = _modeled_pair(*restore_traffic(MODEL_CHUNK))
    return {
        "workload": {"n_pages": MODEL_N, "n_zero": MODEL_ZERO,
                     "n_hot": MODEL_HOT, "n_cold": MODEL_COLD,
                     "chunk_pages": MODEL_CHUNK, "hbm_bw_Bps": HBM_BW},
        "publish": pub,
        "restore": res,
    }


def calibration_section(modeled: dict) -> dict:
    """Per-page constants for serve/strategies.py, derived from the fused
    sweeps' actual traffic at the platform HBM roof (deterministic)."""
    csum = movement_roofline("page_checksum", PAGE_SIZE, 4)
    return {
        "written_by": "benchmarks/kernel_bench.py --write-calibration",
        "note": "per-page data-plane costs at the v5e HBM roofline; "
                "serve/strategies.py reads `constants` at import "
                "(DESIGN.md §13)",
        "platform": {"hbm_bw_Bps": HBM_BW},
        "per_page": {
            "checksum_bytes": PAGE_SIZE + 4,
            "publish_sweep_bytes":
                modeled["publish"]["fused"]["bytes_total"] / MODEL_N,
            "preinstall_bytes":
                modeled["restore"]["fused"]["bytes_total"] / MODEL_CHUNK,
        },
        "constants": {
            "checksum_bw_Bps": PAGE_SIZE / csum["bound_s"],
            "publish_sweep_page_s": modeled["publish"]["fused_s"] / MODEL_N,
            "preinstall_page_s": modeled["restore"]["fused_s"] / MODEL_CHUNK,
        },
    }


def calibration_in_sync(cal: dict) -> bool:
    """Do the constants strategies.py loaded (from the *committed* artifact)
    match what this bench derives now?  Flips the gated boolean if someone
    changes kernel traffic without recommitting the artifact."""
    from repro.serve import strategies

    loaded = {
        "checksum_bw_Bps": strategies.CHECKSUM_BW,
        "publish_sweep_page_s": strategies.PUBLISH_SWEEP_PAGE_S,
        "preinstall_page_s": strategies.PREINSTALL_PAGE_S,
    }
    want = cal["constants"]
    return all(abs(loaded[k] - want[k]) <= 1e-9 * abs(want[k]) for k in want)


# -- measured tier (informational) --------------------------------------------
def _time(fn, reps: int):
    """(first_call_s, steady_s): first call includes trace+compile; steady
    is the mean of ``reps`` warm calls, each blocked to completion."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    jax.block_until_ready(fn())  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return first, (time.perf_counter() - t0) / reps


def _mk_workload(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    pages = rng.integers(0, 256, size=(n, PAGE_SIZE), dtype=np.uint8)
    pages[::3] = 0  # every 3rd page zero
    ws = np.zeros(n, dtype=bool)
    ws[rng.choice(n, size=n // 2, replace=False)] = True
    u32 = pages.view(np.uint32).reshape(n, -1)
    return pages, u32, ws


def measured_section(tier: str) -> dict:
    """tier='interpret': the Pallas kernels at tiny shapes — compiled on a
    TPU, in the interpreter elsewhere.  tier='dispatch': the backend's data
    plane (compiled Pallas on TPU, jit'd oracle elsewhere), larger shapes."""
    if tier == "interpret":
        n, m, reps = 64, 16, 2
        disp = {"use_pallas": True, "interpret": not on_tpu()}
        blk = {"block_pages": 8}
    else:
        n, m, reps = 8192, 2048, 5
        disp = {"use_pallas": on_tpu()}
        blk = {}
    pages, u32, ws = _mk_workload(n)
    rows = []

    def bench(name, fn, nbytes):
        first, steady = _time(fn, reps)
        rows.append({
            "kernel": name, "tier": tier, "bytes": nbytes,
            "first_call_s": first, "steady_s": steady,
            "steady_GBps": nbytes / steady / 1e9,
            "modeled_tpu_s": nbytes / HBM_BW,
        })
        return steady

    # per-kernel rows (satellite: compile/steady split + GB/s)
    bench("zero_detect", lambda: zero_detect(u32, **disp, **blk), u32.nbytes)
    bench("page_checksum", lambda: page_checksum(pages, **disp, **blk),
          pages.nbytes)
    zb = np.asarray(zero_detect(u32, **disp, **blk)) != 0
    hot_idx = np.flatnonzero(~zb & ws).astype(np.int32)
    cold_idx = np.flatnonzero(~zb & ~ws).astype(np.int32)
    bench("page_gather", lambda: page_gather(u32, hot_idx, **disp),
          2 * hot_idx.size * PAGE_SIZE)
    chunk = np.asarray(page_gather(u32, hot_idx, **disp))
    dst = np.sort(hot_idx)
    src = np.arange(dst.size, dtype=np.int32)
    dest0 = np.zeros_like(u32)
    bench("page_scatter", lambda: page_scatter(dest0, chunk, dst, **disp),
          2 * dst.size * PAGE_SIZE)

    # fused vs piecemeal: publish
    def piecemeal_publish():
        zb_ = np.asarray(zero_detect(u32, **disp, **blk)) != 0
        csum = np.asarray(page_checksum(pages, **disp, **blk))
        hi = np.flatnonzero(~zb_ & ws).astype(np.int32)
        ci = np.flatnonzero(~zb_ & ~ws).astype(np.int32)
        hot = np.asarray(page_gather(u32, hi, **disp))
        cold = np.asarray(page_gather(u32, ci, **disp))
        hhash = np.asarray(page_checksum(hot, **disp, **blk))
        chash = np.asarray(page_checksum(cold, **disp, **blk))
        return zb_, csum, hot, cold, hhash, chash

    def do_fused_publish():
        return fused_publish(pages, ws, **disp, **blk)

    nz_bytes = (hot_idx.size + cold_idx.size) * PAGE_SIZE
    pm_bytes = 2 * n * PAGE_SIZE + 2 * nz_bytes + nz_bytes
    fu_bytes = n * PAGE_SIZE + nz_bytes
    pm_pub = bench("publish_piecemeal", piecemeal_publish, pm_bytes)
    fu_pub = bench("publish_fused", do_fused_publish, fu_bytes)

    # fused vs piecemeal: restore pre-install
    m = min(m, dst.size)
    chunk_m, src_m, dst_m = chunk[:m], src[:m], dst[:m]
    chunk_b = np.ascontiguousarray(chunk_m).view(np.uint8)
    dest_b = np.zeros(n * PAGE_SIZE, np.uint8).reshape(n, PAGE_SIZE)

    def piecemeal_restore():
        g = np.asarray(page_gather(chunk_m, src_m, **disp))
        cs = np.asarray(page_checksum(g, **disp, **blk))
        out = page_scatter(dest0, g, dst_m, **disp)
        return cs, out

    def do_fused_restore():
        return fused_restore(dest_b, chunk_b, dst_m, src_indices=src_m, **disp)

    pm_res = bench("restore_piecemeal", piecemeal_restore, 5 * m * PAGE_SIZE)
    fu_res = bench("restore_fused", do_fused_restore, 2 * m * PAGE_SIZE)

    # bit-identity of the two paths on the timed shapes (untimed)
    zb_, csum, hot, cold, hhash, chash = piecemeal_publish()
    fp = do_fused_publish()
    f_out, f_csums = do_fused_restore()
    f_out_u32 = np.asarray(f_out).view(np.uint32).reshape(n, -1)
    p_csums, p_out = piecemeal_restore()
    identical = bool(
        np.array_equal(fp.zero_bitmap, zb_)
        and np.array_equal(fp.checksums, np.asarray(csum))
        and np.array_equal(fp.hot.view(np.uint32).reshape(hot.shape), hot)
        and np.array_equal(fp.cold.view(np.uint32).reshape(cold.shape), cold)
        and np.array_equal(fp.checksums[hot_idx], hhash)
        and np.array_equal(fp.checksums[cold_idx], chash)
        and np.array_equal(f_csums, p_csums)
        and np.array_equal(f_out_u32.reshape(n, -1), np.asarray(p_out))
    )

    # Python/dispatch overhead: steady time at a 1-page shape is ~pure
    # per-call overhead; its fraction of the full-shape steady time says how
    # far each path is from bandwidth-bound on this backend.
    p1, _, w1 = _mk_workload(3)
    _, pm1 = _time(lambda: fused_publish(p1, w1, use_pallas=False), reps)
    n_pm_ops = 6  # zero + csum + 2x gather + 2x hash dispatches
    overhead = {
        "per_dispatch_s": pm1,
        "publish_piecemeal_fraction": min(1.0, n_pm_ops * pm1 / pm_pub),
        "publish_fused_fraction": min(1.0, pm1 / fu_pub),
        "restore_piecemeal_fraction": min(1.0, 3 * pm1 / pm_res),
        "restore_fused_fraction": min(1.0, pm1 / fu_res),
    }
    return {
        "tier": tier, "backend": jax.default_backend(),
        "n_pages": n, "chunk_pages": int(m), "reps": reps,
        "per_kernel": rows,
        "publish": {"piecemeal_steady_s": pm_pub, "fused_steady_s": fu_pub,
                    "speedup": pm_pub / fu_pub},
        "restore": {"piecemeal_steady_s": pm_res, "fused_steady_s": fu_res,
                    "speedup": pm_res / fu_res},
        "python_overhead": overhead,
        "bit_identical": identical,
    }


# -- driver -------------------------------------------------------------------
def run(quick: bool = False, write_calibration: bool = False) -> dict:
    modeled = modeled_section()
    cal = calibration_section(modeled)
    measured = measured_section("interpret" if quick else "dispatch")
    out = {
        "config": {"tier": "quick" if quick else "full",
                   "backend": jax.default_backend()},
        "modeled": modeled,
        "measured": measured,
        "criteria": {
            "bit_identical": measured["bit_identical"],
            "calibration_in_sync": calibration_in_sync(cal),
            "publish_speedup_ge_2": modeled["publish"]["speedup_ge_2"],
            "restore_speedup_ge_2": modeled["restore"]["speedup_ge_2"],
        },
    }
    OUT.mkdir(exist_ok=True)
    (OUT / "kernel_bench.json").write_text(json.dumps(out, indent=2))
    if write_calibration:
        (OUT / "kernel_calibration.json").write_text(json.dumps(cal, indent=2))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="interpret-mode sweep at tiny shapes (fast CI tier)")
    ap.add_argument("--write-calibration", action="store_true",
                    help="write experiments/kernel_calibration.json")
    args = ap.parse_args(argv)
    out = run(quick=args.quick, write_calibration=args.write_calibration)

    mo, me = out["modeled"], out["measured"]
    print(f"tier={out['config']['tier']} backend={out['config']['backend']}")
    for r in me["per_kernel"]:
        print(f"  {r['kernel']:20s} first={r['first_call_s'] * 1e3:8.2f}ms  "
              f"steady={r['steady_s'] * 1e3:8.2f}ms  "
              f"{r['steady_GBps']:7.2f} GB/s")
    for op in ("publish", "restore"):
        print(f"{op}: modeled {mo[op]['speedup']:.2f}x "
              f"(piecemeal {mo[op]['piecemeal_s'] * 1e3:.3f}ms -> "
              f"fused {mo[op]['fused_s'] * 1e3:.3f}ms), "
              f"measured {me[op]['speedup']:.2f}x steady-state")
    print(f"criteria: {out['criteria']}")
    return 0 if all(out["criteria"].values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
