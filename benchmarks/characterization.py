"""Fig. 3 analogue: snapshot image composition across the 9 workloads.

Classes are measured with the real zero-detector + profiler over the built
instance images.  Also cross-checks the Pallas zero_detect kernel against
the numpy bitmap on a sample of each image.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from repro.core.snapshot import classify_pages, _compress_cold
from .workloads import all_workloads, get_workload

OUT = Path(__file__).resolve().parents[1] / "experiments"


def run(verify_kernel: bool = True) -> dict:
    rows = []
    for name in all_workloads():
        bw = get_workload(name)
        classes = classify_pages(bw.image, bw.profile.working_set)
        s = classes.summary()
        total = s["total"]
        row = {
            "workload": name,
            "arch": bw.wdef.arch,
            "total_pages": total,
            "zero_frac": s["zero"] / total,
            "hot_frac": s["hot"] / total,
            "cold_frac": s["cold"] / total,
            "cold_frac_of_nonzero": s["cold"] / max(1, s["cold"] + s["hot"]),
            "image_mb": bw.image.buf.nbytes / (1 << 20),
        }
        if verify_kernel:
            from repro.kernels import zero_detect
            from repro.kernels.backend import on_tpu
            mat = bw.image.pages_matrix()[: 4096].view(np.float32)
            kb = np.asarray(zero_detect(mat, use_pallas=True,
                                        interpret=not on_tpu())).astype(bool)
            nb = ~bw.image.pages_matrix()[: 4096].any(axis=1)
            row["kernel_bitmap_match"] = bool(np.array_equal(kb, nb))
        # beyond-paper: zstd cold-tier ratio (even sample of 2k cold pages)
        step = max(1, classes.cold_pages.size // 2048)
        cold = classes.cold_pages[::step][:2048]
        if cold.size:
            blob, _ = _compress_cold(bw.image.pages_matrix()[cold])
            row["cold_zstd_ratio"] = cold.size * 4096 / max(1, len(blob))
        rows.append(row)

    avg = {
        "zero_frac": float(np.mean([r["zero_frac"] for r in rows])),
        "hot_frac": float(np.mean([r["hot_frac"] for r in rows])),
        "cold_frac_of_nonzero": float(np.mean([r["cold_frac_of_nonzero"] for r in rows])),
        "cold_zstd_ratio": float(np.mean([r.get("cold_zstd_ratio", 1.0) for r in rows])),
    }
    out = {"rows": rows, "average": avg,
           "paper": {"zero_frac": 0.828, "hot_frac": 0.055,
                     "cold_frac_of_nonzero": 0.727,
                     "zero_range": [0.469, 0.907]}}
    OUT.mkdir(exist_ok=True)
    (OUT / "characterization.json").write_text(json.dumps(out, indent=2))
    return out


def main():
    out = run()
    print(f"{'workload':14s}{'total':>8s}{'zero':>8s}{'hot':>8s}{'cold':>8s}  kernel-ok")
    for r in out["rows"]:
        print(f"{r['workload']:14s}{r['total_pages']:8d}{r['zero_frac']:8.1%}"
              f"{r['hot_frac']:8.1%}{r['cold_frac']:8.1%}  {r.get('kernel_bitmap_match')}")
    a = out["average"]
    print(f"{'AVERAGE':14s}{'':8s}{a['zero_frac']:8.1%}{a['hot_frac']:8.1%}"
          f"   cold/nonzero={a['cold_frac_of_nonzero']:.1%}"
          f"   cold-zstd={a['cold_zstd_ratio']:.2f}x")
    p = out["paper"]
    print(f"{'PAPER':14s}{'':8s}{p['zero_frac']:8.1%}{p['hot_frac']:8.1%}"
          f"   cold/nonzero={p['cold_frac_of_nonzero']:.1%}")


if __name__ == "__main__":
    main()
