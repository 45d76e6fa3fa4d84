#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload mistral-coldstart --seed 7 --seconds 30 --trace 0

From the root of a checkout.  The process loads and warms up (set-up),
measures for ``--seconds``, checks what the window served against the plain
reference, and prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window's first invocations), ``device``, ``breakdown`` (traced
runs) and ``checks``, each number compared beside its limit; the checks are
also the last lines of standard error.  Without a TPU, or with fewer chips
than the cell asks for, or without the program's ``src/`` beside it, it
exits 2 and prints no result.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def enable_compile_cache() -> str:
    """JAX's persistent cache at the fixed ``<checkout>/.jax_cache``, unless
    ``JAX_COMPILATION_CACHE_DIR`` names another; every program is cached,
    so that only a checkout's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program's src/repro is not beside {BENCH}")
    import harness

    try:
        cell = harness.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))
    import jax

    cache = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, JAX found {len(devices)}")
    try:
        peak = harness.peaks_for(devices[0].device_kind)
    except KeyError as e:
        return fail(str(e))
    harness.log_stderr(f"{args.workload} seed {args.seed}: {devices[0].device_kind} "
                       f"x{len(devices)}, jax {jax.__version__}, cache {cache}")
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T_START, peak, log=harness.log_stderr)
    for name, c in result["checks"].items():
        harness.log_stderr(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
