#!/usr/bin/env python3
"""Where a cold start's host time goes: the program's ``aquifer.*`` spans
read from a profiler trace of one cold start of ``mistral-coldstart``.

    python bench/restore_spans.py --seed 7

From the root of a checkout, on a TPU.  Sets the cell up as ``run.py``
does (weights from the seed, publish, one warm-up cold start), then runs
an untraced cold start, a traced one and an untraced one, each checked
against the seed's weights.  Prints one JSON object as the last line of
standard output:

- ``restore_s`` / ``first_token_s``: the benchmark's spans of the three
  cold starts (untraced, traced, untraced);
- ``idle_by_span``: the device's idle seconds in the traced cold start,
  split at every span edge, each instant charged to the innermost
  ``bench.*`` or ``aquifer.*`` span open at it; ``idle_s``, ``window_s``;
- ``self_s``: each ``aquifer.*`` span name's summed self time, and
  ``count``, its number of spans;
- ``restore_phases_s``: the restore's host phases (borrow, read, stage,
  verify, extract), each the self time of its spans;
- ``leaf_cover``: the share of ``aquifer.restore`` that the self times of
  its phase spans cover; ``phase_idle_share``: the share of the idle time
  inside ``bench.restore`` charged to a phase of the restore (an
  ``aquifer.*`` span other than ``aquifer.restore``);
- ``span_cost_us``: one span's enter and exit, with the profiler off and on.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

PHASES = {
    "borrow": ["aquifer.restore.borrow"],
    "read": ["aquifer.restore.cxl_read", "aquifer.restore.rdma_read"],
    "stage": ["aquifer.scatter.stage"],
    "verify": ["aquifer.scatter.verify"],
    "extract": ["aquifer.restore.extract"],
}
# every span inside aquifer.restore whose self time is a phase of it
LEAVES = ["aquifer.restore.borrow", "aquifer.restore.cxl_read",
          "aquifer.restore.rdma_read", "aquifer.scatter.stage",
          "aquifer.scatter.launch", "aquifer.scatter.verify",
          "aquifer.restore.install", "aquifer.restore.zero",
          "aquifer.restore.extract"]
SPAN_LOOPS = 100_000
WORKLOAD = "mistral-coldstart"


def span_cost_us(n: int, tdir: Path) -> dict:
    """Mean microseconds of one enter and exit of a program span, with no
    profiler and inside a profiled block."""
    import harness
    from repro.spans import RESTORE_INSTALL, span

    def loop() -> float:
        t = time.perf_counter()
        for _ in range(n):
            with span(RESTORE_INSTALL):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = loop()
    with harness.tracing.record(tdir):
        on = loop()
    return {"off": off, "on": on, "n": n}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    import jax
    from jax.profiler import TraceAnnotation

    import arrivals
    import harness
    import run
    import spans
    import weights as wts

    tracing = harness.tracing
    cell = harness.load_cell(WORKLOAD)
    if jax.devices()[0].platform != "tpu":
        print("restore_spans: needs a TPU", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    log = harness.log_stderr
    prog = harness.Program(cell.cfg, cell.mix)
    vocab = cell.cfg["vocab_size"]
    params = wts.make(prog.template, args.seed)
    ref = wts.digest(params)
    prog.publish(params)
    jax.tree.map(lambda x: x.delete(), params)
    del params
    # set-up's invocation, as run.py makes it: the mix's shortest prompt
    warmup = arrivals.prompts(cell.mix, 0, 0, int(cell.mix["prompt_len"]["min"]), vocab)
    prog.release(prog.coldstart(warmup)[0])

    tdir = Path(tempfile.mkdtemp(prefix="restore-spans-"))
    out = {"workload": WORKLOAD, "seed": args.seed, "restore_s": [],
           "first_token_s": [], "params_differ": 0}
    try:
        for i, traced in enumerate((False, True, False)):
            p = arrivals.prompts(cell.mix, args.seed, i,
                                 arrivals.prompt_length(cell.mix, i), vocab)
            if traced:
                with tracing.record(tdir / "restore"):
                    with TraceAnnotation(harness.TRACED_SPAN):
                        server, _, _, restore_s, first_s = prog.coldstart(p)
            else:
                server, _, _, restore_s, first_s = prog.coldstart(p)
            out["params_differ"] += int((wts.digest(server.params) != ref).sum())
            prog.release(server)
            del server
            out["restore_s"].append(restore_s)
            out["first_token_s"].append(first_s)
            log(f"cold start {i} ({'traced' if traced else 'untraced'}): "
                f"restore {restore_s:.4f} s, first token {first_s:.4f} s")
        prog.close()

        ev = tracing.load(tdir / "restore")
        lines = spans.load(tdir / "restore")
        t0, t1 = tracing.window(ev, harness.TRACED_SPAN)
        red = tracing.reduce(ev, t0, t1)
        every = ev.spans + spans.flatten(lines)
        gaps = spans.idle_gaps(ev.ops[sorted(ev.ops)[0]], t0, t1)
        split = spans.idle_by_span(every, gaps)
        (ra, rb), = [(a, b) for n, a, b in ev.spans
                     if n == "bench.restore" and t0 <= a and b <= t1]
        in_restore = spans.idle_by_span(
            every, [(max(a, ra), min(b, rb)) for a, b in gaps if b > ra and a < rb])
        restore_idle = sum(in_restore.values())
        selfs = spans.self_times(lines, t0, t1)
        counts: dict = {}
        for n, _, _ in spans.flatten(lines):
            counts[n] = counts.get(n, 0) + 1
        restore_span = sum(b - a for n, a, b in spans.flatten(lines)
                           if n == "aquifer.restore") / 1e9
        out.update({
            "window_s": red.window_s, "busy_s": red.busy_s,
            "idle_s": red.window_s - red.busy_s,
            "idle_by_span": sorted(split.items(), key=lambda kv: -kv[1]),
            "self_s": sorted(selfs.items(), key=lambda kv: -kv[1]),
            "count": counts,
            "restore_span_s": restore_span,
            "restore_phases_s": {k: sum(selfs.get(n, 0.0) for n in names)
                                 for k, names in PHASES.items()},
            "leaf_cover": (sum(selfs.get(n, 0.0) for n in LEAVES) / restore_span
                           if restore_span else None),
            "phase_idle_share": (sum(s for lb, s in in_restore.items()
                                     if lb.startswith(spans.PREFIX)
                                     and lb != "aquifer.restore") / restore_idle
                                 if restore_idle else None),
            "modules": red.modules[:8],
            "span_cost_us": span_cost_us(SPAN_LOOPS, tdir / "cost"),
        })
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
