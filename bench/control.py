#!/usr/bin/env python3
"""The control of a cell's correctness check: the plain reference put in the
program's place with its weights in float8 (the precision below the
configuration's bfloat16), judged by ``harness.check`` as a run is.

    python bench/control.py --workload mistral-coldstart --seeds 11 12 13 --invocations 3

It needs no program state and runs no window: per seed it makes the weights,
serves the first ``--invocations`` invocations of a run of that seed (the
same prompts, at their own lengths) from the float8 reference's last-position
logits, and hands those answers to ``harness.check`` with the seed's
weights.  It prints one JSON line per seed: ``correct``, which has to be
false, and each number beside its limit.  Not part of a benchmark run.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def run(cell, seed: int, template, invocations: int) -> dict:
    import arrivals
    import harness
    import weights as wts

    mix, vocab = cell.mix, cell.cfg["vocab_size"]
    low = harness.reference(cell).quantize(wts.make(template, seed))
    outs = []
    for i in range(invocations):
        p = arrivals.prompts(mix, seed, i, arrivals.prompt_length(mix, i), vocab)
        logits = harness.reference_logits(cell, low, p)
        outs.append((p, logits.argmax(-1), logits))
    del low
    checks = harness.check(cell, wts.make(template, seed), None, [], outs)
    return {"seed": seed, "correct": harness.passes(checks), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--invocations", type=int, required=True)
    args = ap.parse_args(argv)
    import harness
    import run as bench_run

    cell = harness.load_cell(args.workload)
    bench_run.enable_compile_cache()
    prog = harness.Program(cell.cfg, cell.mix)
    template = prog.template
    prog.close()
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload,
                          **run(cell, seed, template, args.invocations)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
