"""Serverless model serving with Aquifer cold-start mitigation.

Publishes a model snapshot to the two-tier pool, then compares the five
restore strategies (§5.1.3) on a real workload instance, and finally does an
actual warm restore into a pre-provisioned skeleton and serves tokens.

    PYTHONPATH=src:. python examples/serve_coldstart.py --workload chameleon
"""
import argparse

import jax
import jax.numpy as jnp

from benchmarks.workloads import all_workloads, get_workload
from repro.core import HierarchicalPool, Orchestrator, PoolMaster
from repro.checkpoint.ckpt import save_checkpoint
from repro.configs.base import get_config
from repro.models.model_zoo import build
from repro.serve.coldstart import SkeletonPool, restore_server
from repro.serve.strategies import STRATEGIES, run_strategy


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="chameleon", choices=all_workloads())
    ap.add_argument("--concurrency", type=int, default=32)
    ap.add_argument("--quick", action="store_true",
                    help="smallest workload, fewer tokens (CI smoke)")
    args = ap.parse_args(argv)
    if args.quick:
        args.workload = "pyaes"       # xlstm-125m — the smallest image
        args.concurrency = 8

    bw = get_workload(args.workload)
    spec = bw.spec()
    print(f"workload={args.workload} arch={bw.wdef.arch} "
          f"image={bw.image.buf.nbytes/(1<<20):.0f}MiB "
          f"(scaled to paper-size 1.5GiB instances, x{spec.scale:.1f})")
    print(f"\nrestore strategies @ concurrency={args.concurrency} (modeled):")
    print(f"{'strategy':12s}{'setup':>9s}{'prefetch':>9s}{'install':>9s}{'total':>9s}")
    rows = {}
    for s in STRATEGIES:
        r = run_strategy(s, spec, concurrency=args.concurrency)
        rows[s] = r
        b = r.breakdown()
        print(f"{s:12s}{b['setup']:9.4f}{b['prefetch']:9.4f}{b['exec_install']:9.4f}"
              f"{b['total']:9.4f}")
    print(f"\nAquifer speedup: {rows['firecracker'].total_s/rows['aquifer'].total_s:.2f}x "
          f"vs firecracker, {rows['faasnap'].total_s/rows['aquifer'].total_s:.2f}x vs faasnap")

    # real restore path: publish model params → skeleton → warm restore → serve
    cfg = get_config(bw.wdef.arch).reduced(vocab=512)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = HierarchicalPool(1 << 30, 2 << 30)
    master = PoolMaster(pool)
    save_checkpoint(master, "model", {"params": params}, step=0)
    orch = Orchestrator("serve-host", pool, master.catalog)
    sp = SkeletonPool(cfg, batch=1, max_len=64, target_size=1, background=False)
    out = restore_server(orch, "model", sp.claim(), params)
    st = out["stats"]
    # host wall times of the installs; the extracted arrays are not awaited
    print(f"\nwarm restore: borrow+hot installs {st['time_to_hot_s']*1e3:.1f}ms, "
          f"all installs {st['time_to_full_s']*1e3:.1f}ms "
          f"(pre-installed {st['instance']['pre_installed']} hot pages, "
          f"{st['instance']['uffd_copies'] - st['instance']['pre_installed']} "
          f"cold pages in RDMA batches)")
    toks = out["instance"].generate(jnp.asarray([[1, 2, 3]], jnp.int32),
                                    2 if args.quick else 8)
    print("served tokens:", toks[0].tolist())
    sp.close()


if __name__ == "__main__":
    main()
