"""Elastic scaling: checkpoint under one mesh, restore re-sharded under
another, and keep training — the snapshot's offset-array indirection makes
pages location-independent, so the restore path is mesh-agnostic.

    PYTHONPATH=src python examples/elastic_restore.py [--quick]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import HierarchicalPool, Orchestrator, PoolMaster
from repro.checkpoint.ckpt import restore_checkpoint, reshard, save_checkpoint
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.mesh import make_host_mesh
from repro.models.model_zoo import build
from repro.sharding.partition import param_specs
from repro.train.trainstep import TrainState, init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller arch/batch, 2+2 steps (CI smoke)")
    args = ap.parse_args(argv)
    arch = "xlstm-125m" if args.quick else "qwen2.5-14b"
    n_steps = 2 if args.quick else 5

    cfg = get_config(arch).reduced(vocab=512)
    model = build(cfg)
    data = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=32 if args.quick else 64,
                                      global_batch=4 if args.quick else 8))
    step = jax.jit(make_train_step(model))

    # phase 1: "big mesh" run (this container has one device; the mesh
    # plumbing is identical — the dry-run proves the 256/512-chip variants)
    state = init_train_state(model, jax.random.PRNGKey(0))
    for i in range(n_steps):
        state, m = step(state, {k: jnp.asarray(v) for k, v in data.batch_at(i).items()})
    print(f"phase1 loss={float(m['loss']):.3f} — checkpointing")

    pool = HierarchicalPool(1 << 30, 2 << 30)
    master = PoolMaster(pool)
    save_checkpoint(master, "elastic", {"params": state.params, "opt": state.opt},
                    step=n_steps)

    # phase 2: restore on a DIFFERENT mesh ("scale-down" re-shard)
    orch = Orchestrator("new-fleet-host", pool, master.catalog)
    restored, stats = restore_checkpoint(
        orch, "elastic", {"params": state.params, "opt": state.opt})
    mesh = make_host_mesh(1, 1)
    placed = reshard(restored["params"], mesh, param_specs(restored["params"]))
    print(f"restored step={stats['meta']['step']} and re-sharded onto "
          f"mesh {dict(mesh.shape)} — borrow+hot installs "
          f"{stats['time_to_hot_s']*1e3:.1f}ms (host wall time)")

    state2 = TrainState(placed, restored["opt"])
    for i in range(n_steps, 2 * n_steps):
        state2, m = step(state2, {k: jnp.asarray(v) for k, v in data.batch_at(i).items()})
    print(f"phase2 (post-reshard) loss={float(m['loss']):.3f} — training continued ✓")


if __name__ == "__main__":
    main()
