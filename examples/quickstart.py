"""Quickstart: train a tiny LM, publish its state to the hierarchical pool,
warm-restore it on another "host", and serve tokens from the restored
instance.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import HierarchicalPool, Orchestrator, PoolMaster
from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.models.model_zoo import build
from repro.serve.engine import ServerInstance
from repro.train.loop import LoopConfig, Trainer


def main():
    # 1) a tiny same-family config of an assigned arch (full configs are for
    #    the dry-run; --arch selects any of the ten)
    cfg = get_config("qwen2.5-14b").reduced(vocab=512)
    model = build(cfg)
    data = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8))

    # 2) shared pod infrastructure: two-tier pool + pool master
    pool = HierarchicalPool(cxl_capacity=512 << 20, rdma_capacity=1 << 30)
    master = PoolMaster(pool)

    # 3) train a few steps with periodic Aquifer checkpoints
    trainer = Trainer(model, data, master=master,
                      loop_cfg=LoopConfig(steps=30, ckpt_every=15, log_every=10))
    state = trainer.run()
    print("train metrics:", [(m.get("step"), round(m.get("loss", 0), 3))
                             for m in trainer.metrics_log if "loss" in m])
    print("checkpoint composition:", trainer.ckpt_stats[-1])

    # 4) warm restore on a different host (borrow → clflush → pre-install hot
    #    set → demand-page cold pages from the RDMA tier)
    orch = Orchestrator("other-host", pool, master.catalog)
    restored, stats = restore_checkpoint(
        orch, trainer.loop_cfg.ckpt_name,
        {"params": state.params, "opt": state.opt})
    print(f"restored step={stats['meta']['step']} "
          f"borrow+hot installs {stats['time_to_hot_s']*1e3:.1f}ms, "
          f"all installs {stats['time_to_full_s']*1e3:.1f}ms (host wall time)")
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("restored params are bit-identical ✓")

    # 5) serve from the restored weights
    inst = ServerInstance(model, restored["params"],
                          model.init_caches(None, 1, 64), 64)
    prompt = jnp.asarray([[5, 17, 42]], jnp.int32)
    tokens = inst.generate(prompt, 12)
    print("generated:", tokens[0].tolist())


if __name__ == "__main__":
    main()
