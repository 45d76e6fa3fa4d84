"""Fault tolerance: Aquifer checkpoint/restart, crash recovery, elastic
resharding, straggler-tolerant restore (hot-first)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import HierarchicalPool, Orchestrator, PoolMaster
from repro.checkpoint.ckpt import (
    default_train_hotness,
    flatten_state,
    restore_checkpoint,
    reshard,
    save_checkpoint,
    unflatten_state,
)
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.models.model_zoo import build
from repro.train.loop import LoopConfig, Trainer
from repro.train.trainstep import init_train_state, make_train_step

TINY = get_config("qwen2.5-14b").reduced(
    n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128, vocab=128, d_head=32)


def make_stack():
    pool = HierarchicalPool(512 << 20, 1 << 30)
    master = PoolMaster(pool)
    orch = Orchestrator("host0", pool, master.catalog)
    return pool, master, orch


class TestCheckpoint:
    def test_state_roundtrip_bit_identical(self):
        model = build(TINY)
        state = init_train_state(model, jax.random.PRNGKey(0))
        tree = {"params": state.params, "opt": state.opt}
        pool, master, orch = make_stack()
        save_checkpoint(master, "ck", tree, step=0)
        restored, stats = restore_checkpoint(orch, "ck", tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # hot tier restore completes before the full state (straggler tolerance)
        assert stats["time_to_hot_s"] <= stats["time_to_full_s"]

    def test_hotness_split_params_hot_moments_cold(self):
        model = build(TINY)
        state = init_train_state(model, jax.random.PRNGKey(0))
        tree = {"params": state.params, "opt": state.opt}
        from repro.core import StateImage
        img = StateImage.build(flatten_state(tree))
        ws = set(default_train_hotness(img.manifest).tolist())
        by = img.manifest.by_name()
        for e in img.manifest.extents:
            if "/m/" in f"/{e.name}" or "/v/" in f"/{e.name}":
                continue
        # params pages are hot
        some_param = next(e for e in img.manifest.extents if "params" in e.name)
        assert set(some_param.pages()) <= ws
        # Adam moment pages are cold
        some_m = next(e for e in img.manifest.extents if "/m/" in e.name or e.name.startswith("opt/m"))
        assert not (set(some_m.pages()) & ws)

    @pytest.mark.slow
    def test_crash_resume_reproduces_uninterrupted_run(self):
        """train 10 → [crash] → restore → train to 20 must equal a straight
        20-step run (deterministic data + exact state restore)."""
        model = build(TINY)
        data = SyntheticLMData(DataConfig(vocab=TINY.vocab, seq_len=32, global_batch=4))

        # uninterrupted reference
        step = jax.jit(make_train_step(model))
        ref = init_train_state(model, jax.random.PRNGKey(0))
        for i in range(20):
            ref, _ = step(ref, {k: jnp.asarray(v) for k, v in data.batch_at(i).items()})

        # crash/restart path
        pool, master, orch = make_stack()
        t1 = Trainer(model, data, master=master, orch=orch,
                     loop_cfg=LoopConfig(steps=10, ckpt_every=10, log_every=100,
                                         async_checkpoint=False))
        t1.run()
        t2 = Trainer(model, data, master=master, orch=orch,
                     loop_cfg=LoopConfig(steps=20, ckpt_every=0, log_every=100))
        final = t2.run(resume=True)

        for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(final.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)

    def test_elastic_reshard_roundtrip(self):
        """Snapshot pages are mesh-agnostic: restore onto a different mesh."""
        model = build(TINY)
        state = init_train_state(model, jax.random.PRNGKey(0))
        from repro.launch.mesh import make_host_mesh
        from repro.sharding.partition import param_specs
        mesh = make_host_mesh(1, 1)
        specs = param_specs(state.params)
        placed = reshard(state.params, mesh, specs)
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(placed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_restore_missing_snapshot_falls_back(self):
        pool, master, orch = make_stack()
        with pytest.raises(FileNotFoundError):
            restore_checkpoint(orch, "nope", {})
        assert orch.stats["cold_starts"] == 1


class TestServing:
    def test_skeleton_pool_claim(self):
        from repro.serve.coldstart import SkeletonPool
        sp = SkeletonPool(TINY, batch=1, max_len=32, target_size=1, background=False)
        sk = sp.claim()
        assert sk.cfg.name == TINY.name
        sk2 = sp.claim()           # pool empty → created on demand
        assert sp.stats["created_on_demand"] >= 1
        sp.close()

    def test_generate_from_restored_params(self):
        """End-to-end serverless path: publish params snapshot → warm restore
        → bind to skeleton → generate tokens; equals direct generation."""
        from repro.serve.coldstart import SkeletonPool, restore_server
        from repro.serve.engine import ServerInstance
        model = build(TINY)
        params = model.init(jax.random.PRNGKey(1))
        pool, master, orch = make_stack()
        save_checkpoint(master, "srv", {"params": params}, step=0)
        sp = SkeletonPool(TINY, batch=1, max_len=48, target_size=1, background=False)
        out = restore_server(orch, "srv", sp.claim(), {"params": params})
        inst = out["instance"]
        prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        got = inst.generate(prompt, 8)

        direct = ServerInstance(model, params, model.init_caches(params, 1, 48), 48)
        want = direct.generate(prompt, 8)
        np.testing.assert_array_equal(got, want)
        sp.close()
