"""The serving launcher (``repro.launch.serve``) — the entry point
``chip_smoke.py`` drives — and the compile-cache rule of the entry points."""
import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.launch import compile_cache, serve


def test_main_serves_and_names_its_device(capsys):
    assert serve.main(["--arch", "phi4-mini-3.8b", "--requests", "2",
                       "--gen-tokens", "3", "--max-len", "16"]) == 0
    out = capsys.readouterr().out
    dev = jax.devices()[0]
    last = out.strip().splitlines()[-1]
    assert last.startswith("served 2 requests x 3 tokens")
    assert last.endswith(f"on {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    assert "published phi4-mini-3.8b" in out and "warm restore:" in out


@pytest.mark.parametrize("published_widths", [False, True])
def test_model_config_cuts(published_widths):
    full = get_config("phi4-mini-3.8b")
    cfg = serve.model_config("phi4-mini-3.8b", published_widths=published_widths,
                             layers=2, dtype="bfloat16")
    assert (cfg.n_layers, cfg.param_dtype) == (2, "bfloat16")
    widths = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab)
    full_widths = (full.d_model, full.n_heads, full.n_kv_heads, full.d_ff,
                   full.vocab)
    assert (widths == full_widths) == published_widths


def test_pool_is_sized_from_the_image():
    params = {"w": np.zeros((3, 5000), np.float32), "b": np.zeros(7, np.int8)}
    nbytes = serve.image_nbytes(params)
    assert nbytes == (15 + 1) * 4096        # each leaf page-aligned
    pool = serve.pool_for(nbytes)
    assert pool.cxl.capacity >= nbytes and pool.rdma.capacity >= nbytes
    big = serve.pool_for(4 << 30)
    assert big.cxl.capacity >= (4 << 30) + (4 << 30) // 512   # + offset array


def test_served_state_publishes_hot_cold_and_zero_and_restores():
    """The launcher's snapshot: weights hot but the rare-vocab embedding
    rows (cold), and the KV arena (zero); restore_server gives back the
    weights bit for bit."""
    from repro.core import Orchestrator
    from repro.serve.coldstart import SkeletonPool, restore_server

    cfg = serve.model_config("phi4-mini-3.8b", layers=1)
    sp = SkeletonPool(cfg, batch=2, max_len=16, target_size=1, background=False)
    params = serve.init_params(sp.model)
    master, image, stats = serve.publish(cfg, params, sp.claim().caches)
    table = image.manifest.by_name()[serve.EMBED_TABLE]
    rare_rows = table.shape[0] - int(table.shape[0] * serve.HOT_VOCAB_SHARE)
    row_bytes = table.nbytes // table.shape[0]
    assert stats["cold"] == -(-rare_rows * row_bytes // 4096)
    assert stats["zero"] == sum(e.page_count for e in image.manifest.extents
                                if e.name.startswith("caches/"))
    assert stats["hot"] + stats["cold"] + stats["zero"] == stats["total_pages"]
    orch = Orchestrator("t", master.pool, master.catalog)
    out = restore_server(orch, cfg.name, sp.claim(), params)
    orch.close()
    sp.close()
    inst = out["stats"]["instance"]
    assert inst["uffd_copies"] == stats["hot"] + stats["cold"]
    assert inst["uffd_zeropages"] == stats["zero"]
    for got, want in zip(jax.tree.leaves(out["instance"].params),
                         jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compile_cache_honours_env(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before   # left to JAX


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = compile_cache.Path(serve.__file__).resolve().parents[3]
    assert compile_cache.CACHE_DIR == repo / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)   # tests stay off
