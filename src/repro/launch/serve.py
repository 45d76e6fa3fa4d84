"""Serving launcher: publish an arch's weights to the two-tier pool,
warm-restore them into a server skeleton and serve batched greedy-decoding
requests.

    PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --requests 4
    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \\
        --published-widths --layers 8 --dtype bfloat16

By default the config is cut to toy widths (``ModelConfig.reduced``), which
is what the CPU tests serve.  ``--published-widths`` keeps the arch's own
widths and cuts only depth (``--layers``); ``--dtype`` sets the weight
dtype.  The pool is sized from the image.  On a TPU the publish and the
restore run the compiled page kernels and the restoring instance lives in
HBM (``kernels/snapshot_fuse``); elsewhere they run on the host.
"""
import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.ckpt import save_checkpoint
from ..configs.base import ModelConfig, all_arch_names, get_config
from ..core import HierarchicalPool, Manifest, Orchestrator, PoolMaster
from ..core.pagestore import PAGE_SIZE, num_pages
from ..core.profiler import AccessRecorder
from ..models.model_zoo import Model
from ..serve.coldstart import SkeletonPool, restore_server
from .compile_cache import enable_compile_cache

EMBED_TABLE = "params/embed/table"
# BPE ids grow with merge order, so the last quarter of the vocab is rare:
# its input-embedding rows stay cold on the RDMA tier
HOT_VOCAB_SHARE = 0.75


def model_config(arch: str, *, published_widths: bool = False,
                 layers: int = None, dtype: str = None) -> ModelConfig:
    """``arch`` at toy widths (default) or at its published widths, with
    an optional depth cut and weight dtype."""
    cfg = get_config(arch)
    if not published_widths:
        cfg = cfg.reduced(vocab=2048)
    over = {}
    if layers is not None:
        over["n_layers"] = layers
    if dtype is not None:
        over["param_dtype"] = dtype
    return dataclasses.replace(cfg, **over)


def init_params(model: Model, seed: int = 0):
    """Random weights from ``seed``, built on the default device."""
    return jax.jit(model.init)(jax.random.PRNGKey(seed))


def image_nbytes(tree) -> int:
    """Bytes of the paged image ``tree`` publishes as (page-aligned leaves)."""
    return sum(num_pages(leaf.nbytes) * PAGE_SIZE
               for leaf in jax.tree.leaves(tree))


def pool_for(nbytes: int) -> HierarchicalPool:
    """A pool whose tiers each hold an image of ``nbytes`` plus its
    machine state and offset array (8 B per page)."""
    cap = nbytes + nbytes // 256 + (16 << 20)
    return HierarchicalPool(cxl_capacity=cap, rdma_capacity=cap)


def serving_hotness(manifest: Manifest) -> np.ndarray:
    """A server's working set: every weight but the rare-vocab rows of the
    input embedding.  The KV arena is zero at snapshot time, so it is
    neither hot nor cold."""
    rec = AccessRecorder(manifest)
    for e in manifest.extents:
        if e.name == EMBED_TABLE:
            rec.touch_rows(e.name, np.arange(int(e.shape[0] * HOT_VOCAB_SHARE)))
        elif e.name.startswith("params/"):
            rec.touch_array(e.name)
    return rec.working_set()


def publish(cfg: ModelConfig, params, caches, publish_fn=None):
    """Publish a server's state — weights and its KV arena — as snapshot
    ``cfg.name`` to a pool sized from its image.  Returns ``(master, image,
    stats)``."""
    state = {"params": params, "caches": caches}
    master = PoolMaster(pool_for(image_nbytes(state)), publish_fn=publish_fn)
    image, stats = save_checkpoint(master, cfg.name, state, step=0,
                                   hotness=serving_hotness)
    return master, image, stats


def make_prompts(cfg: ModelConfig, requests: int, prompt_len: int,
                 seed: int = 0) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab, (requests, prompt_len)),
                       jnp.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b", choices=all_arch_names())
    ap.add_argument("--published-widths", action="store_true",
                    help="serve the arch's own widths, not the toy cut")
    ap.add_argument("--layers", type=int, default=None, help="depth cut")
    ap.add_argument("--dtype", default=None, help="weight dtype, e.g. bfloat16")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    args = ap.parse_args(argv)

    cfg = model_config(args.arch, published_widths=args.published_widths,
                       layers=args.layers, dtype=args.dtype)
    if cfg.is_encdec:
        print("enc-dec serving requires encoder features; see examples/")
        return 2
    sp = SkeletonPool(cfg, batch=args.requests, max_len=args.max_len,
                      target_size=1, background=False)
    params = init_params(sp.model)
    master, _, stats = publish(cfg, params, sp.claim().caches)
    print(f"published {cfg.name}: {stats['total_pages']} pages "
          f"(hot={stats['hot']} cold={stats['cold']} zero={stats['zero']})")
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    del params

    orch = Orchestrator("serve-host", master.pool, master.catalog)
    t0 = time.perf_counter()
    out = restore_server(orch, cfg.name, sp.claim(), template)
    st = out["stats"]
    # host wall times of the installs; the extracted arrays are not awaited
    print(f"warm restore: borrow+hot installs {st['time_to_hot_s']*1e3:.0f}ms, "
          f"all installs {st['time_to_full_s']*1e3:.0f}ms "
          f"(modeled pool time {sum(st['modeled'].values())*1e3:.2f}ms)")

    prompts = make_prompts(cfg, args.requests, args.prompt_len)
    toks = out["instance"].generate(prompts, args.gen_tokens)
    dt = time.perf_counter() - t0
    dev = jax.devices()[0]
    for i in range(args.requests):
        print(f"  req{i}: {toks[i].tolist()}")
    print(f"served {args.requests} requests x {args.gen_tokens} tokens "
          f"in {dt:.2f}s wall on {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}")
    orch.close()
    sp.close()
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
