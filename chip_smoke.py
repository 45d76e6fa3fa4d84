#!/usr/bin/env python3
"""Publish → restore → serve on one TPU chip, at a model's published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # elastic restore over a 1x4 mesh only

One chip: phi4-mini-3.8b at its published widths (d_model 3072, 24 query and
8 KV heads of 128, d_ff 8192, vocab 200064) with depth cut to 8 of 32 layers
and bfloat16 weights from a seed.  The script publishes the server's state
(weights, with the rare-vocab embedding rows cold, and its zero KV arena)
through the compiled publish kernel and checks it against the numpy oracle,
restores it through ``Orchestrator`` and ``restore_server`` with the
checksum-bound restore kernel into HBM (hot chunks, cold-run RDMA batches,
zero ranges) and checks the bytes, then serves 4 requests of 16 tokens from
the restored weights and checks their prefill logits against the original
weights'.
``--chips 4`` restores the same snapshot, reshards it over a 1x4 mesh and
checks one sharded prefill against a single-device prefill.

Every phase that fails makes the script exit non-zero.  Without a TPU, or
without the repository's ``src/`` beside it, it exits non-zero and prints no
result.  Timings are single readings, not benchmark numbers.  The last line
of standard output is one JSON object naming the device.
"""
import argparse
import json
import resource
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "phi4-mini-3.8b"
LAYERS = 8            # of 32: the depth cut
DTYPE = "bfloat16"    # weights as a server holds them
REQUESTS = 4
PROMPT_LEN = 16
GEN_TOKENS = 16
MAX_LEN = 64
SLAB = 1 << 16        # pages per slab of the host-side oracle check
# sharded vs single-device prefill: bf16 partial sums are reduced in another
# order across 4 devices, so the logits agree to a fraction of their range.
# One four-chip run read max |diff| 0.0538 over max |logit| 4.63 (1.2%);
# the bound leaves about 4x that for other seeds and prompts.
SHARDED_RTOL = 5e-2


def host_rss_bytes() -> int:
    """The process's resident host memory now."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * resource.getpagesize()


class Phases:
    """Wall seconds and compile seconds per phase (single readings)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def run(self, name, fn, *args):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args)
        wall = time.perf_counter() - t0
        print(f"phase {name}: {wall:.3f} s wall, of which "
              f"{self.compile_s - c0:.3f} s compiling (single reading, not a "
              f"benchmark); host RSS after it {host_rss_bytes()} bytes",
              flush=True)
        return out


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the elastic-restore path over a 1x4 mesh")
    args = ap.parse_args(argv)
    try:
        import jax
        import numpy as np

        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repository's code: {e}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}; host RSS "
          f"{host_rss_bytes()} bytes", flush=True)

    from repro.core import Orchestrator
    from repro.kernels.snapshot_fuse import FusedScatter
    from repro.kernels.snapshot_fuse.ops import default_publish_fn
    from repro.serve.coldstart import SkeletonPool, restore_server
    from repro.serve.engine import new_instance

    cfg = serve.model_config(ARCH, published_widths=True, layers=LAYERS,
                             dtype=DTYPE)
    sp = SkeletonPool(cfg, batch=REQUESTS, max_len=MAX_LEN, target_size=1,
                      background=False)
    phases = Phases(jax)
    params = phases.run("init", serve.init_params, sp.model)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"config: {ARCH} d_model={cfg.d_model} heads={cfg.n_heads} "
          f"kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} (published widths); cuts: layers {cfg.n_layers} "
          f"of 32, weights {cfg.param_dtype}, random from seed 0; "
          f"{n_params} parameters, weights {serve.image_nbytes(params)} bytes "
          f"as pages", flush=True)
    prompts = serve.make_prompts(cfg, REQUESTS, PROMPT_LEN)

    # -- publish through the compiled publish kernel --------------------------
    captured = {}
    kernel_publish = default_publish_fn()
    check(kernel_publish is not None, "publish data plane is the compiled kernel")

    def publish_fn(pages, ws):
        res = kernel_publish(pages, ws)
        captured.update(zero=res.zero_bitmap, csum=res.checksums, ws=ws,
                        n_hot=len(res.hot), n_cold=len(res.cold))
        return res

    if args.chips == 1:
        ref_logits = phases.run("reference_prefill", lambda: np.asarray(
            new_instance(cfg, params, REQUESTS, MAX_LEN).prefill(prompts)))
    # the server's state: weights (the rare-vocab embedding rows cold) and
    # its KV arena (zero at snapshot time)
    master, image, pub = phases.run("publish", serve.publish, cfg, params,
                                    sp.claim().caches, publish_fn)
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            params)
    jax.tree.map(lambda x: x.delete(), params)   # HBM back for the restore
    del params
    print(f"published {pub['total_pages']} pages: hot={pub['hot']} "
          f"cold={pub['cold']} zero={pub['zero']}", flush=True)
    check(pub["hot"] > 0 and pub["cold"] > 0 and pub["zero"] > 0,
          "the image has hot, cold and zero pages")
    if args.chips == 1:
        phases.run("publish_oracle_check", _check_publish, np, image, captured, pub)

    # -- restore through Orchestrator into HBM --------------------------------
    orch = Orchestrator("chip-host", master.pool, master.catalog)
    sf = orch.scatter_fn
    check(isinstance(sf, FusedScatter) and sf.use_pallas and not sf.interpret,
          "restore data plane is the compiled fused restore kernel")
    out = phases.run("restore", restore_server, orch, cfg.name, sp.claim(),
                     template)
    orch.close()
    rst, server = out["stats"], out["instance"]
    inst = rst["instance"]
    check(rst["device_resident"], "restored instance lived in HBM")
    check(inst["uffd_copies"] == pub["hot"] + pub["cold"],
          f"pages installed {inst['uffd_copies']} == hot + cold "
          f"(hot chunks and cold-run RDMA batches)")
    check(inst["uffd_zeropages"] == pub["zero"],
          f"zero pages {inst['uffd_zeropages']} installed as ranges")
    check(sf.stats["pages_verified"] == inst["uffd_copies"],
          f"pages verified {sf.stats['pages_verified']} == pages installed")
    if args.chips == 1:
        same = phases.run("restore_byte_check", _same_bytes, np, jax,
                          server.params, image)
        check(same, "restored weights equal the published bytes")
    # serving reads only the restored params: free the pool's host tiers
    del orch, master, image

    if args.chips == 1:
        _serve(phases, np, sp, server, prompts, ref_logits, cfg)
    else:
        _elastic(phases, jax, np, cfg, server.params, prompts)
    sp.close()

    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"peak HBM in use: {peak} bytes (device 0, memory_stats); peak host "
          f"RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


def _same_bytes(np, jax, params, image) -> bool:
    """Every restored weight, downloaded one array at a time, equals its
    published bytes."""
    from repro.checkpoint.ckpt import leaf_names

    extents = image.manifest.by_name()
    for name, leaf in zip(leaf_names({"params": params}),
                          jax.tree.leaves(params)):
        e = extents[name]
        got = np.asarray(leaf).reshape(-1).view(np.uint8)
        if not np.array_equal(got, image.buf[e.byte_offset : e.byte_offset + e.nbytes]):
            return False
    return True


def _check_publish(np, image, captured, pub):
    """The compiled sweep's zero bitmap and checksums bit-equal the numpy
    oracle's, and its hot/cold counts match, slab by slab."""
    from repro.kernels.layout import page_words
    from repro.kernels.snapshot_fuse.ref import fused_publish_ref

    pages = image.pages_matrix()
    n_hot = n_cold = 0
    zero_ok = csum_ok = True
    for lo in range(0, pages.shape[0], SLAB):
        zero, csum, hot, cold = fused_publish_ref(
            page_words(pages[lo : lo + SLAB]), captured["ws"][lo : lo + SLAB])
        zero_ok &= bool(np.array_equal(zero, captured["zero"][lo : lo + SLAB]))
        csum_ok &= bool(np.array_equal(csum, captured["csum"][lo : lo + SLAB]))
        n_hot, n_cold = n_hot + len(hot), n_cold + len(cold)
    check(zero_ok, "publish zero bitmap == fused_publish_ref")
    check(csum_ok, "publish checksums == fused_publish_ref")
    check((captured["n_hot"], captured["n_cold"]) == (n_hot, n_cold)
          == (pub["hot"], pub["cold"]),
          f"publish hot/cold counts {n_hot}/{n_cold} == fused_publish_ref")


def _serve(phases, np, sp, server, prompts, ref_logits, cfg):
    from repro.serve.engine import ServerInstance

    toks = phases.run("generate", server.generate, prompts, GEN_TOKENS)
    check(toks.shape == (REQUESTS, GEN_TOKENS)
          and bool(((toks >= 0) & (toks < cfg.vocab)).all()),
          f"served {REQUESTS} requests x {GEN_TOKENS} tokens")
    for i in range(REQUESTS):
        print(f"  req{i}: {toks[i].tolist()}")
    fresh = ServerInstance(sp.model, server.params, sp.claim().caches, MAX_LEN)
    logits = phases.run("restored_prefill",
                        lambda: np.asarray(fresh.prefill(prompts)))
    check(logits.shape == (REQUESTS, cfg.padded_vocab)
          and bool(np.isfinite(logits[:, : cfg.vocab]).all()),
          f"prefill logits finite, shape {logits.shape}")
    check(bool(np.array_equal(logits, ref_logits)),
          "restored server's prefill logits == original weights' logits")


def _elastic(phases, jax, np, cfg, params, prompts):
    from repro.checkpoint.ckpt import reshard
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import new_instance
    from repro.sharding.partition import param_specs

    single = phases.run("single_device_prefill", lambda: np.asarray(
        new_instance(cfg, params, REQUESTS, MAX_LEN).prefill(prompts)))
    mesh = make_host_mesh(data=1, model=4)
    sharded = phases.run("reshard", lambda: jax.block_until_ready(
        reshard(params, mesh, param_specs(params))))
    sizes = {len(x.sharding.device_set) for x in jax.tree.leaves(sharded)}
    check(sizes == {4}, f"every weight spans 4 devices (device sets {sizes})")
    got = phases.run("sharded_prefill", lambda: np.asarray(
        new_instance(cfg, sharded, REQUESTS, MAX_LEN).prefill(prompts)))
    diff = float(np.abs(got - single).max())
    scale = float(np.abs(single).max())
    agree = int((got.argmax(-1) == single.argmax(-1)).sum())
    print(f"  sharded vs single-device prefill: max |diff| {diff} over "
          f"max |logit| {scale}; argmax agrees on {agree}/{REQUESTS}",
          flush=True)
    check(diff <= SHARDED_RTOL * scale,
          f"sharded prefill logits within {SHARDED_RTOL} x max |logit| "
          "of the single-device prefill")
    check(agree == REQUESTS, "sharded prefill's argmax equals the "
          "single-device prefill's on every request")


if __name__ == "__main__":
    sys.exit(main())
