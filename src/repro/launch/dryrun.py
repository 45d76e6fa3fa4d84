"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract memory/cost/roofline data.

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b --shape decode_32k --multipod

Outputs one JSON per cell under experiments/dryrun/.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks device count on first init.

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import all_arch_names, get_config
from ..configs.shapes import SHAPES, cell_supported
from ..models.model_zoo import build
from ..roofline.analysis import analyze, model_flops_for
from ..sharding.partition import (
    batch_specs,
    cache_specs,
    param_specs,
)
from ..train.trainstep import TrainState, make_train_step
from ..train.optimizer import AdamWState
from .compile_cache import enable_compile_cache
from .mesh import make_production_mesh

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _state_specs(params_sds):
    ps = param_specs(params_sds)
    opt = AdamWState(step=P(), m=ps, v=jax.tree.map(lambda x: x, ps))
    return TrainState(params=ps, opt=opt)


def lower_cell(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True,
               overrides: dict | None = None):
    import dataclasses as _dc

    cfg = get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    model = build(cfg)
    t0 = time.perf_counter()

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_specs(params_sds)
    in_specs = model.input_specs(shape)

    jax.sharding.set_mesh(mesh)  # populate the abstract mesh for constrain()
    with mesh:
        if shape.kind == "train":
            state_sds = TrainState(
                params=params_sds,
                opt=AdamWState(
                    step=jax.ShapeDtypeStruct((), jnp.int32),
                    m=jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), params_sds),
                    v=jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), params_sds),
                ),
            )
            sspecs = _state_specs(params_sds)
            bspecs = batch_specs(in_specs, mesh)
            step = make_train_step(model)
            jitted = jax.jit(
                step,
                in_shardings=(_named(mesh, sspecs), _named(mesh, bspecs)),
                out_shardings=(_named(mesh, sspecs), None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_sds, in_specs)
        elif shape.kind == "prefill":
            bspecs = batch_specs(in_specs, mesh)

            def prefill(params, batch):
                logits, aux = model.forward(params, batch)
                return logits

            jitted = jax.jit(
                prefill,
                in_shardings=(_named(mesh, pspecs), _named(mesh, bspecs)),
            )
            lowered = jitted.lower(params_sds, in_specs)
        else:  # decode
            caches_sds = jax.eval_shape(
                lambda: model.init_caches(None, shape.global_batch, shape.seq_len)
            )
            cspecs = cache_specs(caches_sds, cfg, mesh, shape.global_batch)
            bspecs = batch_specs(in_specs, mesh)

            def serve_step(params, batch, caches):
                return model.decode_step(params, batch, caches)

            jitted = jax.jit(
                serve_step,
                in_shardings=(
                    _named(mesh, pspecs), _named(mesh, bspecs), _named(mesh, cspecs),
                ),
                out_shardings=(None, _named(mesh, cspecs)),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(params_sds, in_specs, caches_sds)

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    hlo_text = compiled.as_text()
    terms = analyze(compiled, chips, model_flops_for(cfg, shape), hlo_text=hlo_text)

    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
                           + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "roofline": terms.to_dict(),
    }
    if verbose:
        print(f"[{arch} × {shape_name} × {'2x16x16' if multi_pod else '16x16'}] "
              f"compile={t_compile:.1f}s  "
              f"mem(arg={result['memory']['argument_bytes']}, "
              f"temp={result['memory']['temp_bytes']})  "
              f"terms: C={terms.compute_s:.4f}s M={terms.memory_s:.4f}s "
              f"X={terms.collective_s:.4f}s dom={terms.dominant}")
        print("  memory_analysis:", mem)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (perf knobs), e.g. "
                         "--set seq_parallel=true; result JSON gets an @opt tag")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = {"true": True, "false": False}.get(v.lower(), v)

    if args.list:
        for a in all_arch_names():
            for s in SHAPES:
                ok, reason = cell_supported(get_config(a), SHAPES[s])
                print(f"{a:24s} {s:12s} {'ok' if ok else reason}")
        return 0

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    cells = []
    archs = all_arch_names() if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multipod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    opt_tag = ("@" + ",".join(f"{k}={v}" for k, v in sorted(overrides.items()))
               if overrides else "")
    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'multipod' if mp else 'pod'}{opt_tag}"
        out_path = OUT_DIR / f"{tag}.json"
        try:
            result = lower_cell(arch, shape, mp, overrides=overrides)
        except Exception as e:  # noqa: BLE001
            failures += 1
            result = {"arch": arch, "shape": shape, "multi_pod": mp,
                      "status": "error", "error": f"{type(e).__name__}: {e}",
                      "traceback": traceback.format_exc()[-3000:]}
            print(f"[{tag}] FAILED: {e}")
        out_path.write_text(json.dumps(result, indent=2, default=str))
    print(f"done: {len(cells)} cells, {failures} failures -> {OUT_DIR}")
    return 1 if failures else 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
