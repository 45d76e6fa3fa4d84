"""The DeepSeek-V3 cells' correctness check, driven end to end on the CPU at
a tiny size: the program against ``bench/references/deepseek.py``, the held
share against the uncut layer, a sound run ``correct``, and each fault the
new mechanisms can have failing the cells' limits.

The tiny configuration beside this file (``tiny-deepseek.json``) routes 32
experts in 8 groups (4 searched, top 4) and holds 8; it computes in float32,
because bf16 rounding over 128-wide rows, unlike the cells' 7,168, flips a
routing choice now and then and moves a logit row by up to ~0.26."""
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "bench"))

import control  # noqa: E402
import harness  # noqa: E402
import weights as wts  # noqa: E402
import work  # noqa: E402

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2**31 + 4343
SECONDS = 0.3
TINY = json.loads((HERE / "tiny-deepseek.json").read_text())
CELLS = ["deepseek-coldstart", "deepseek-warm"]


def _tiny(cell_name: str, **overrides) -> harness.Cell:
    cell = harness.load_cell(cell_name)
    cell.cfg = json.loads(json.dumps(TINY))
    cell.cfg["program"]["overrides"].update(overrides)
    cell.mix = dict(cell.mix, max_len=128,
                    prompt_len={"median": 24, "sigma": 0.6, "min": 4, "max": 64})
    return cell


def _run(cell):
    return harness.run_cell(cell, SEED, SECONDS, False, time.perf_counter(), PEAK,
                            log=lambda *_: None)


@pytest.fixture(autouse=True)
def fresh_decode_step(monkeypatch):
    """Each test traces its own decode step: the engine keeps one per model
    name, and a fault below changes what the step computes."""
    from repro.serve import engine
    monkeypatch.setattr(engine, "_decode_cache", {})


def _program(cfg: dict):
    from repro.launch import serve
    from repro.models.model_zoo import build

    p = cfg["program"]
    mc = serve.model_config(p["arch"], published_widths=p["published_widths"],
                            layers=p["layers"], dtype=p["dtype"])
    return build(dataclasses.replace(mc, **p["overrides"]))


def test_prefill_through_the_latent_cache_matches_the_reference():
    """Token by token through the program's decode step and its 576-wide
    (here 48-wide) latent cache, against the reference's full forward."""
    from repro.serve.engine import new_instance

    cell = _tiny("deepseek-warm")
    model = _program(cell.cfg)
    params = wts.make(jax.eval_shape(model.init, jax.random.PRNGKey(0)), SEED)
    tokens = np.random.default_rng(0).integers(0, TINY["vocab_size"], (2, 40)).astype(np.int32)
    inst = new_instance(model.cfg, params, 2, 64)
    got = np.asarray(inst.prefill(jnp.asarray(tokens)))[:, :TINY["vocab_size"]]
    want = harness.reference_logits(cell, params, tokens)
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4
    assert inst.stats["held_expert_tokens"] > 0


def test_the_held_shares_add_up_to_the_uncut_layer():
    """Every rank's share of the routed experts (32 shares of 2 experts of
    64), each computed by the program's held layer without the shared
    expert, summed, plus the shared expert once, is the reference's layer
    holding all 64."""
    from repro.models import moe

    ref = harness.reference(_tiny("deepseek-warm"))
    cfg = dict(TINY, first_held_expert=0, n_routed_experts=64, num_experts_per_tok=8)
    arch = ref.Arch.from_config(cfg)
    model = _program(dict(TINY, program=dict(TINY["program"], overrides=dict(
        TINY["program"]["overrides"], n_experts=64, top_k=8, held_experts=0))))
    mc = model.cfg
    whole = wts.make(jax.eval_shape(lambda: moe.init_moe(jax.random.PRNGKey(0), mc,
                                                         jnp.float32)), SEED)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, mc.d_model))
    want = np.asarray(ref._moe(arch, whole, x))
    total = np.asarray(moe.mlp(whole["shared"], x))
    for r in range(32):
        share = dataclasses.replace(mc, held_experts=2, first_held_expert=2 * r,
                                    n_shared_experts=0)
        p = {k: v for k, v in whole.items() if k != "shared"}
        p.update({k: whole[k][2 * r:2 * r + 2] for k in ("wi", "wg", "wo")})
        y, _ = moe.moe_held(p, x[None], share)
        total = total + np.asarray(y[0])
    assert np.abs(total - want).max() / np.abs(want).max() < 1e-5


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_sound_run_is_correct(cell_name):
    res = _run(_tiny(cell_name))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def _softmax_routing(mp):
    """The router's scores by softmax, as the capacity baseline routes."""
    from repro.models import moe
    mp.setattr(moe, "router_scores", lambda logits: jax.nn.softmax(logits, axis=-1))


def _no_latent_norms(mp):
    """The q and kv latents left unnormed."""
    from repro.models import attention
    mp.setattr(attention, "rmsnorm", lambda params, x, eps=1e-5: x)


def _dropped_tokens(mp):
    """Each token's last choice of a held expert dropped, as a held expert
    over its capacity drops a token (one choice in a decode step's batch of
    one is all there is to drop)."""
    from repro.models import moe
    orig = moe.route

    def drops(params, x, cfg):
        idx, w = orig(params, x, cfg)
        local = idx - cfg.first_held_expert
        held = (local >= 0) & (local < cfg.n_held)
        last = held & (jnp.cumsum(held[:, ::-1], axis=1)[:, ::-1] == 1)
        return idx, jnp.where(last, 0.0, w)
    mp.setattr(moe, "route", drops)


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("fault,overrides", [
    (_softmax_routing, {}),
    (None, {"yarn_factor": 1.0}),
    (_no_latent_norms, {}),
    (_dropped_tokens, {}),
], ids=["softmax_routing", "no_yarn", "no_latent_norms", "dropped_tokens"])
def test_a_fault_in_the_new_mechanisms_is_not_correct(cell_name, fault, overrides,
                                                      monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    res = _run(_tiny(cell_name, **overrides))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_control_is_not_correct(cell_name):
    """The reference with float8 weights in the program's place, judged by
    ``harness.check`` as a run is, comes out not correct on three seeds."""
    cell = _tiny(cell_name)
    template = jax.eval_shape(_program(cell.cfg).init, jax.random.PRNGKey(0))
    for seed in (SEED, SEED + 1, SEED + 2):
        r = control.run(cell, seed, template, invocations=3)
        assert not r["correct"], r["checks"]


def test_the_moe_readers_leave_the_held_experts_out():
    cell = harness.load_cell("deepseek-warm")
    c = cell.cfg
    experts = 4 * 8 * 3 * 7168 * 2048
    assert (c["num_hidden_layers"] - c["first_k_dense_replace"], c["n_routed_experts"]) == (4, 8)
    invs = [harness.Invocation(n, 0.01 * n, None, 0.01 * n, False) for n in (100, 300)]
    step = work.StepWork(2.0 * experts + 4e9, 2.0 * experts + 2e9)
    run = harness.Run(cell, PEAK, 30.0, 8.0, invs, {}, 1000, step, None)
    least = max(4e9 / PEAK["bf16_flops_per_s"], 2e9 / PEAK["hbm_bytes_per_s"])
    assert harness.reader("moe_warm_mfu")(run) == pytest.approx(100 * least / 0.01)
    assert harness.reader("moe_coldstart_mfu")(run) is None
    cold = harness.load_cell("deepseek-coldstart")
    invs = [harness.Invocation(n, 0.01 * n + 5.0, 4.0, 0.01 * n, False) for n in (100, 300)]
    run = harness.Run(cold, PEAK, 30.0, 8.0, invs, {}, 1000, step, None)
    want = 2 * 1000 * 4096 / 1e11 + 400 * least
    assert harness.reader("moe_coldstart_mfu")(run) == pytest.approx(100 * want / 14.0)
    assert harness.reader("moe_warm_mfu")(run) is None
