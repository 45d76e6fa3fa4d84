"""DeepSeek-V3's mechanisms in the program, at a small size on the CPU:
noaux_tc routing against a brute-force NumPy search, YaRN frequencies and
the softmax scale against the published formulas, the held-share expert
layer (dropless, f32 combine, its counter) and decode through the latent
cache against the full forward."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import moe
from repro.models.attention import mla_softmax_scale
from repro.models.common import yarn_inv_freq
from repro.models.model_zoo import build

KEY = jax.random.PRNGKey(0)
F32 = dict(compute_dtype="float32", param_dtype="float32")


def _small(**over):
    """The registered model at toy widths, routing 32 experts in 8 groups
    (4 searched, top 4), 8 of them held from ``first_held_expert`` on."""
    cfg = get_config("deepseek-v3-671b").reduced(**F32)
    return dataclasses.replace(cfg, **dict(dict(n_experts=32, top_k=4, moe_groups=8,
                                                moe_groups_kept=4, held_experts=8), **over))


def _moe_params(cfg, seed=0):
    p = moe.init_moe(jax.random.PRNGKey(seed), cfg, jnp.float32)
    p["bias"] = 0.0625 * jax.random.normal(jax.random.PRNGKey(seed + 1), p["bias"].shape)
    return p


def test_the_registered_config_carries_the_published_values():
    c = get_config("deepseek-v3-671b")
    assert (c.norm_eps, c.moe_score, c.moe_groups, c.moe_groups_kept) == (1e-6, "sigmoid", 8, 4)
    assert (c.moe_scale, c.top_k, c.n_held) == (2.5, 8, 256)
    assert (c.yarn_factor, c.yarn_beta_fast, c.yarn_beta_slow) == (40.0, 32.0, 1.0)
    assert (c.yarn_original_len, c.yarn_mscale_all_dim) == (4096, 1.0)


def _brute_force_route(x, router, bias, cfg):
    """Per token, as the published description reads: sigmoid scores, the
    bias added for the choice, groups ranked by their two best biased
    scores, the top k among the kept groups' experts, weights from the
    unbiased scores over their sum, × the scale."""
    scores = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router.astype(np.float64))))
    e = scores.shape[1]
    size = e // cfg.moe_groups
    out_i, out_w = [], []
    for s in scores:
        biased = s + bias
        ranks = sorted(range(cfg.moe_groups), key=lambda g: -sum(
            sorted(biased[g * size:(g + 1) * size])[-2:]))
        kept = set(ranks[:cfg.moe_groups_kept])
        cands = [j for j in range(e) if j // size in kept]
        chosen = sorted(cands, key=lambda j: -biased[j])[:cfg.top_k]
        w = s[chosen] / s[chosen].sum() * cfg.moe_scale
        out_i.append(chosen)
        out_w.append(w)
    return np.array(out_i), np.array(out_w)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_routing_matches_a_brute_force_group_limited_search(seed):
    cfg = _small()
    p = _moe_params(cfg, seed)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(seed + 9), (64, cfg.d_model)))
    idx, w = moe.route(p, jnp.asarray(x), cfg)
    want_i, want_w = _brute_force_route(x, np.asarray(p["router"]),
                                        np.asarray(p["bias"])[:, 0], cfg)
    order = np.argsort(np.asarray(idx), axis=1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(idx), order, 1),
                                  np.sort(want_i, axis=1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, 1),
                               np.take_along_axis(want_w, np.argsort(want_i, 1), 1),
                               rtol=1e-5)
    assert w.dtype == jnp.float32


def test_yarn_frequencies_and_softmax_scale_follow_the_published_formulas():
    """DeepSeek-V3's rope dims (64, θ 1e4) under factor 40, β 32/1 at 4,096."""
    dim, base, factor = 64, 10000.0, 40.0

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    want = []
    for i in range(dim // 2):
        extra = 1.0 / base ** (2 * i / dim)
        mask = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * (1 - mask) + extra * mask)
    got = yarn_inv_freq(dim, base, factor, 32, 1, 4096)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(want[-1])
    assert got[-1] == pytest.approx(base ** (-(dim - 2) / dim) / factor, rel=1e-6)
    cfg = get_config("deepseek-v3-671b")
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert mla_softmax_scale(cfg) == pytest.approx(0.13524, abs=1e-5)


def _dense_held(p, x, idx, w, cfg):
    """Every held expert on every token, weighted by its choice (0 if not
    chosen): the plain form of the held share."""
    first, n = cfg.first_held_expert, cfg.n_held
    y = np.zeros(x.shape, np.float64)
    for e in range(n):
        wt = (np.asarray(w) * (np.asarray(idx) == first + e)).sum(-1)
        h = x @ np.asarray(p["wi"][e]), x @ np.asarray(p["wg"][e])
        y += wt[:, None] * ((h[1] / (1 + np.exp(-h[1])) * h[0]) @ np.asarray(p["wo"][e]))
    return y


@pytest.mark.parametrize("first,tokens", [(0, 48), (8, 48), (24, 48), (0, 2), (8, 1)])
def test_the_held_layer_computes_its_own_experts_and_drops_no_token(first, tokens):
    """Sorted by expert (48 tokens) and through every held expert (a decode
    step's 1 or 2 tokens) alike."""
    cfg = _small(first_held_expert=first)
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, cfg.d_model))
    idx, w = moe.route(p, x, cfg)
    y, n_held = moe.held_experts(p, x, idx, w, cfg)
    want = _dense_held(p, np.asarray(x, np.float64), idx, w, cfg)
    assert y.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    local = np.asarray(idx) - first
    assert int(n_held) == int(((local >= 0) & (local < 8)).any(-1).sum())


@pytest.mark.parametrize("tokens", [48, 2])
def test_combine_weights_stay_float32(tokens):
    """Rounding the routing weights to bf16 moves the held part by ~2^-9 of
    itself: the f32 layer does not."""
    cfg = _small()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (tokens, cfg.d_model))
    idx, w = moe.route(p, x, cfg)
    want = _dense_held(p, np.asarray(x, np.float64), idx, w, cfg)
    y, _ = moe.held_experts(p, x, idx, w, cfg)
    y16, _ = moe.held_experts(p, x, idx, w.astype(jnp.bfloat16).astype(jnp.float32), cfg)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(y) - want).max() / scale < 1e-5
    assert np.abs(np.asarray(y16) - want).max() / scale > 1e-4


@pytest.mark.parametrize("absorb", [True, False], ids=["absorbed", "expanded"])
def test_decode_through_the_latent_cache_matches_the_full_forward(absorb):
    cfg = _small(mla_absorb=absorb)
    m = build(cfg)
    params = m.init(KEY)
    params["layers"]["moe"]["bias"] = 0.0625 * jax.random.normal(
        KEY, params["layers"]["moe"]["bias"].shape)
    s = 24
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, s), 0, cfg.vocab)
    full, _ = m.forward(params, {"tokens": tokens})
    caches = m.init_caches(params, 2, 32)
    assert caches["scan"]["latent"].shape[-1] == cfg.kv_lora_rank + cfg.qk_rope_head_dim
    for t in range(s):
        lg, caches = m.decode_step(params, {"tokens": tokens[:, t:t + 1],
                                            "pos": jnp.asarray(t, jnp.int32)}, caches)
        rel = float(jnp.abs(full[:, t] - lg[:, 0]).max() / jnp.abs(full[:, t]).max())
        assert rel < 1e-4, (t, rel)
    assert 0 < int(caches["held_expert_tokens"]) <= 2 * s * (cfg.n_layers - cfg.n_dense_layers)


def test_the_served_instance_reports_its_held_expert_tokens():
    from repro.serve.engine import new_instance

    cfg = _small()
    m = build(cfg)
    params = m.init(KEY)
    inst = new_instance(cfg, params, 1, 32)
    assert inst.stats == {"held_expert_tokens": 0}
    inst.prefill(jax.random.randint(KEY, (1, 12), 0, cfg.vocab))
    n = inst.stats["held_expert_tokens"]
    assert 0 < n <= 12 * (cfg.n_layers - cfg.n_dense_layers)
    dense = new_instance(get_config("mistral-large-123b").reduced(), None, 1, 8)
    assert dense.stats == {}
