"""Mixture-of-Experts FFNs.

``moe_ffn``: shared experts + softmax top-k with capacity-factor
dispatch/combine einsums (GShard-style "dropping" baseline); the §Perf
hillclimb swaps the (tokens, experts, capacity) dispatch for a sort-based
formulation (``moe_ffn_sorted``).  Router softmax runs in f32; an auxiliary
load-balancing loss (Switch-style) is returned for the train step.

``moe_held``: DeepSeek-V3's layer as one rank of expert parallelism runs it.
The router scores all ``n_experts`` (sigmoid, f32, group-limited, a bias for
the choice only); the layer holds ``cfg.n_held`` of them and computes, with
no capacity and no drop, each token's part from the held experts it chose,
plus the shared expert.  What experts held elsewhere add is left out: on one
chip there is no exchange to stand in for.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.partition import constrain
from ..spans import MOE_EXPERTS, MOE_ROUTE, MOE_SHARED
from .common import dense_init, init_mlp, mlp


def init_moe(key, cfg: ModelConfig, dtype) -> Dict:
    """The router over all ``n_experts`` (f32) and the ``n_held`` experts
    this layer holds.  DeepSeek's bias is an ``(n_experts, 1)`` leaf."""
    d, fe, e, n = cfg.d_model, cfg.d_ff_expert, cfg.n_experts, cfg.n_held
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "wi": dense_init(ks[1], (n, d, fe), dtype),
        "wg": dense_init(ks[2], (n, d, fe), dtype),
        "wo": dense_init(ks[3], (n, fe, d), dtype, fan_in=fe),
    }
    if cfg.moe_score == "sigmoid":
        p["bias"] = jnp.zeros((e, 1), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, fe * cfg.n_shared_experts, dtype)
    return p


def router_scores(logits: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.sigmoid(logits)


def route(params: Dict, x: jnp.ndarray, cfg: ModelConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """noaux_tc routing of tokens x (t, D) in f32: the experts each token
    chooses (t, top_k) and their weights (t, top_k).  The bias moves the
    choice only; a group's score is the sum of its two best biased scores,
    and the top experts are searched in the ``moe_groups_kept`` best
    groups.  Weights are the chosen unbiased scores, divided by their sum,
    × ``moe_scale``."""
    scores = router_scores(jnp.einsum("td,de->te", x.astype(jnp.float32),
                                      params["router"].astype(jnp.float32)))
    choice = scores + params["bias"][:, 0]
    t, e = scores.shape
    g = cfg.moe_groups
    if g > 1:
        best2 = jax.lax.top_k(choice.reshape(t, g, e // g), 2)[0].sum(-1)   # (t, g)
        _, kept = jax.lax.top_k(best2, cfg.moe_groups_kept)
        searched = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(searched, e // g, axis=1), choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    return idx, w / w.sum(-1, keepdims=True) * cfg.moe_scale


def held_experts(params: Dict, x: jnp.ndarray, idx: jnp.ndarray, w: jnp.ndarray,
                 cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the result, f32 (t, D), and the number of
    tokens routed to at least one of them.  No token is dropped.

    A decode step's few tokens (no more choices than held experts) go
    through every held expert, weighted by zero where not chosen: the step
    reads each held expert's weights whole either way, and the products read
    them in place from the stacked layers.  Longer inputs run each (token,
    choice) routed to a held expert once, grouped by expert (``ragged_dot``);
    the rest sort last, outside every group, and add nothing."""
    t, k = idx.shape
    n = cfg.n_held
    local = idx - cfg.first_held_expert
    held = (local >= 0) & (local < n)
    n_held = held.any(-1).sum(dtype=jnp.int32)
    if t * k <= n:
        weight = (w[..., None] * (local[..., None] == jnp.arange(n))).sum(1)   # (t, n)
        h = jnp.einsum("td,edf->tef", x, params["wi"])
        gate = jnp.einsum("td,edf->tef", x, params["wg"])
        out = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * h, params["wo"],
                         preferred_element_type=jnp.float32)
        return jnp.einsum("te,ted->td", weight, out), n_held
    flat = jnp.where(held, local, n).reshape(t * k)
    order = jnp.argsort(flat)                                # stable
    sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
    xs = jnp.take(x, order // k, axis=0)
    h = jax.lax.ragged_dot(xs, params["wi"], sizes)
    gate = jax.lax.ragged_dot(xs, params["wg"], sizes)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * h, params["wo"], sizes,
                             preferred_element_type=jnp.float32)
    routed = jnp.arange(t * k) < sizes.sum()
    part = jnp.where(routed[:, None], out * w.reshape(t * k)[order][:, None], 0.0)
    return jnp.zeros((t, x.shape[-1]), jnp.float32).at[order // k].add(part), n_held


def moe_held(params: Dict, x: jnp.ndarray, cfg: ModelConfig
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, tokens routed to a held expert).  Routing weights
    stay f32 through the combine; y is cast to x's dtype at the end."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope(MOE_ROUTE):
        idx, w = route(params, xt, cfg)
    with jax.named_scope(MOE_EXPERTS):
        y, n_held = held_experts(params, xt, idx, w, cfg)
    if cfg.n_shared_experts:
        with jax.named_scope(MOE_SHARED):
            y = y + mlp(params["shared"], xt).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, s, d), n_held


def moe_ffn_sorted(params: Dict, x: jnp.ndarray, cfg: ModelConfig
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """§Perf alternative: sort-based dropless dispatch (MegaBlocks-style).

    Tokens are argsorted by expert id and run through `jax.lax.ragged_dot`
    grouped GEMMs — no (tokens, experts, capacity) one-hot tensors, no
    drops.  Working set is tokens x top_k x d instead of tokens x 10 x d
    (~e*c/(k) smaller dispatch state at DeepSeek shapes)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                     # (t, k)
    topw = topw / (topw.sum(-1, keepdims=True) + 1e-9)

    flat_e = topi.reshape(t * k)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = topw.reshape(t * k)
    order = jnp.argsort(flat_e)                              # stable
    tok_sorted = flat_tok[order]
    w_sorted = flat_w[order]
    xs = jnp.take(xt, tok_sorted, axis=0)                    # (t*k, d)
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)

    h = jax.lax.ragged_dot(xs, params["wi"], group_sizes)
    g = jax.lax.ragged_dot(xs, params["wg"], group_sizes)
    act = jax.nn.silu(g) * h
    out = jax.lax.ragged_dot(act, params["wo"], group_sizes)  # (t*k, d)

    y = jnp.zeros((t, d), x.dtype).at[tok_sorted].add(
        out * w_sorted[:, None].astype(out.dtype))

    top1 = jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32)
    aux = (top1.mean(axis=0) * probs.mean(axis=0)).sum() * e

    y = y.reshape(b, s, d)
    if cfg.n_shared_experts:
        y = y + mlp(params["shared"], x)
    return y, aux.astype(jnp.float32)


def moe_ffn(params: Dict, x: jnp.ndarray, cfg: ModelConfig,
            group_size: int = 2048) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss).

    Tokens are processed in groups with per-group expert capacity
    C = group_size * top_k / E * capacity_factor (overflow tokens drop to the
    residual path, standard for dropping MoE).  cfg.moe_impl="sorted" routes
    to the dropless sort-based formulation instead.
    """
    if getattr(cfg, "moe_impl", "dispatch") == "sorted":
        return moe_ffn_sorted(params, x, cfg)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    g = max(1, t // group_size)
    gs = t // g
    xt = x.reshape(g, gs, d)

    logits = jnp.einsum("gsd,de->gse", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (g, s, e)
    topw, topi = jax.lax.top_k(probs, k)                         # (g, s, k)
    topw = topw / (topw.sum(-1, keepdims=True) + 1e-9)

    cap = max(1, int(gs * k / e * cfg.capacity_factor))
    # position of each (token, choice) in its expert's queue
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)          # (g, s, k, e)
    pos_in_e = (jnp.cumsum(onehot.sum(2), axis=1) - onehot.sum(2))  # (g, s, e)
    # per-choice slot: recover via gather of pos + intra-token offset
    prior_within = jnp.cumsum(onehot, axis=2) - onehot            # (g, s, k, e)
    slot = jnp.einsum("gske,gse->gsk", onehot, pos_in_e) + jnp.einsum(
        "gske,gske->gsk", onehot, prior_within
    )
    keep = slot < cap
    w = topw * keep

    # dispatch/combine tensors — bf16: they are 0/1 masks (disp) and softmax
    # weights (comb); the (g,s,e,c) materialization is the structural cost of
    # dropping-MoE and dominates MoE-train memory, so halving its bytes
    # matters (§Perf)
    slot_oh = jax.nn.one_hot(slot.astype(jnp.int32), cap, dtype=jnp.float32) * keep[..., None]
    disp = jnp.einsum("gske,gskc->gsec", onehot, slot_oh).astype(x.dtype)
    comb = jnp.einsum("gsk,gske,gskc->gsec", w, onehot, slot_oh).astype(x.dtype)

    xin = jnp.einsum("gsec,gsd->gecd", disp.astype(x.dtype), xt)  # (g, e, c, d)
    # expert-parallel layout: dispatched tokens live on the expert's shard
    # (all-to-all at this boundary), groups ride the DP axes
    xin = constrain(xin, ("pod", "data"), "model", None, None)
    h = jnp.einsum("gecd,edf->gecf", xin, params["wi"])
    gate = jnp.einsum("gecd,edf->gecf", xin, params["wg"])
    act = jax.nn.silu(gate) * h
    xout = jnp.einsum("gecf,efd->gecd", act, params["wo"])
    xout = constrain(xout, ("pod", "data"), "model", None, None)
    y = jnp.einsum("gsec,gecd->gsd", comb.astype(x.dtype), xout)

    # Switch aux loss: fraction of tokens per expert x mean router prob
    frac = onehot[:, :, 0, :].mean(axis=1)                       # top-1 assignment share
    mean_p = probs.mean(axis=1)
    aux = (frac * mean_p).sum(-1).mean() * e

    y = y.reshape(b, s, d)
    if cfg.n_shared_experts:
        y = y + mlp(params["shared"], x)
    return y, aux.astype(jnp.float32)
