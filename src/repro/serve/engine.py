"""Serving engine: prefill + decode with KV caches, greedy sampling.

`ServerInstance` is the MicroVM analogue: a model + caches + pre-compiled
step functions.  Prefill uses the full-sequence forward for logits; caches
are filled by a scanned decode pass (compact HLO, works for every family).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models.model_zoo import Model, build
from ..spans import SERVE_PREFILL, spanned


@dataclasses.dataclass
class ServerInstance:
    """A live serving instance: model, params, and decode caches."""

    model: Model
    params: Any
    caches: Any
    max_len: int
    pos: int = 0

    @spanned(SERVE_PREFILL)
    def prefill(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """Feed prompt tokens (B, S); returns last-position logits (B, V)."""
        logits, self.caches = _prefill_scan(
            self.model, self.params, tokens, self.caches, self.pos
        )
        self.pos += tokens.shape[1]
        return logits

    def decode(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """One step: tokens (B, 1) -> logits (B, V)."""
        logits, self.caches = _decode_jit(self.model)(
            self.params, tokens, self.caches, jnp.asarray(self.pos, jnp.int32)
        )
        self.pos += 1
        return logits[:, 0]

    def generate(self, prompt: jnp.ndarray, n_tokens: int) -> np.ndarray:
        logits = self.prefill(prompt)
        out = []
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        for _ in range(n_tokens):
            out.append(np.asarray(tok[:, 0]))
            logits = self.decode(tok)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return np.stack(out, axis=1)


_decode_cache: Dict[str, Any] = {}


def _decode_jit(model: Model):
    key = model.cfg.name
    if key not in _decode_cache:
        # named so that its program reads ``jit_decode_step`` in a trace
        def decode_step(params, tokens, caches, pos):
            return model.decode_step(params, {"tokens": tokens, "pos": pos}, caches)
        _decode_cache[key] = jax.jit(decode_step)
    return _decode_cache[key]


def _prefill_scan(model: Model, params, tokens, caches, start_pos: int):
    """Sequentially decode the prompt to fill caches; returns final logits."""
    step_fn = _decode_jit(model)
    b, s = tokens.shape
    logits = None
    for t in range(s):
        logits, caches = step_fn(params, tokens[:, t : t + 1], caches,
                                 jnp.asarray(start_pos + t, jnp.int32))
    return logits[:, 0], caches


def new_instance(cfg: ModelConfig, params, batch: int, max_len: int) -> ServerInstance:
    model = build(cfg)
    caches = model.init_caches(params, batch, max_len)
    return ServerInstance(model, params, caches, max_len)
