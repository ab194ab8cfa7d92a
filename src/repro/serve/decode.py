"""Batched autoregressive decoding on top of the LM cache machinery.

``DecodeSession`` owns jitted prefill/step functions and per-batch cache
state — the serving inner loop the ``decode_*`` dry-run shapes lower.
Weight-stationary serve sharding (DESIGN.md SS6 / SSPerf hillclimb 2) is a
property of the shardings attached to ``params``, not of this code.

Serving checklist (applies equally to a search deployment — see
search/guards.py): before a process takes traffic, run the preflight
self-tests against the compiled paths it will serve from —
``build_index(..., preflight=True)`` for the single-device engine — and
admit inputs through the hygiene boundary (``sanitize=`` on
``build_index`` / ``nn_search``) rather than trusting upstream data.  The
runtime guards then stay default-on; a tripped guard degrades to the
reference path instead of serving a silently wrong answer.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.model import LM

Array = jax.Array


@dataclasses.dataclass
class DecodeSession:
    model: LM
    params: Any
    max_len: int

    def __post_init__(self):
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, max_len=self.max_len)
        )
        self._step = jax.jit(self.model.decode_step)
        self.caches = None
        self.index = None

    def prefill(self, batch: dict[str, Array]) -> Array:
        logits, self.caches, self.index = self._prefill(self.params, batch)
        return logits

    def step(self, tokens: Array) -> Array:
        """Feed (B, 1) tokens; returns (B, V) next-token logits."""
        logits, self.caches = self._step(
            self.params, self.caches, tokens, self.index
        )
        self.index = self.index + 1
        return logits


def greedy_decode(
    model: LM, params: Any, prompt: Array, n_steps: int
) -> Array:
    """Greedy continuation of ``prompt`` (B, S) for ``n_steps`` tokens."""
    sess = DecodeSession(model, params, max_len=prompt.shape[1] + n_steps)
    logits = sess.prefill({"tokens": prompt})
    toks = [jnp.argmax(logits, -1)[:, None].astype(jnp.int32)]
    for _ in range(n_steps - 1):
        logits = sess.step(toks[-1])
        toks.append(jnp.argmax(logits, -1)[:, None].astype(jnp.int32))
    return jnp.concatenate(toks, axis=1)
