"""Batched serving engine: LITS prefix-cache -> prefill -> decode loop."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import LMModel
from .prefix_cache import PrefixCache


@dataclasses.dataclass
class ServeStats:
    prefills: int = 0
    cached_prefills: int = 0
    decode_steps: int = 0
    wall_s: float = 0.0


class ServeEngine:
    """Greedy batched decoding with exact-prefix KV reuse via LITS."""

    def __init__(self, model: LMModel, params, cache_capacity: int = 1024,
                 index_config=None, max_len: int = 512,
                 index_service=None):
        self.model = model
        self.params = params
        # index_config: a repro.index.IndexConfig for the prompt cache
        # (unified policy, DESIGN.md §8).
        # index_service: a repro.serve.service.IndexService to share one
        # request plane across engines (DESIGN.md §9).
        self.prefix_cache = PrefixCache(capacity=cache_capacity,
                                        config=index_config,
                                        service=index_service)
        self.prefill_fn = jax.jit(model.prefill, static_argnames=("max_len",))
        self.decode_fn = jax.jit(model.decode_step)
        # max_len bounds prompt + generation + 1 (the KV allocation); it is
        # validated per request in generate() — never silently clamped
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.max_len = int(max_len)
        self.stats = ServeStats()

    @staticmethod
    def _prompt_key(tokens: np.ndarray, need: int) -> bytes:
        # tokenizer-independent exact key: 1-based bytes of the token ids.
        # ``need`` (the KV window the state was prefilled with) is part of
        # the identity: a cached state can only serve requests with the
        # same allocation — reusing a smaller-window state for a longer
        # generation would decode past its KV buffers, and mixing windows
        # in one all-hit batch would stack mismatched shapes.
        return b"p:%d:" % need + \
            tokens.astype(">u4").tobytes().replace(b"\x00", b"\x01")

    def generate(self, prompt_tokens: np.ndarray, n_steps: int) -> Dict[str, np.ndarray]:
        """prompt_tokens: (B, S) int32.  Returns generated ids (B, n_steps)."""
        t0 = time.time()
        B, S = prompt_tokens.shape
        need = S + n_steps + 1
        if need > self.max_len:
            raise ValueError(
                f"prompt ({S} tokens) + generation ({n_steps}) needs a KV "
                f"window of {need} > max_len={self.max_len}; raise max_len "
                f"on the engine or shorten the request")
        keys = [self._prompt_key(prompt_tokens[i], need) for i in range(B)]
        hit, slots = self.prefix_cache.lookup(keys)
        if hit.all():
            # whole batch served from the prefix cache (skip prefill entirely)
            states = [self.prefix_cache.get_state(s) for s in slots]
            cache = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=1), *[s["cache"] for s in states]
            )
            logits = jnp.stack([s["logits"] for s in states], axis=0)
            self.stats.cached_prefills += B
        else:
            cache, logits = self.prefill_fn(
                self.params, {"tokens": jnp.asarray(prompt_tokens)},
                max_len=need,
            )
            self.stats.prefills += B
            misses = [i for i in range(B) if not hit[i]]
            states = [
                {
                    "cache": jax.tree_util.tree_map(lambda x: x[:, i], cache),
                    "logits": logits[i],
                }
                for i in misses
            ]
            self.prefix_cache.admit([keys[i] for i in misses], states)
        out = np.zeros((B, n_steps), np.int32)
        tok = jnp.argmax(logits[:, : self.model.cfg.vocab], axis=-1).astype(jnp.int32)
        pos = jnp.int32(S)
        for t in range(n_steps):
            out[:, t] = np.asarray(tok)
            cache, logits = self.decode_fn(self.params, cache, tok, pos + t)
            tok = jnp.argmax(logits[:, : self.model.cfg.vocab], axis=-1).astype(jnp.int32)
            self.stats.decode_steps += 1
        self.stats.wall_s += time.time() - t0
        return {"generated": out}
