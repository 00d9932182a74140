"""Per-slot KV-cache management for continuous batching.

The engine keeps ONE slot-batched decode cache (leaves stacked
``(num_blocks, num_slots, ...)``; self-attention K/V as ``(num_blocks,
num_slots, max_len, n_kv·hd)``) alive for its whole life; admitting a
request prefills it alone (batch 1, exact prompt length — no padding, so
ragged prompts never leak pad keys into attention) and scatters the
prepared single-request cache into the free slot's row. Releasing a slot
needs no work: the next admission overwrites the row wholesale.

Cross-attention caches (encoder-decoder models) are the one ragged leaf:
their length is the encoder source length of *that* request, so they are
zero-padded up to the allocated buffer and the engine masks the padding
via ``cross_valid`` at decode time.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Model


class SlotKVCache:
    """Slot-batched decode cache with jitted single-slot insertion.

    Self-attention K/V leaves are ``(num_blocks, num_slots, max_len,
    n_kv·hd)``: the layout the engine's decode step carries through its
    layer scan and writes in place, one row per slot, so a step neither
    copies a layer's cache out nor the stack back (the step donates it).

    Tracks per-slot VALID lengths host-side (``lengths[slot]`` = number of
    cache rows holding real tokens). The ragged-decode path reads
    ``max_valid_len()`` to bound how far batched decode attention must
    scan — everything past the longest live slot is pad by construction.
    """

    def __init__(self, model: Model, num_slots: int, max_len: int):
        self.num_slots = num_slots
        self.max_len = max_len
        self.cache: Dict[str, Any] = model.init_cache(num_slots, max_len)
        self.lengths = np.zeros(num_slots, np.int32)
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))

    @staticmethod
    def _insert_impl(big, small, slot):
        def put(b, s):
            if s.ndim >= 3 and s.shape[2] != b.shape[2]:
                # ragged cross-attention K/V: zero-pad to the allocated
                # buffer; decode masks the padding via cross_valid.
                pad = [(0, 0)] * s.ndim
                pad[2] = (0, b.shape[2] - s.shape[2])
                s = jnp.pad(s, pad)
            return b.at[:, slot].set(s[:, 0])

        return jax.tree.map(put, big, small)

    def insert(self, prepared_cache: Dict[str, Any], slot: int,
               length: int = 0) -> None:
        """Scatter a prepared batch-1 decode cache into ``slot``'s row.

        ``length`` records how many of the row's cache positions hold
        real tokens (prompt + frontend) for ragged-decode bounding."""
        self.cache = self._insert(self.cache, prepared_cache,
                                  jnp.int32(slot))
        self.lengths[slot] = length

    def update(self, new_cache: Dict[str, Any]) -> None:
        """Adopt the cache returned by a batched decode step."""
        self.cache = new_cache

    def set_length(self, slot: int, length: int) -> None:
        self.lengths[slot] = length

    def release(self, slot: int) -> None:
        """Mark a slot's rows as dead (the next insert overwrites them)."""
        self.lengths[slot] = 0

    def max_valid_len(self) -> int:
        """Longest valid row across slots — the ragged-decode bound."""
        return int(self.lengths.max()) if self.num_slots else 0
