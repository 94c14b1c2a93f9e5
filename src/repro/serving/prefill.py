"""Chunked prefill driver: long prompts stream into the serving cache in
fixed-size compiled chunks.

The engine's default prefill is teacher-forcing through the decode step —
one engine tick per prompt token, correct but O(prompt) ticks. This
driver instead feeds a slot's prompt through
`transformer.chunk_prefill_step` in ``chunk``-token slices: every slice
has the same traced shape (the final one is padded; pads neither write
KV nor produce used output), so ONE compiled chunk trace serves every
prompt length — never a per-length trace.

The driver prefills ``seed[:-1]`` only. The engine then teacher-forces
the final prompt token through the normal decode step, which both writes
that token's KV and emits the first generated token — exactly the state
the teacher-forced path reaches, so downstream decode is unchanged.

Mechanism only: page allocation for the chunks is the engine's job
(tables must cover ``ceil((len(seed)-1)/page_size)`` pages before `run`).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.models import transformer as tf


class ChunkedPrefill:
    """One jitted chunk step per engine (two traces with/without lora,
    mirroring the engine's decode closure). ``compile_count`` counts
    traces and must stay at the number of distinct signatures used (1
    in steady state — asserted by tests)."""

    def __init__(self, params, cfg, chunk: int):
        if chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        if not tf.supports_chunked_prefill(cfg):
            raise ValueError(
                f"chunked prefill unsupported for {cfg.name}: attention-only "
                f"decoders (recurrent/enc-dec archs use the engine's "
                f"teacher-forced prefill)")
        self.params = params
        self.cfg = cfg
        self.chunk = int(chunk)
        self.compile_count = 0

        def _step(p, c, toks, slot, start, limit):
            self.compile_count += 1
            return tf.chunk_prefill_step(p, cfg, toks, c, slot, start, limit)

        def _step_lora(p, c, toks, slot, start, limit, lo):
            self.compile_count += 1
            return tf.chunk_prefill_step(p, cfg, toks, c, slot, start, limit,
                                         lora=lo)

        self._step = jax.jit(_step)
        self._step_lora = jax.jit(_step_lora)

    def n_prefill_tokens(self, seed_len: int) -> int:
        """Tokens this driver would write for a seed (the rest is the
        engine's teacher-forced final token)."""
        return max(seed_len - 1, 0)

    def run(self, cache, seed: np.ndarray, slot: int, *, lora=None):
        """Stream ``seed[:-1]`` into ``cache`` for batch row ``slot``;
        returns the new cache. ``lora`` is the slot-mapped lora tree for a
        (1, C, d) activation (slot maps of shape (1,)), or None."""
        n_pre = self.n_prefill_tokens(len(seed))
        C = self.chunk
        for start in range(0, n_pre, C):
            toks = np.zeros((1, C), np.int32)
            part = np.asarray(seed[start:min(start + C, n_pre)], np.int32)
            toks[0, :len(part)] = part
            args = (self.params, cache, toks, np.int32(slot),
                    np.int32(start), np.int32(n_pre))
            with jax.profiler.TraceAnnotation("repro.prefill"):
                cache = (self._step(*args) if lora is None
                         else self._step_lora(*args, lora))
        return cache
