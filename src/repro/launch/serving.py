"""Batched serving runtime with slot management (continuous batching).

A fixed pool of ``n_slots`` decode slots shares ONE compiled decode_step.
Every engine tick advances every active slot by exactly one token:
slots still consuming their prompt are teacher-forced (prefill-by-decode,
the default small-scale path; ``prefill_chunk`` switches long prompts to
the serving core's chunked prefill — `repro.serving.prefill`), slots past
it consume their previously generated token. Finished sequences (EOS /
max_new) free their slot immediately and the next queued request is
admitted on the following tick — no batch-wide barrier, which is the
continuous-batching property. Ticks with no active slot skip the device
entirely (``device_steps`` counts real compiled-step invocations).

Admission policy lives in `repro.serving.scheduler`: per-adapter queues
under deficit-round-robin with optional per-tenant quotas; the engine's
``queue``/``requests`` attributes are views onto it (one queue + no
quotas degenerates to the old FIFO behavior exactly). Request lifecycle
metrics (queue wait, TTFT, latency, preemptions) come out of
``engine.metrics()``.

KV storage has two modes:

- contiguous (default): per-slot rolling caches sized max_len — simple,
  but ``n_slots x max_len`` is a compile-time memory wall.
- ``paged=True``: GLOBAL attention layers keep their K/V in a shared
  physical page pool (`repro.serving.paging`); each slot holds a block
  table mapping logical pages to pool pages, shipped to the device as
  data each tick. Windowed layers keep rolling caches (already O(window)).
  When the pool runs dry the engine preempts the latest-admitted slot
  (pages freed, request requeued at the front; on re-admission its
  prompt + already-generated tokens are teacher-forced back in, which
  reproduces the exact cache state, so the continuation is unchanged).

Per-slot position counters in the KV cache ("t": (B,), models/attention)
make admission a pure cache-row reset: positions restart at 0 for the new
request and the per-row validity mask hides the previous occupant's stale
entries. No reallocation, no recompilation, ever.

Multi-adapter serving: pass ``adapters`` (an object with ``row(name)`` and
``serving_lora(slot_rows)`` — repro.api.serving.AdapterPool) and each
request may name the TAD-LoRA adapter it wants. The engine keeps a per-slot
adapter-row map and hands decode_step a lora tree whose leaves carry the
whole stacked pool plus the (B,) slot map; adapter selection is DATA
(per-row gather in kernels.ops.slot_lora_matmul), so heterogeneous
adapters, hot-swapped weights, and retargeted slots all reuse the one
compiled step. ``compile_count`` counts traces and must stay at 1 for the
engine's lifetime (asserted by tests/test_serving.py and
benchmarks/serving.py).

(The decode_32k / long_500k dry-run shapes are exactly one engine tick at
production scale.)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.serving.paging import BlockTables, PagePool
from repro.serving.prefill import ChunkedPrefill
from repro.serving.scheduler import (Request, Scheduler,  # noqa: F401
                                     TenantQuota)

__all__ = ["Request", "ServeEngine", "TenantQuota"]


@dataclass
class _Slot:
    req: Optional[Request] = None
    seed: Optional[np.ndarray] = None    # prompt (+ replayed tokens_out)
    fed: int = 0                         # seed tokens consumed so far
    pos: int = 0                         # next cache position to be written


class _QueueView:
    """The engine's pre-scheduler ``queue`` deque, as a facade over the
    scheduler's per-adapter queues (append/extend submit; len/bool/iter
    aggregate). Keeps direct-queue tests and callers working unchanged."""

    def __init__(self, engine: "ServeEngine"):
        self._engine = engine

    def append(self, req: Request) -> None:
        self._engine.scheduler.submit(req, tick=self._engine.ticks)

    def extend(self, reqs) -> None:
        for r in reqs:
            self.append(r)

    def __len__(self) -> int:
        return self._engine.scheduler.n_queued

    def __bool__(self) -> bool:
        return self._engine.scheduler.n_queued > 0

    def __iter__(self):
        return iter(self._engine.scheduler.queued_requests())

    def __getitem__(self, i):
        return self._engine.scheduler.queued_requests()[i]


class ServeEngine:
    """Continuous-batching decode engine over one compiled decode_step.

    ``params`` is the base model; with ``adapters`` set, decode additionally
    applies a per-slot TAD-LoRA adapter chosen at admission from
    ``Request.adapter``. Completed requests stay reachable via
    ``engine.requests[rid]`` after their slot is freed.

    Serving-core knobs: ``paged``/``page_size``/``n_pages`` switch global
    attention layers to page-pool KV (n_pages defaults to exactly enough
    for every slot at max_len, i.e. no contention; size it smaller to
    exercise preemption), ``prefill_chunk`` enables chunked prefill for
    prompts longer than one chunk, ``quotas`` maps adapter refs to
    `TenantQuota` limits, and ``scheduler`` swaps the whole policy.
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int = 4,
                 max_len: int = 256, adapters=None, paged: bool = False,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 prefill_chunk: int = 0,
                 quotas: Optional[Dict] = None,
                 scheduler: Optional[Scheduler] = None):
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.adapters = adapters
        self.paged = bool(paged)
        self.scheduler = scheduler if scheduler is not None \
            else Scheduler(quotas=quotas)
        if self.paged:
            self.page_size = int(page_size)
            # round the horizon up to whole pages: L = P * page_size is
            # what the gathered paged view sees, so it must cover max_len
            self.max_len = -(-max_len // self.page_size) * self.page_size
            self.pages_per_seq = self.max_len // self.page_size
            if n_pages is None:
                n_pages = 1 + n_slots * self.pages_per_seq
            self.page_pool = PagePool(n_pages)
            self.tables = BlockTables(n_slots, self.pages_per_seq)
            self.cache = tf.init_cache(cfg, n_slots, self.max_len,
                                       paging=(n_pages, self.page_size))
        else:
            self.page_size = 0
            self.max_len = max_len
            self.page_pool = None
            self.tables = None
            self.cache = tf.init_cache(cfg, n_slots, max_len)
        self.prefill = (ChunkedPrefill(params, cfg, prefill_chunk)
                        if prefill_chunk else None)
        self.slots = [_Slot() for _ in range(n_slots)]
        self.queue = _QueueView(self)
        self.next_in = np.zeros((n_slots, 1), np.int32)
        # adapter row per slot; row 0 is the pool's base (zero) adapter
        self.slot_rows = np.zeros((n_slots,), np.int32)
        self.compile_count = 0           # traces of decode_step (== compiles)
        if adapters is None:
            def _step(p, c, t):
                self.compile_count += 1
                return tf.decode_step(p, cfg, t, c)
        else:
            def _step(p, c, t, lo):
                self.compile_count += 1
                return tf.decode_step(p, cfg, t, c, lora=lo)
        self._decode = jax.jit(_step)
        self._next_rid = 0
        self.ticks = 0
        self.device_steps = 0            # compiled-step invocations (idle
        #                                  ticks never reach the device)

    @property
    def requests(self) -> Dict[int, Request]:
        """rid -> Request registry (owned by the scheduler)."""
        return self.scheduler.requests

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new: int = 32, eos_id: Optional[int] = None,
               adapter: Union[str, int, None] = None) -> int:
        """Queue a request; returns its rid (see ``engine.requests``).
        Raises `QuotaExceeded` past the adapter's ``max_queued`` and
        ValueError when a paged request could never fit the pool."""
        if adapter is not None and self.adapters is None:
            raise ValueError("engine built without an AdapterPool cannot "
                             "serve per-request adapters")
        if self.adapters is not None:
            self.adapters.row(adapter)   # unknown names fail HERE, not
            #                              mid-admission with a slot held
        prompt = np.asarray(prompt, np.int32)
        if self.paged:
            total = len(prompt) + max_new
            if total > self.max_len:
                raise ValueError(f"prompt+max_new = {total} exceeds the "
                                 f"paged horizon {self.max_len}")
            need = -(-total // self.page_size)
            if need > self.page_pool.capacity:
                # guarantees any single admitted request can always run to
                # completion (eviction has everyone else to evict but never
                # needs to evict the sole survivor)
                raise ValueError(
                    f"request needs {need} pages but the pool holds "
                    f"{self.page_pool.capacity}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new=max_new,
                      eos_id=eos_id, adapter=adapter)
        self.scheduler.submit(req, tick=self.ticks)
        return rid

    def set_frontend(self, frontend) -> None:
        """Fill the cross-attention KV caches from frontend embeddings
        (enc-dec / VLM archs), shared by every slot. Slot admission resets
        only positions and recurrent rows, so the cross KV survives
        request turnover; call again to change the context."""
        cfg = self.cfg
        mem = (tf._encoder_forward(self.params, cfg, frontend, None)
               if cfg.family == "encdec" else frontend)
        B = frontend.shape[0]

        def fill(attn_p):
            k = (mem @ attn_p["wk"]).reshape(B, -1, cfg.n_kv_heads, cfg.hd)
            v = (mem @ attn_p["wv"]).reshape(B, -1, cfg.n_kv_heads, cfg.hd)
            return {"ck": k, "cv": v}

        for j, spec in enumerate(cfg.pattern):
            gp = self.params["groups"][j]
            target = gp.get("cross") or (gp["attn"] if spec.kind == "cross"
                                         else None)
            if target is None:
                continue
            for g in range(cfg.n_groups):
                pg = jax.tree.map(lambda x: x[g], target)
                cc = fill(pg)
                self.cache["groups"][j]["cross"] = jax.tree.map(
                    lambda buf, new, g=g: buf.at[g].set(new),
                    self.cache["groups"][j]["cross"], cc)

    # ------------------------------------------------------------------
    # Admission (scheduler pick -> page grant -> cache-row reset -> prefill)
    # ------------------------------------------------------------------
    def _active_counts(self) -> Dict:
        counts: Dict = {}
        for s in self.slots:
            if s.req is not None:
                counts[s.req.adapter] = counts.get(s.req.adapter, 0) + 1
        return counts

    def _reset_slot_cache(self, slots: list) -> None:
        """Zero the slots' position counters across every layer cache and
        recurrent state — admission is a per-row reset, nothing else.
        Takes ALL slots admitted this tick at once: one tree pass total
        instead of rebuilding the whole cache pytree per admitted slot."""
        rows = np.asarray(slots)

        def reset(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            if name == "t":
                return leaf.at[..., rows].set(0)
            if name in ("h", "c", "n", "m", "C", "conv"):
                # recurrent states: zero the slots' rows (axis after groups)
                axis = 1 if leaf.ndim >= 2 and any(
                    getattr(k, "key", None) == "groups" for k in path) else 0
                idx = [slice(None)] * leaf.ndim
                idx[axis] = rows
                return leaf.at[tuple(idx)].set(0)
            return leaf
        self.cache = jax.tree_util.tree_map_with_path(reset, self.cache)

    def _chunk_lora(self, row: int):
        return (None if self.adapters is None
                else self.adapters.serving_lora(np.asarray([row], np.int32)))

    def _push_table(self) -> None:
        self.cache["pages"]["table"] = jnp.asarray(self.tables.table)

    def _admit(self) -> None:
        placed: list = []                # (slot, req, row, seed, chunked)
        free = [i for i, s in enumerate(self.slots) if s.req is None]
        for i in free:
            req = self.scheduler.next_request(self._active_counts())
            if req is None:
                break
            # resolve the adapter BEFORE touching any engine state so a
            # bad name (possible via direct queue.append) cannot leave
            # a half-admitted slot behind
            row = (self.adapters.row(req.adapter)
                   if self.adapters is not None else 0)
            seed = np.asarray(req.prompt, np.int32)
            if req.tokens_out:
                # re-admission after preemption: teacher-force the already
                # generated tokens back in — bitwise the same cache state,
                # so the continuation is exactly what it would have been
                seed = np.concatenate(
                    [seed, np.asarray(req.tokens_out, np.int32)])
            chunked = self.prefill is not None and len(seed) > 1
            if self.paged:
                n_pre = len(seed) - 1 if chunked else 0
                # pages covering prefill positions + the next decode write
                if not self.tables.grow(i, n_pre // self.page_size,
                                        self.page_pool):
                    # admission never preempts running slots; try again
                    # next tick when completions return pages
                    self.scheduler.push_front(req)
                    break
            s = _Slot(req=req, seed=seed)
            self.slots[i] = s
            self.slot_rows[i] = row
            self.scheduler.mark_admitted(req, self.ticks)
            placed.append((i, s, row, chunked))
        if not placed:
            return
        self._reset_slot_cache([i for i, *_ in placed])
        if self.paged:
            self._push_table()           # chunk prefill reads the table
        for i, s, row, chunked in placed:
            if chunked:
                n_pre = len(s.seed) - 1
                self.cache = self.prefill.run(self.cache, s.seed, i,
                                              lora=self._chunk_lora(row))
                s.fed = len(s.seed)
                s.pos = n_pre
                self.next_in[i, 0] = s.seed[-1]
            else:
                s.fed = 1
                s.pos = 0
                self.next_in[i, 0] = s.seed[0]

    # ------------------------------------------------------------------
    # Page upkeep (decode growth + preemption-by-eviction)
    # ------------------------------------------------------------------
    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Latest-admitted active slot other than ``exclude`` — LIFO
        preemption keeps the oldest streams flowing."""
        best, best_tick = None, -1
        for j, s in enumerate(self.slots):
            if j == exclude or s.req is None:
                continue
            at = s.req.admit_tick if s.req.admit_tick is not None else 0
            if at >= best_tick:
                best, best_tick = j, at
        return best

    def _evict(self, victim: int) -> None:
        req = self.slots[victim].req
        self.tables.release(victim, self.page_pool)
        self.slots[victim] = _Slot()
        self.scheduler.requeue_front(req)

    def _ensure_decode_pages(self) -> None:
        """Every active slot writes cache position ``pos`` this tick —
        make sure its page exists, evicting latest-admitted slots when the
        pool is dry (submit-time capacity checks guarantee the last slot
        standing always fits)."""
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            while not self.tables.grow(i, s.pos // self.page_size,
                                       self.page_pool):
                victim = self._pick_victim(exclude=i)
                if victim is None:
                    raise RuntimeError(
                        "page pool exhausted with no evictable slot — "
                        "submit-time capacity checks should prevent this")
                self._evict(victim)

    def _free_slot(self, i: int) -> None:
        if self.paged:
            self.tables.release(i, self.page_pool)
        self.slots[i] = _Slot()

    # ------------------------------------------------------------------
    def tick(self) -> int:
        """One engine step. Returns number of active slots. Idle ticks
        (nothing queued or running) return 0 without touching the device.
        Each tick is a ``repro.tick`` host span on the profiler's clock
        (recorded only while a profiler trace is on)."""
        with jax.profiler.StepTraceAnnotation(
                "repro.tick", step_num=self.ticks,
                active=sum(s.req is not None for s in self.slots),
                queued=self.scheduler.n_queued):
            return self._tick()

    def _tick(self) -> int:
        with jax.profiler.TraceAnnotation("repro.tick.admit"):
            self._admit()
            if self.paged:
                self._ensure_decode_pages()
        active = [i for i, s in enumerate(self.slots) if s.req is not None]
        if not active:
            self.ticks += 1          # the clock advances; the device idles
            return 0
        if self.paged:
            with jax.profiler.TraceAnnotation("repro.tick.table"):
                self._push_table()
        with jax.profiler.TraceAnnotation("repro.tick.dispatch"):
            tokens = jnp.asarray(self.next_in)
            if self.adapters is not None:
                # the pool tree is re-read every tick, so pool.update()/
                # sync between ticks hot-swaps weights with no engine
                # involvement
                lora = self.adapters.serving_lora(self.slot_rows)
                logits, self.cache = self._decode(self.params, self.cache,
                                                  tokens, lora)
            else:
                logits, self.cache = self._decode(self.params, self.cache,
                                                  tokens)
        self.device_steps += 1
        with jax.profiler.TraceAnnotation("repro.tick.readback"):
            # the host waits here for the decode step to finish
            logits_np = np.asarray(logits[:, -1, :self.cfg.vocab_size])
        with jax.profiler.TraceAnnotation("repro.tick.sample"):
            self._sample(active, logits_np)
        self.ticks += 1
        return len(active)

    def _sample(self, active: list, logits_np: np.ndarray) -> None:
        """Greedy next tokens for the active slots; finished requests
        free their slots."""
        for i in active:
            s = self.slots[i]
            req = s.req
            s.pos += 1
            if s.fed < len(s.seed):
                # still prefilling: teacher-force the next prompt token
                self.next_in[i, 0] = s.seed[s.fed]
                s.fed += 1
                continue
            nxt = int(logits_np[i].argmax())
            req.tokens_out.append(nxt)
            self.scheduler.mark_first_token(req, self.ticks)
            self.next_in[i, 0] = nxt
            if (req.eos_id is not None and nxt == req.eos_id) or \
                    len(req.tokens_out) >= req.max_new:
                req.done = True
                self.scheduler.mark_done(req, self.ticks)
                self._free_slot(i)               # freed immediately

    def run(self, max_ticks: int = 10_000) -> None:
        """Tick until the queue and every slot drain. Returns immediately
        on an idle engine — no device steps are spent."""
        for _ in range(max_ticks):
            if not self.scheduler.n_queued and \
                    all(s.req is None for s in self.slots):
                return
            self.tick()
        raise RuntimeError("serve engine did not drain")

    # ------------------------------------------------------------------
    def metrics(self) -> dict:
        """Scheduler lifecycle aggregates + engine counters."""
        out = self.scheduler.summary()
        out["ticks"] = self.ticks
        out["device_steps"] = self.device_steps
        if self.paged:
            out["pages_used"] = self.page_pool.n_used
            out["pages_free"] = self.page_pool.n_free
        return out
