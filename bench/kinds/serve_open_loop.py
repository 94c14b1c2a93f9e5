"""Open-loop multi-adapter serving through `ServingSession`.

Traffic parameters (bench/traffic/<name>.json): rate_per_s; engine
(n_slots, page_size, prefill_chunk, max_len); prompt and output length
distributions (lognormal: median, sigma, min, max); adapters (clients,
zipf_s; the pool also holds the clients' consensus); drain_s; check
(requests, min_tokens); limits.

Every seed gets the same multiset of arrival gaps, prompt lengths,
output lengths and adapter choices (stratified quantiles of their
distributions), in an order the seed draws, with fresh prompt tokens:
runs differ in order and content, not in the amount of work.

Requests are submitted at their due times on the host clock, whether or
not earlier ones have finished. Latency runs from the due time, so a
stall of the engine or of the generator shows in every later request.
Tokens are greedy (the engine's argmax) and carry no EOS.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

from bench import counts, seeded
from bench.kinds.base import RunBase, derived_seeds
from bench.reference import model as ref_model, serve as ref_serve


def _inv_normal(u):
    """Quantile function of the standard normal (Acklam's rational
    approximation, relative error under 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    u = np.asarray(u, np.float64)
    out = np.empty_like(u)
    lo, hi = u < 0.02425, u > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(u[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
               + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = u[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                * r + b[4]) * r + 1)
    q = np.sqrt(-2 * np.log(1 - u[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    return out


def _lognormal_set(dist: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    x = np.exp(np.log(dist["median"]) + dist["sigma"] * _inv_normal(u))
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def _zipf_set(n_items: int, s: float, n: int) -> np.ndarray:
    """n draws of a rank with P(k) ~ 1/k^s, as stratified quantiles."""
    p = 1.0 / np.arange(1, n_items + 1) ** s
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), n_items - 1)


@dataclass
class Planned:
    due: float                    # seconds after the window opens
    prompt: np.ndarray
    max_new: int
    row: int                      # adapter: client index, -1 = consensus


def plan(mix: dict, seconds: float, seed: int, vocab: int) -> list:
    """The requests due in a window of ``seconds``, from the seed."""
    rng = np.random.default_rng(seed)
    n = max(1, int(mix["rate_per_s"] * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / mix["rate_per_s"])
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plens = rng.permutation(_lognormal_set(mix["prompt"], n))
    outs = rng.permutation(_lognormal_set(mix["output"], n))
    k = mix["adapters"]["clients"] + 1
    ranks = rng.permutation(_zipf_set(k, mix["adapters"]["zipf_s"], n))
    order = rng.permutation(k)          # which adapter holds each rank
    rows = np.where(order[ranks] == k - 1, -1, order[ranks])
    return [Planned(float(due[i]),
                    rng.integers(0, vocab, size=int(plens[i])).astype(
                        np.int32), int(outs[i]), int(rows[i]))
            for i in range(n)]


def _p(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class Run(RunBase):
    TRACE_SECONDS = 8.0

    def setup(self) -> None:
        import jax
        from repro.api.serving import AdapterPool, ServingSession
        from repro.models.transformer import param_specs

        t, mc = self.mix, self.model_cfg
        self.seeds = derived_seeds(self.seed, ("init", "adapters",
                                               "traffic"))
        self.ref_spec = ref_model.spec_from_config(self.config)
        m = t["adapters"]["clients"]
        stacked = jax.jit(ref_serve.adapters, static_argnums=(1, 2))(
            jax.random.key(self.seeds["adapters"]), self.ref_spec, m)
        lora = {"groups": [{"attn": stacked}]}
        pool = AdapterPool.from_stacked(lora, consensus=True)
        eng = t["engine"]
        params = seeded.base_params(self.seeds["init"], param_specs(mc))
        self.serving = ServingSession(
            model_cfg=mc, params=params, adapters=pool,
            n_slots=eng["n_slots"], max_len=eng["max_len"], paged=True,
            page_size=eng["page_size"], prefill_chunk=eng["prefill_chunk"])
        self.engine = self.serving.engine
        self._warm_up()

    def _warm_up(self) -> None:
        """Compile every shape the window uses: the slot resets for each
        number of slots admitted at once, the chunk prefill, the decode
        step and the host-side slicing of a tick."""
        eng = self.engine
        for k in range(1, eng.n_slots + 1):
            eng._reset_slot_cache(list(range(k)))
        chunk = self.mix["engine"]["prefill_chunk"]
        for row in ("client_0", "consensus"):
            self.serving.submit(np.arange(2 * chunk + 3, dtype=np.int32)
                                % 97, adapter=row, max_new=3)
        self.serving.run()
        import jax
        jax.block_until_ready(eng.cache)

    def _name(self, row: int) -> str:
        return "consensus" if row < 0 else f"client_{row}"

    def window(self, seconds: float) -> dict:
        vocab = self.model_cfg.vocab_size
        reqs = plan(self.mix, seconds, self.seeds["traffic"], vocab)
        eng = self.engine
        pending = deque(reqs)
        live, done = {}, {}
        times = {}
        self.contexts = []              # per tick: active slots' contexts
        drain = self.mix["drain_s"]
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while pending and pending[0].due <= now:
                r = pending.popleft()
                with self.span("bench.submit"):
                    rid = self.serving.submit(r.prompt, adapter=self._name(
                        r.row), max_new=r.max_new)
                live[rid] = r
                times[rid] = []
            busy = eng.scheduler.n_queued or any(
                s.req is not None for s in eng.slots)
            if busy:
                with self.span("bench.tick"):
                    eng.tick()
                now = time.perf_counter() - t0
                ctx = []
                for rid in list(live):
                    req = eng.requests[rid]
                    stamps = times[rid]
                    n_out = len(req.tokens_out)
                    if n_out > len(stamps):
                        # the new token attended the prompt and every
                        # earlier output token
                        ctx.append(len(req.prompt) + n_out - 1)
                        stamps.extend([now] * (n_out - len(stamps)))
                    if req.done:
                        done[rid] = live.pop(rid)
                self.contexts.append(ctx)
            elif pending:
                time.sleep(max(0.0, pending[0].due - now))
            else:
                break
            if now > seconds + drain:
                break
        self.finished = [(live_r.prompt, np.asarray(eng.requests[rid]
                                                    .tokens_out, np.int32),
                          live_r.row) for rid, live_r in done.items()]
        return self._summarise(reqs, live, done, times, seconds, drain)

    def _summarise(self, reqs, live, done, times, seconds, drain) -> dict:
        ttft, itl, in_window = [], [], 0
        for rid, r in list(done.items()) + list(live.items()):
            st = times[rid]
            in_window += sum(1 for x in st if x <= seconds)
            if rid in done:
                ttft.append(st[0] - r.due)
                itl.extend(np.diff(st).tolist())
            else:                         # missing: counts as the limit
                ttft.append(seconds + drain - r.due)
        failed = len(reqs) - len(done)
        self.n_served_tokens = in_window
        return {"attempted": len(reqs), "failed": failed, "metrics": {
            "serve_tokens_per_s": {"value": in_window / seconds,
                                   "unit": "tokens/s"},
            "ttft_p90_ms": {"value": 1e3 * _p(ttft, 90), "unit": "ms"},
            "itl_p95_ms": {"value": 1e3 * _p(itl, 95) if itl else
                           1e3 * (seconds + drain), "unit": "ms"}}}

    def counts(self) -> dict:
        eng = self.mix["engine"]
        cfg = self.config
        return {"kind": "serve",
                "decode_flops": sum(counts.decode_flops(cfg, ctx)
                                    for ctx in self.contexts if ctx),
                "paged_attn_bytes": sum(
                    counts.paged_attn_bytes(cfg, ctx, eng["page_size"])
                    for ctx in self.contexts if ctx),
                "slot_lora": [counts.slot_lora_cost(cfg, len(ctx))
                              for ctx in self.contexts if ctx],
                "ticks": sum(1 for ctx in self.contexts if ctx)}

    def _sample(self) -> list:
        """The finished requests the reference checks: the one with the
        most served tokens, then others drawn from the seed until
        ``min_tokens`` served tokens or ``requests`` requests."""
        chk = self.mix["check"]
        fin = sorted(self.finished, key=lambda f: -len(f[1]))
        if not fin:
            return []
        rng = np.random.default_rng(self.seeds["traffic"] + 1)
        rest = [fin[i] for i in rng.permutation(len(fin) - 1) + 1]
        out, n = [fin[0]], len(fin[0][1])
        for f in rest:
            if n >= chk["min_tokens"] or len(out) >= chk["requests"]:
                break
            out.append(f)
            n += len(f[1])
        return out

    def check(self) -> dict:
        sample = self._sample()
        self.serving = self.engine = None
        gc.collect()
        eng = self.mix["engine"]
        got = ref_serve.served_gaps(
            self.config, self.seeds["init"], self.seeds["adapters"],
            self.mix["adapters"]["clients"], sample, eng["max_len"],
            self.mix["output"]["max"])
        lim = self.mix["limits"]
        return {"served_logit_gap": {"value": got["gap"],
                                     "limit": lim["logit_gap"]},
                "checked_tokens_short": {
                    "value": max(0, self.mix["check"]["min_tokens"]
                                 - got["tokens"]), "limit": 0}}
