"""DFL training rounds through `Session`, back to back.

Traffic parameters (bench/traffic/<name>.json): n_clients, local_steps,
batch, seq_len, topology, p, scenario, method, T (0: T* of the graph),
lr, check_rounds, and ``limits`` for the comparison with the reference.

Set-up builds one `Session` (the compiled round with its state), hands
it the cell's seeded inputs (bench/seeded.py: base weights, LoRA start,
batch feed, W_t stream, T*), and drives it through its first
``check_rounds`` rounds, which also compiles the round; the window then
keeps stepping the same session on the same feed. After the window the
session is freed and the plain reference (bench/reference/train.py)
repeats the first rounds on the same inputs.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from bench import counts, seeded
from bench.core import HarnessError
from bench.kinds.base import RunBase, derived_seeds
from bench.reference import train as ref_train


def _norm_tree(lora) -> dict:
    """{"wq.a": lora["groups"][0]["attn"]["wq"]["a"], ...} for the
    group-scanned attention targets."""
    attn = lora["groups"][0]["attn"]
    return {f"{t}.{f}": attn[t][f] for t in sorted(attn) for f in ("a", "b")}


def _leaf_norms(tree) -> dict:
    import jax.numpy as jnp

    return {k: np.asarray(jnp.sqrt(jnp.sum(jnp.square(v), axis=(1, 2, 3))),
                          np.float64)
            for k, v in _norm_tree(tree).items()}


def rel_gaps(prog: dict, ref: dict, floor_share: float = 1e-3):
    """Worst per-leaf gap between norms, |p - r| / max(r, median r).
    Leaves whose reference norm is under ``floor_share`` of the median
    leaf (frozen or round-off-only leaves) are left out."""
    r_all = np.concatenate([np.ravel(v) for v in ref.values()])
    med = float(np.median(r_all[r_all > 0])) if np.any(r_all > 0) else 0.0
    worst = 0.0
    for k, r in ref.items():
        r = np.ravel(r)
        p = np.ravel(prog[k])
        keep = r >= floor_share * med
        if np.any(keep):
            gap = np.abs(p[keep] - r[keep]) / np.maximum(r[keep], med)
            worst = max(worst, float(gap.max()))
    return worst


class Run(RunBase):
    TRACE_SECONDS = 8.0

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.api import DFLConfig, Session

        t = self.mix
        self.seeds = derived_seeds(self.seed, ("init", "topology", "data"))
        adj = seeded.graph(t["topology"], t["n_clients"])
        self.T = t["T"] or seeded.switching_interval(adj, t["p"],
                                                     self.seeds["topology"])
        cfg = DFLConfig(
            model=self.model_cfg.name, reduced=False, task="lm",
            n_clients=t["n_clients"], topology=t["topology"], p=t["p"],
            scenario=t["scenario"], method=t["method"], T=self.T,
            rounds=1, local_steps=t["local_steps"], batch_size=t["batch"],
            seq_len=t["seq_len"], lr=t["lr"], donate=True,
            seed=self.seeds["topology"], data_seed=self.seeds["data"],
            init_seed=self.seeds["init"])
        self.sess = Session(cfg, model_cfg=self.model_cfg,
                            topology_schedule=seeded.GossipSchedule(
                                adj, t["p"], self.seeds["topology"]))
        self._hand_inputs()
        lora0 = jax.tree.map(lambda x: jnp.array(x, copy=True),
                             self.sess.lora)
        self.prog_loss = []
        self.prog_grad = None
        for r in range(t["check_rounds"]):
            ev = self._step()
            self.prog_loss.append(np.asarray(ev.metrics["loss_per_client"]))
            if r == 0:
                self.prog_grad = {k: v / 0.1 for k, v in
                                  _leaf_norms(self.sess.opt_state.mu).items()}
        self.prog_change = _leaf_norms(jax.tree.map(
            lambda a, b: a - b, self.sess.lora, lora0))
        del lora0
        if self.feed.drawn != t["check_rounds"]:
            raise HarnessError(
                f"{t['check_rounds']} rounds drew {self.feed.drawn} "
                f"batches from the benchmark's feed")

    def _hand_inputs(self) -> None:
        """Replace the session's own initial weights, LoRA start and
        batch stream with the cell's seeded ones, freeing the program's
        weights before the benchmark's are made."""
        import jax

        sess, t = self.sess, self.mix
        if not hasattr(sess, "_batches"):
            raise HarnessError("Session has no batch stream to replace")

        def like(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding), tree)
        base_like, lora_like = like(sess.base), like(sess.lora)
        sess.base = sess.lora = sess.opt_state = None
        gc.collect()
        sess.base = seeded.base_params(self.seeds["init"], base_like)
        sess.lora = seeded.lora_start(self.seeds["init"], lora_like)
        sess.opt_state = sess.opt.init(sess.lora)
        self.feed = seeded.Feed(seeded.token_rounds(
            self.model_cfg.vocab_size, t["n_clients"], t["local_steps"],
            t["batch"], t["seq_len"], self.seeds["data"]))
        sess._batches = self.feed

    def _step(self):
        with self.span("bench.round"):
            return self.sess.step()

    def tokens_per_round(self) -> int:
        t = self.mix
        return t["n_clients"] * t["local_steps"] * t["batch"] * t["seq_len"]

    def window(self, seconds: float) -> dict:
        import jax

        losses = []
        n = 0
        t0 = time.perf_counter()
        prev = None
        while True:
            ev = self._step()
            n += 1
            loss = ev.metrics["loss"]
            if prev is not None:
                jax.block_until_ready(prev)    # at most two rounds in flight
            losses.append(loss)
            prev = loss
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(self.sess.lora)
        elapsed = time.perf_counter() - t0
        failed = int(sum(not np.isfinite(float(x)) for x in losses))
        self.rounds_in_window = n
        return {"attempted": n, "failed": failed, "metrics": {
            "train_tokens_per_s": {
                "value": n * self.tokens_per_round() / elapsed,
                "unit": "tokens/s"}}}

    def counts(self) -> dict:
        t = self.mix
        return {"kind": "train",
                "round_flops": counts.round_flops(
                    self.config, t["n_clients"], t["local_steps"],
                    t["batch"], t["seq_len"]),
                "mix_bytes": counts.mix_bytes(self.config, t["n_clients"]),
                "rounds": self.rounds_in_window}

    def check(self) -> dict:
        self.sess = None
        gc.collect()
        ref = ref_train.run_rounds(self.config, self.mix, self.seeds,
                                   self.mix["check_rounds"])
        # the per-client losses are read but not compared: the program's
        # float32 at default matmul precision and the bfloat16 control
        # read alike on them (see PERF.md)
        prog_loss = np.stack(self.prog_loss)
        loss_gap = float(np.max(np.abs(prog_loss - ref["loss"])
                                / np.abs(ref["loss"])))
        print(f"reading loss_gap (not compared): {loss_gap!r}",
              file=sys.stderr)
        lim = self.mix["limits"]
        return {
            "grad_gap": {"value": rel_gaps(self.prog_grad, ref["grad"]),
                         "limit": lim["grad_gap"]},
            "change_gap": {"value": rel_gaps(self.prog_change,
                                             ref["change"]),
                           "limit": lim["change_gap"]},
        }
