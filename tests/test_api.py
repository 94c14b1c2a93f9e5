"""`repro.api` contract tests: DFLConfig validation/keys, Session parity
against the legacy hand-wired round loop (bit-for-bit at fixed seed),
static-vs-adaptive MaskSchedule parity at T=1, checkpoint/resume replay,
and callbacks."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AdaptiveSchedule, ConsoleLogger, DFLConfig,
                       HistoryRecorder, Session, StaticSchedule)
from repro.core import (build_lora_tree, make_dfl_round, make_topology,
                        round_masks)
from repro.data.synthetic import lm_token_stream
from repro.optim import AdamW

ENC_KW = dict(n_layers=1, d_model=32, n_heads=2, d_ff=64, vocab_size=256)


def _clf_config(**kw):
    base = dict(model="encoder", task="sst2", model_kw=ENC_KW, n_clients=4,
                rounds=4, local_steps=2, batch_size=8, p=1.0, T=2,
                lr=1e-3, seed=0)
    base.update(kw)
    return DFLConfig(**base)


# ---------------------------------------------------------------------------
# DFLConfig
# ---------------------------------------------------------------------------

def test_config_validation():
    with pytest.raises(ValueError):
        DFLConfig(method="sgd")                      # unknown method
    with pytest.raises(ValueError):
        DFLConfig(task="imagenet")                   # unknown task
    with pytest.raises(ValueError):
        DFLConfig(task="sst2", model="gemma3-1b")    # classifier != encoder
    with pytest.raises(ValueError):
        DFLConfig(task="lm", model="encoder")        # lm needs an arch
    with pytest.raises(ValueError):
        DFLConfig(rounds=0)
    with pytest.raises(ValueError):
        DFLConfig(adaptive_T=True, method="ffa")     # non-alternating


def test_config_seed_defaults_and_key():
    c = DFLConfig(seed=5)
    assert c.data_seed == 5 and c.init_seed == 5
    # explicit resolution matches defaulted resolution -> same key
    assert c.cache_key() == DFLConfig(seed=5, data_seed=5,
                                      init_seed=5).cache_key()
    assert c.cache_key() != DFLConfig(seed=6).cache_key()
    # model_kw dict vs tuple normalizes identically; json round-trips
    a = _clf_config()
    b = DFLConfig.from_dict(a.to_dict())
    assert a == b and a.cache_key() == b.cache_key()


def test_replace_rederives_dependent_seeds():
    # seed sweeps via replace() must move data/init seeds along
    c1 = DFLConfig(seed=0).replace(seed=1)
    assert c1.data_seed == 1 and c1.init_seed == 1
    assert c1 == DFLConfig(seed=1)
    # explicitly pinned seeds stay pinned across a seed change
    c2 = DFLConfig(seed=0, data_seed=17, init_seed=99).replace(seed=1)
    assert c2.data_seed == 17 and c2.init_seed == 99
    # explicit override together with the seed change wins
    c3 = DFLConfig(seed=0).replace(seed=1, data_seed=5)
    assert c3.data_seed == 5 and c3.init_seed == 1


# ---------------------------------------------------------------------------
# Session vs the legacy hand-wired loop (the quickstart setting, shrunk)
# ---------------------------------------------------------------------------

def test_session_matches_handwired_quickstart_loop():
    """Session must reproduce the hand-wired quickstart loop bit-for-bit
    at fixed seed: same per-round losses, same final lora. The legacy
    loop below is the pre-api quickstart BODY under the api's documented
    seed conventions (base <- key(seed), lora <- key(seed+1); the
    pre-api script drew both from key(0)) — the parity proven is of the
    loop mechanics, not of the init-key convention, which deliberately
    changed in the migration."""
    from repro.configs import get_config
    from repro.models import transformer as tf

    M, ROUNDS, LS, B, S = 4, 4, 2, 2, 16
    config = DFLConfig(model="gemma3-1b", task="lm", n_clients=M,
                       rounds=ROUNDS, local_steps=LS, batch_size=B,
                       seq_len=S, method="tad", p=0.15, T=3, lr=1e-3,
                       seed=0)

    # --- legacy hand-wired loop (pre-api quickstart body) ---
    cfg = get_config("gemma3-1b").reduced()
    base = tf.init_params(jax.random.key(0), cfg)
    lora = build_lora_tree(jax.random.key(1), base, cfg, n_clients=M)
    topo = make_topology("complete", M, p=0.15, seed=0)
    opt = AdamW(lr=1e-3)
    opt_state = opt.init(lora)

    def loss_fn(bp, lo, micro):
        # per_client=True mirrors the api's loss_fn: the REPORTED loss is
        # the host-side fixed-order reduction of the per-client vector
        # (grid-invariant), while the in-graph scalar feeds the gradient
        out, per = tf.lm_loss(bp, cfg, micro["tokens"], micro["targets"],
                              frontend=micro.get("frontend"), lora=lo,
                              per_client=True)
        return out[0], per

    from repro.api.session import _metric_loss
    round_fn = jax.jit(make_dfl_round(loss_fn, opt, local_steps=LS))
    stream = lm_token_stream(cfg.vocab_size, B * LS, S, n_clients=M, seed=0)
    legacy_losses = []
    for t in range(ROUNDS):
        raw = next(stream)
        batch = {k: jnp.asarray(v.reshape(M, LS, B, S).swapaxes(0, 1))
                 for k, v in raw.items()}
        W = jnp.asarray(topo.sample(), jnp.float32)
        masks = round_masks("tad", t, 3).as_array()
        lora, opt_state, metrics = round_fn(base, lora, opt_state, batch,
                                            W, masks)
        legacy_losses.append(_metric_loss(metrics))

    # --- the same experiment through the declarative API ---
    rec = HistoryRecorder()
    session = Session(config, callbacks=[rec])
    session.run()

    assert [h["loss"] for h in rec.history] == legacy_losses
    for a, b in zip(jax.tree.leaves(session.lora), jax.tree.leaves(lora)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# MaskSchedule: adaptive-vs-static parity at T=1
# ---------------------------------------------------------------------------

def test_adaptive_matches_static_at_T1():
    """With c small the controller pins T=1 for any observed rho, so the
    adaptive schedule must emit exactly the static T=1 mask calendar and
    the two runs must agree bit-for-bit."""
    config = _clf_config(T=1, rounds=6, p=0.5)
    static = Session(config, schedule=StaticSchedule("tad", T=1))
    adaptive_sched = AdaptiveSchedule("tad", c=0.1)
    adaptive = Session(config, schedule=adaptive_sched)
    r_s = static.run()
    r_a = adaptive.run()
    assert adaptive_sched.t_trace == [1] * 6
    assert r_s.final_loss == r_a.final_loss
    for a, b in zip(jax.tree.leaves(static.lora),
                    jax.tree.leaves(adaptive.lora)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adaptive_schedule_observes_W():
    sched = AdaptiveSchedule("tad", c=1.0, t_max=8)
    rho0 = sched.controller.rho_sq
    topo = make_topology("complete", 6, p=0.1, seed=0)
    for t in range(10):
        sched.next_masks(t, {"W": topo.sample()})
    assert sched.controller.rho_sq != rho0           # estimator engaged
    assert len(sched.t_trace) == 10


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

def test_checkpoint_resume_bitwise(tmp_path):
    path = os.path.join(tmp_path, "sess.npz")
    config = _clf_config(rounds=6, p=0.5)
    full = Session(config)
    full.run(3)
    full.save(path)
    full.run(3)

    resumed = Session(config)
    assert resumed.restore(path) == 3
    resumed.run(3)
    assert resumed.t == full.t == 6
    for a, b in zip(jax.tree.leaves(full.lora),
                    jax.tree.leaves(resumed.lora)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(full.opt_state.mu),
                    jax.tree.leaves(resumed.opt_state.mu)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class _WRecorder:
    """Callback capturing every round's realized W_t."""

    def __init__(self):
        self.Ws = []

    def on_round_end(self, ev):
        self.Ws.append(np.asarray(ev.W).copy())

    def on_run_end(self, session, result):
        pass


def test_checkpoint_resume_time_varying_topology_schedule(tmp_path):
    """Resume under a TIME-VARYING TopologySchedule (client churn: a
    stateful per-node Markov chain) must replay the W_t stream bit-for-bit:
    the resumed run's mixing matrices, lora, and opt state all match the
    uninterrupted run exactly."""
    path = os.path.join(tmp_path, "churn.npz")
    config = _clf_config(rounds=6, topology="torus", scenario="churn",
                         p=0.6, scenario_kw={"leave": 0.3, "rejoin": 0.4})
    full_rec = _WRecorder()
    full = Session(config, callbacks=[full_rec])
    full.run(3)
    full.save(path)
    full.run(3)

    res_rec = _WRecorder()
    resumed = Session(config, callbacks=[res_rec])
    assert resumed.restore(path) == 3
    resumed.run(3)
    assert resumed.t == full.t == 6
    # the churn Markov state was replayed: rounds 3..5 produce identical W_t
    assert len(full_rec.Ws) == 6 and len(res_rec.Ws) == 3
    for a, b in zip(full_rec.Ws[3:], res_rec.Ws):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(full.lora),
                    jax.tree.leaves(resumed.lora)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(full.opt_state.mu),
                    jax.tree.leaves(resumed.opt_state.mu)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# callbacks / events
# ---------------------------------------------------------------------------

def test_callbacks_and_events(capsys):
    rec_all = HistoryRecorder(consensus=True)
    rec_sub = HistoryRecorder(every=2)
    session = Session(_clf_config(), callbacks=[
        rec_all, rec_sub, ConsoleLogger(every=2, consensus=True)])
    result = session.run()
    assert [h["round"] for h in rec_all.history] == [0, 1, 2, 3]
    assert {"cross_norm", "delta_a_sq", "delta_b_sq"} <= \
        set(rec_all.history[0])
    # every=2 + the forced final round
    assert [h["round"] for h in rec_sub.history] == [0, 2, 3]
    assert result.final_loss == rec_all.history[-1]["loss"]
    out = capsys.readouterr().out
    assert "round" in out and "‖C‖" in out
    ev = session.step()                              # single-round stepping
    assert ev.t == 4 and session.t == 5
    assert 0.0 <= ev.w_gap() <= 1.0 + 1e-6


def test_evaluate_classifier_only():
    session = Session(_clf_config())
    res = session.evaluate()
    assert set(res) == {"acc", "acc_std_clients", "per_client"}
    assert len(res["per_client"]) == 4
    lm = Session(DFLConfig(model="gemma3-1b", task="lm", n_clients=4,
                           rounds=2, local_steps=1, batch_size=2,
                           seq_len=16, T=1))
    with pytest.raises(ValueError):
        lm.evaluate()


# ---------------------------------------------------------------------------
# build cache
# ---------------------------------------------------------------------------

def test_build_cache_shared_across_seeds():
    """Sweeps that vary only data/topology (pinned init_seed, the
    benchmark convention) share one model init and one compiled round."""
    from repro.api.session import _BUILD_CACHE
    s0 = Session(_clf_config(seed=11, init_seed=99))
    n = len(_BUILD_CACHE)
    s1 = Session(_clf_config(seed=12, init_seed=99, p=0.3, T=5))
    assert len(_BUILD_CACHE) == n
    assert s0.round_fn is s1.round_fn
    assert s0.base is s1.base
    Session(_clf_config(seed=11, init_seed=99, lr=2e-3))  # new build: lr
    assert len(_BUILD_CACHE) == n + 1
