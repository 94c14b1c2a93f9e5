"""Smoke run of TAD-LoRA's main path on a TPU: train, then serve the result.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four local chips: the sharded round

Everything runs in this one process (a chip belongs to one process).

  A. train: three full-width gemma3-1b DFL rounds through `Session` on the
     seeded synthetic LM stream; per-round losses must be finite and the
     compiled round must hold the Pallas gossip kernel (`tpu_custom_call`).
  B. serve: `ServingSession.from_session` with 8 paged slots and chunked
     prefill; more requests than slots, over several trained adapters, run
     to completion with no compile after warm-up.
  C. kernels: `gossip_mix_seg`, `slot_lora_matmul` and `paged_attn_decode`
     on the chip against their `kernels/ref.py` oracles at the widths the
     phases used; `flash_attention`'s output and gradients at gemma3-1b's
     heads and at the benchmark cells' (the training phase's 64-token
     sequences keep XLA's attention).

With ``--chips 4`` only the sharded path runs: a one-process
`ClusterSession` over the four local chips (client axis sharded) against a
one-device `Session` of the same config, plus the column-sharded gossip mix
against the one-device mix on the same input.

Times and memory printed on the way are smoke observations, not a
benchmark. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
it is printed only when every phase passed. Without a TPU the script exits
non-zero before any phase.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import DFLConfig, Session  # noqa: E402
from repro.api.cluster import ClusterSession  # noqa: E402
from repro.api.serving import ServingSession  # noqa: E402
from repro.api.session import clear_build_cache  # noqa: E402
from repro.core import mixing  # noqa: E402
from repro.dist import multihost, sharding  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

TRAIN = DFLConfig(model="gemma3-1b", task="lm", reduced=False, n_clients=8,
                  local_steps=4, batch_size=4, seq_len=64, method="tad",
                  rounds=3, donate=True)

# Kernel vs oracle: norm-relative error against the f32 oracle run at
# "highest" matmul precision. A kernel whose f32 dots take one bf16 pass
# rounds each operand to 2^-9 relative, so a product carries <= 2^-8
# (~3.9e-3); sums of such products stay under that in norm. 1e-2 leaves
# 2.5x headroom; a wrong block, page or adapter row is off by O(1).
KERNEL_RTOL = 1e-2
# The same under "highest", where every kernel product takes float32
# operands: only the order of float32 sums differs from the oracle
# (~1e-6 relative); a product left at one bf16 pass reads ~3e-3.
KERNEL_F32_RTOL = 1e-4
# Sharded vs one-device gossip mix: the same kernel on the same columns,
# only the column stripes differ between devices, so it agrees to f32
# round-off.
MIX_RTOL = 1e-5
# Sharded vs one-device round losses. Both runs round the same operands
# to bf16 in their default-precision f32 dots; they differ only in f32
# accumulation order (~1e-6 relative), which Adam's sign-like early steps
# can amplify on near-zero gradients. The loss is ~ln(vocab) ~ 12.5 at
# random init; 1e-3 relative (~0.0125) is far above that drift and well
# below the change a mis-sharded client block or a wrong mix would cause
# in the logits over 12 local steps.
LOSS_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) /
                 max(np.linalg.norm(want), 1e-30))


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def compile_round_once(session) -> dict:
    """Swap the session's jitted round for one ahead-of-time compile made
    from the first round's own arguments, so every round runs that one
    executable. Returns {"seconds", "hlo"} once the first round has run."""
    info: dict = {}
    jitted = session.round_fn

    def first_call(*args):
        specs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), args)
        t0 = time.perf_counter()
        compiled = jitted.lower(*specs).compile()
        info["seconds"] = time.perf_counter() - t0
        info["hlo"] = compiled.as_text()
        session.round_fn = compiled
        return compiled(*args)

    session.round_fn = first_call
    return info


def run_rounds(session, tag: str) -> list:
    """Run config.rounds rounds one by one; returns the per-round losses."""
    info = compile_round_once(session)
    losses = []
    for _ in range(session.config.rounds):
        t0 = time.perf_counter()
        ev = session.step()
        jax.block_until_ready(session.lora)
        loss = ev.loss
        dt = time.perf_counter() - t0
        losses.append(loss)
        log(f"[{tag}] round {ev.t}: loss={loss!r} wall_s={dt!r}"
            + (" (after compile)" if ev.t == 0 else ""))
    log(f"[{tag}] round compile_s={info['seconds']!r}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"[{tag}] non-finite round loss: {losses}")
    if "tpu_custom_call" not in info["hlo"]:
        raise AssertionError(f"[{tag}] the compiled round holds no Pallas "
                             f"kernel: the flat gossip mix did not lower "
                             f"to gossip_mix_seg")
    return losses


# ---------------------------------------------------------------------------
# phases on one chip
# ---------------------------------------------------------------------------

def phase_train(config: DFLConfig) -> Session:
    t0 = time.perf_counter()
    session = Session(config)
    log(f"[train] session built in {time.perf_counter() - t0!r}s "
        f"({session.model_cfg.name}, T={session.T})")
    run_rounds(session, "train")
    log(f"[train] peak_bytes_in_use={peak_bytes()}")
    return session


def phase_serve(session: Session, *, n_requests: int = 12,
                max_new: int = 16) -> ServingSession:
    serving = ServingSession.from_session(session, n_slots=8, paged=True,
                                          prefill_chunk=32, max_len=256)
    adapters = [a for a in serving.adapters if a != "base"]
    vocab = serving.model_cfg.vocab_size
    rng = np.random.default_rng(0)

    def prompt():
        return rng.integers(0, vocab, size=int(rng.integers(8, 100)))

    t0 = time.perf_counter()
    serving.generate(prompt(), adapter=adapters[0], max_new=max_new)
    warm = (serving.compile_count, serving.engine.prefill.compile_count)
    log(f"[serve] warm-up (compile) wall_s={time.perf_counter() - t0!r} "
        f"decode_compiles={warm[0]} prefill_compiles={warm[1]}")

    names = [adapters[i % len(adapters)] for i in range(n_requests)]
    if len(set(names)) < 3 or n_requests <= serving.engine.n_slots:
        raise AssertionError("[serve] the smoke needs more requests than "
                             "slots, over >= 3 adapters")
    rids = [serving.submit(prompt(), adapter=name, max_new=max_new)
            for name in names]
    ticks = []
    t0 = time.perf_counter()
    while not all(serving.engine.requests[r].done for r in rids):
        t1 = time.perf_counter()
        serving.tick()
        ticks.append(time.perf_counter() - t1)
        if len(ticks) > 10_000:
            raise AssertionError("[serve] requests did not complete")
    wall = time.perf_counter() - t0
    now = (serving.compile_count, serving.engine.prefill.compile_count)
    log(f"[serve] {n_requests} requests over {len(set(names))} adapters "
        f"on {serving.engine.n_slots} slots: ticks={len(ticks)} "
        f"wall_s={wall!r} "
        f"tick_s_median={float(np.median(ticks))!r} "
        f"tick_s_max={max(ticks)!r}")
    if now != warm:
        raise AssertionError(f"[serve] recompiled after warm-up: "
                             f"{warm} -> {now}")
    for r in rids:
        out = serving.result(r)
        if len(out) != max_new or not all(0 <= t < vocab for t in out):
            raise AssertionError(f"[serve] request {r} returned {out}")
    log(f"[serve] metrics={json.dumps(serving.metrics(), default=float)}")
    log(f"[serve] peak_bytes_in_use={peak_bytes()}")
    return serving


def _check(name: str, got, want, rtol: float) -> None:
    err = rel_err(got, want)
    log(f"[kernels] {name}: rel_err={err!r} (limit {rtol})")
    if not err <= rtol:
        raise AssertionError(f"[kernels] {name} disagrees with its oracle: "
                             f"{err} > {rtol}")


def phase_kernels(session: Session, serving: ServingSession) -> None:
    cfg = session.model_cfg
    keys = iter(jax.random.split(jax.random.key(7), 48))
    normal = lambda shape: jax.random.normal(next(keys), shape, jnp.float32)

    # gossip_mix_seg on the round's own flat layout and a real W_t
    plan = mixing.get_mix_plan(session.lora)
    m = plan.m
    W = jnp.asarray(session.topo_schedule.next_w(0), jnp.float32)
    x = normal((m, plan.padded))
    seg = jnp.asarray(plan.segment_mask(1.0, 0.0), jnp.float32)
    got = ops.gossip_mix_seg(W, x, seg)
    with jax.default_matmul_precision("highest"):
        want = ref.gossip_mix_seg_ref(W, x, seg)
    _check(f"gossip_mix_seg m={m} P={plan.padded}", got, want, KERNEL_RTOL)

    # slot_lora_matmul at the decode step's widths and pool size
    pool = serving.pool
    B, K, r = serving.engine.n_slots, cfg.d_model, cfg.lora_rank
    slots = jnp.asarray(np.random.default_rng(1).integers(
        0, pool.capacity, size=B), jnp.int32)
    for name, N in (("wq", cfg.n_heads * cfg.hd),
                    ("wv", cfg.n_kv_heads * cfg.hd)):
        xs, w = normal((B, K)), normal((K, N)) / np.sqrt(K)
        a, b = normal((pool.capacity, K, r)), normal((pool.capacity, r, N))
        got = ops.slot_lora_matmul(xs, w, a, b, slots, 2.0)
        with jax.default_matmul_precision("highest"):
            want = ref.slot_lora_matmul_ref(xs, w, a, b, slots, 2.0)
        _check(f"slot_lora_matmul {name} B={B} K={K} N={N} "
               f"adapters={pool.capacity}", got, want, KERNEL_RTOL)

    # paged_attn_decode at the serving geometry, and at a KV > 1 one
    eng = serving.engine
    rng = np.random.default_rng(2)
    for n_kv, n_heads, hd in ((cfg.n_kv_heads, cfg.n_heads, cfg.hd),
                              (4, 28, 128)):
        ps, P = eng.page_size, eng.pages_per_seq
        n_pages = 1 + B * P
        q = normal((B, 1, n_heads, hd))
        kp, vp = (normal((n_pages, n_kv, ps, hd)) for _ in range(2))
        table = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                            .reshape(B, P), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, P * ps + 1, size=B),
                              jnp.int32)
        got = ops.paged_attn_decode(q, kp, vp, table, lengths)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_attn_decode_ref(q, kp, vp, table, lengths)
        _check(f"paged_attn_decode KV={n_kv} H={n_heads} hd={hd} "
               f"page_size={ps} pages={P}", got, want, KERNEL_RTOL)


    # flash_attention, value and gradients: the model's heads past one
    # 512-row block with and without its local window, and the qwen2-7b
    # (also under "highest") and deepseek-moe-16b training cells' heads
    local = next(ls.window for ls in cfg.pattern if ls.window)
    gemma = (cfg.n_heads, cfg.n_kv_heads, cfg.hd)
    for B, S, (H, KV, hd), window, precision in (
            (1, 1024, gemma, local, "default"),
            (1, 1024, gemma, None, "default"),
            (8, 512, (28, 4, 128), None, "default"),
            (8, 512, (28, 4, 128), None, "highest"),
            (8, 256, (16, 16, 128), None, "default")):
        if not ops.flash_attention_supported(S, S, hd):
            raise AssertionError(f"[kernels] flash_attention does not take "
                                 f"S={S} hd={hd}")
        qkv = (normal((B, S, H, hd)), normal((B, S, KV, hd)),
               normal((B, S, KV, hd)))
        do = normal((B, S, H, hd))
        with jax.default_matmul_precision(precision):
            got = _value_and_vjp(functools.partial(
                ops.flash_attention, window=window), qkv, do)
        with jax.default_matmul_precision("highest"):
            want = _value_and_vjp(functools.partial(
                ref.flash_attention_ref, window=window), qkv, do)
        rtol = KERNEL_F32_RTOL if precision == "highest" else KERNEL_RTOL
        for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
            _check(f"flash_attention {name} B={B} S={S} H={H} KV={KV} "
                   f"hd={hd} window={window} {precision}", g, w, rtol)


def _value_and_vjp(f, args, cotangent) -> tuple:
    out, vjp = jax.vjp(f, *args)
    return (out,) + tuple(vjp(cotangent))


# ---------------------------------------------------------------------------
# the sharded path on four chips
# ---------------------------------------------------------------------------

def phase_sharded(config: DFLConfig) -> None:
    single = Session(config)
    base_losses = run_rounds(single, "one-device")
    rng = np.random.default_rng(3)
    tree = jax.tree.map(
        lambda x: rng.standard_normal(x.shape).astype(np.float32),
        single.lora)
    del single
    clear_build_cache()
    gc.collect()

    cluster = ClusterSession(config)
    mesh = cluster.mesh
    log(f"[four-chip] mesh={dict(mesh.shape)}")
    losses = run_rounds(cluster, "four-chip")
    diffs = [abs(a - b) / abs(b) for a, b in zip(losses, base_losses)]
    log(f"[four-chip] loss rel diff per round={diffs!r} (limit {LOSS_RTOL})")
    if not max(diffs) <= LOSS_RTOL:
        raise AssertionError(f"[four-chip] losses disagree with the "
                             f"one-device run: {losses} vs {base_losses}")

    # the column-sharded gossip mix against the one-device mix (separate
    # jits: a trace made without the mesh must not serve the sharded call)
    W = np.asarray(cluster.topo_schedule.next_w(0), np.float32)

    def mix(w, lo):
        return mixing.mix_tree_planned(w, lo, 1.0, 0.5)

    want = jax.jit(mix)(jnp.asarray(W), jax.tree.map(jnp.asarray, tree))
    w_rep = multihost.replicate(mesh, W)
    lo_sh = jax.tree.map(lambda x: multihost.shard_clients(
        mesh, x, x.shape, axis=x.ndim - 3), tree)
    sharding.set_mesh(mesh)
    try:
        compiled = jax.jit(mix).lower(w_rep, lo_sh).compile()
    finally:
        sharding.clear_mesh()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("[four-chip] the sharded mix holds no kernel")
    got = multihost.to_host(compiled(w_rep, lo_sh), mesh)
    flat = lambda t: np.concatenate([np.ravel(x) for x in jax.tree.leaves(t)])
    err = rel_err(flat(got), flat(want))
    log(f"[four-chip] sharded vs one-device mix rel_err={err!r} "
        f"(limit {MIX_RTOL})")
    if not err <= MIX_RTOL:
        raise AssertionError(f"[four-chip] sharded mix disagrees: {err}")
    log(f"[four-chip] peak_bytes_in_use(device 0)={peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded round on four local "
                         "chips against a one-device run")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} local "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache at {enable_compile_cache()}")

    if args.chips == 4:
        phase_sharded(TRAIN)
    else:
        session = phase_train(TRAIN)
        serving = phase_serve(session)
        phase_kernels(session, serving)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
