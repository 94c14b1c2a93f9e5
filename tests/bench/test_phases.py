"""bench/phases.py: the compiled DFL round's operations by phase and
scope, and the program's own host spans.

On the CPU: the round of core/fedtrain.py over a tiny Qwen2 is compiled
here and its op names classified, and a tiny `Session` is stepped under
the profiler. On a TPU v5e trace (fixtures/phases.xplane.pb, beside the
program's compiled text fixtures/phases.hlo.txt): `scoped_step`, a
scoped, rematerialised gradient step with one gossip-mix kernel under
"mix", executed twice. Running this file on the chip records both:

    PYTHONPATH=src:. python tests/bench/test_phases.py <out_dir>
"""
import dataclasses
import re
import shutil
import sys
import tempfile
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from bench import phases, trace

FIXTURES = Path(__file__).parent / "fixtures"
STEP = r"^jit_scoped_step(\(|$)"
M, N, D = 8, 64, 1024           # clients, rows, width (two mix stripes)


def scoped_step(w, x, a, mix_w):
    """A loss under "loss" with a checkpointed layer and a "head", its
    gradient, an "opt" update, and the gossip-mix kernel under "mix"."""
    from repro.kernels.gossip_mix import gossip_mix

    def objective(a):
        with jax.named_scope("loss"):
            h = jax.checkpoint(
                lambda a: jnp.tanh((x @ w)[None] + a[:, None, :]))(a)
            with jax.named_scope("head"):
                return jnp.mean(jnp.square(h @ w))

    loss, g = jax.value_and_grad(objective)(a)
    with jax.named_scope("opt"):
        a = a - 0.1 * g
    with jax.named_scope("mix"):
        a = gossip_mix(mix_w, a, interpret=jax.default_backend() != "tpu")
    return a, loss


def _step_args():
    k = jax.random.split(jax.random.key(0), 3)
    return (jax.random.normal(k[0], (D, D)) / D ** 0.5,
            jax.random.normal(k[1], (N, D)),
            jax.random.normal(k[2], (M, D)),
            jnp.full((M, M), 1.0 / M))


def record(out_dir) -> None:
    """Trace two executions of the compiled `scoped_step` and write the
    trace and the compiled text into ``out_dir``."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    args = _step_args()
    compiled = jax.jit(scoped_step).lower(*args).compile()
    jax.block_until_ready(compiled(*args))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0          # device events only: a small file
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        for _ in range(2):
            out = compiled(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        shutil.copy(next(Path(d).rglob("*.xplane.pb")),
                    out_dir / "phases.xplane.pb")
    # source paths relative to the checkout, as in the repository
    root = str(Path(__file__).resolve().parents[2]) + "/"
    (out_dir / "phases.hlo.txt").write_text(
        compiled.as_text().replace(root, ""))


# ---------------------------------------------------------------------------
# op names and their phases
# ---------------------------------------------------------------------------

def test_op_names_read_from_compiled_text():
    text = """
ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(f)/jvp(loss)/head/mul" source_file="x.py" source_line=3}
  ROOT %gossip_mix.1 = f32[8]{0} custom-call(%fusion.3), metadata={op_name="jit(f)/mix/jit(gossip_mix)/pallas_call"}
}
"""
    assert phases.op_names(text) == {
        "p": "",
        "fusion.3": "jit(f)/jvp(loss)/head/mul",
        "gossip_mix.1": "jit(f)/mix/jit(gossip_mix)/pallas_call"}


@pytest.mark.parametrize("op_name,phase,head", [
    ("jit(round_fn)/while/body/closed_call/jvp(loss)/while/body/"
     "closed_call/ffn/dot_general", "fwd", False),
    ("jit(round_fn)/while/body/closed_call/jvp(loss)/head/dot_general",
     "fwd", True),
    ("jit(round_fn)/while/body/closed_call/transpose(jvp(loss))/head/"
     "dot_general", "bwd", True),
    ("jit(round_fn)/while/body/closed_call/transpose(jvp(loss))/while/"
     "body/closed_call/checkpoint/rematted_computation/attn/dot_general",
     "remat", False),
    ("jit(round_fn)/while/body/closed_call/opt/mul", "opt", False),
    ("jit(round_fn)/mix/jit(gossip_mix)/pallas_call", "mix", False),
    ("jit(round_fn)/while/body/add", "unscoped", False),
])
def test_phase_and_head_of_an_op_name(op_name, phase, head):
    assert phases.phase(op_name) == phase
    assert phases.under(op_name, "head") is head


# ---------------------------------------------------------------------------
# the round of the program, compiled on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_round():
    """{instruction: (opcode, op_name, opcodes of the computation a
    fusion calls)} of the compiled round: a two-layer Qwen2 at tiny
    widths, 4 clients, 2 local steps of 1024 tokens (so the head runs its
    checkpointed chunk scan)."""
    from repro.api.rounds import build_round
    from repro.configs import get_config
    from repro.core.lora import build_lora_tree
    from repro.models import transformer as tf
    from repro.optim.adamw import AdamW

    mc = dataclasses.replace(get_config("qwen2-7b"), d_model=64, n_heads=4,
                             n_kv_heads=2, d_ff=128, vocab_size=500,
                             n_layers=2)
    m, ls, S = 4, 2, 1024

    def loss_fn(bp, lo, micro):
        out, per = tf.lm_loss(bp, mc, micro["tokens"], micro["targets"],
                              lora=lo, per_client=True)
        return out[0], per

    opt = AdamW(lr=1e-3)
    key = jax.random.key(0)
    base = jax.eval_shape(lambda k: tf.init_params(k, mc), key)
    lora = jax.eval_shape(lambda k: build_lora_tree(
        k, tf.init_params(k, mc), mc, n_clients=m), key)
    tok = jax.ShapeDtypeStruct((ls, m, 1, S), jnp.int32)
    fn = build_round(loss_fn, opt, local_steps=ls, donate=True)
    text = fn.lower(base, lora, jax.eval_shape(opt.init, lora),
                    {"tokens": tok, "targets": tok},
                    jax.ShapeDtypeStruct((m, m), jnp.float32),
                    jax.ShapeDtypeStruct((4,), jnp.float32)
                    ).compile().as_text()
    names = phases.op_names(text)
    instr = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = [^\n]*?\b([\w-]+)\(")
    opcode, calls, bodies, body = {}, {}, {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            body = bodies.setdefault(head.group(1), set())
        elif i := instr.match(line):
            opcode[i.group(1)] = i.group(2)
            body.add(i.group(2))
            if c := re.search(r"\bcalls=%([^\s,]+)", line):
                calls[i.group(1)] = c.group(1)
    return {k: (opcode[k], v, bodies.get(calls.get(k), set()))
            for k, v in names.items()}


@pytest.mark.parametrize("want", ["fwd", "bwd", "remat", "opt", "mix",
                                  "head"])
def test_round_phases_found_in_compiled_round(tiny_round, want):
    """Each phase of the round, and the head, holds compiled work: a dot
    (the optimizer's work is elementwise)."""
    if want == "head":
        found = {phases.phase(n) for op, n, _ in tiny_round.values()
                 if op == "dot" and phases.under(n, "head")}
        assert {"fwd", "bwd", "remat"} <= found
        return
    ops = {op for op, n, _ in tiny_round.values()
           if phases.phase(n) == want}
    assert ("fusion" if want == "opt" else "dot") in ops


def test_local_step_work_is_scoped(tiny_round):
    """Of the instructions the program traced (those with an op_name; XLA
    adds some without one), no dot is unscoped, and every dot or fusion
    that the local step traced (inside the local-steps scan body) lies
    under one of the program's scopes, but for fusions that only
    materialise a constant (JAX hoists those out of the differentiated
    function, and its scopes with them)."""
    named = {"loss", "opt", "mix", "head", "attn", "ffn"}
    constant = {"parameter", "constant", "broadcast", "iota"}
    traced = [(op, name, body) for op, name, body in tiny_round.values()
              if name]
    assert any(op == "dot" for op, _, _ in traced)
    for op, name, body in traced:
        if op == "dot":
            assert phases.phase(name) != "unscoped", name
        if op in ("dot", "fusion") and \
                name.startswith("jit(round_fn)/while/body/closed_call/") \
                and not (op == "fusion" and body <= constant):
            assert named & set(phases.scopes(name)), name


# ---------------------------------------------------------------------------
# the program's host spans
# ---------------------------------------------------------------------------

def test_program_spans_of_three_session_rounds(tmp_path):
    """A fresh `Session` stepped three times: three ``repro.round`` spans
    with their step numbers and tokens, each holding its phases, and
    ``repro.round.joins`` only in the round where a client joins."""
    import numpy as np

    from repro.api import DFLConfig, Session
    from repro.scenarios.schedule import ColdJoin

    m, ls, S = 4, 2, 8
    ring = np.roll(np.eye(m), 1, axis=1) + np.roll(np.eye(m), -1, axis=1)
    cfg = DFLConfig(model="gemma3-1b", reduced=True, task="lm", n_clients=m,
                    rounds=1, local_steps=ls, batch_size=1, seq_len=S,
                    method="tad", T=2)
    sess = Session(cfg, topology_schedule=ColdJoin(
        ring, p=0.5, seed=0, joiners=1, join_round=1))
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            sess.step()
        jax.block_until_ready(sess.lora)
    spans = phases.program_spans(next(tmp_path.rglob("*.xplane.pb")))
    rounds = [s for s in spans if s.name == "repro.round"]
    assert [r.stats["step_num"] for r in rounds] == [0, 1, 2]
    assert all(r.stats["tokens"] == m * ls * S for r in rounds)
    for r in rounds:
        inside = [s.name for s in spans
                  if s is not r and r.start <= s.start and s.end <= r.end]
        joins = ["repro.round.joins"] if r.stats["step_num"] == 1 else []
        assert inside == joins + [
            "repro.round.batch", "repro.round.topology", "repro.round.put",
            "repro.round.dispatch", "repro.round.observe"]
    assert len(spans) == 3 * 6 + 1


# ---------------------------------------------------------------------------
# the chip's trace joined with the compiled text
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chip():
    red = trace.reduce(FIXTURES / "phases.xplane.pb")
    names = phases.op_names((FIXTURES / "phases.hlo.txt").read_text())
    return red, names


def test_chip_trace_joins_the_compiled_text(chip):
    red, names = chip
    got = phases.round_phases(red, names, program=STEP)
    assert got is not None and got["executions"] == 2
    assert got["coverage"] >= phases.COVERAGE
    assert all(got["seconds"][p] > 0 for p in
               ("fwd", "bwd", "remat", "opt", "mix"))
    assert 0 < got["head"] < got["total"]
    assert sum(got["seconds"].values()) == pytest.approx(
        got["total"] * got["coverage"], rel=1e-9)


def test_chip_gossip_kernel_is_mix(chip):
    red, names = chip
    ops = red.ops_within(red.module_runs(STEP))
    mix = [e for e in ops if e.name.startswith("gossip_mix")]
    assert len(mix) == 2
    assert all(phases.phase(names[e.name]) == "mix" for e in mix)


def test_round_phases_refuse_names_of_another_program(chip):
    """Names that miss the traced operations, or carry none of the
    round's scopes, attribute nothing."""
    red, names = chip
    assert phases.round_phases(red, {}, program=STEP) is None
    unscoped = {k: v.replace("loss", "objective") for k, v in names.items()}
    assert phases.round_phases(red, unscoped, program=STEP) is None
    assert phases.round_phases(red, names) is None     # no jit_round_fn


if __name__ == "__main__":
    record(sys.argv[1] if len(sys.argv) > 1 else FIXTURES)
