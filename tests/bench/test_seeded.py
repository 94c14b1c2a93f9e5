"""The inputs a cell makes from its seed (bench/seeded.py) are the same
whether drawn whole for the program or layer by layer for the
reference, and they are what the program's timed path runs on."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import seeded


def _same(x, y):
    """Equal to the rounding of a multiply fused or not (eager and
    jitted draws differ in the last bit)."""
    np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6,
                               atol=1e-9)


def _tiny(arch, **kw):
    from repro.configs import get_config

    sizes = dict(d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                 vocab_size=500, n_layers=3)
    return dataclasses.replace(get_config(arch), **dict(sizes, **kw))


@pytest.mark.parametrize("arch", ["qwen2-7b", "deepseek-moe-16b"])
def test_weights_drawn_whole_match_layer_by_layer(arch):
    from repro.models.transformer import param_specs

    kw = dict(n_experts=4, top_k=2, n_shared_experts=1, moe_d_ff=32,
              n_kv_heads=4) if arch.startswith("deepseek") else {}
    mc = _tiny(arch, **kw)
    base = seeded.base_params(1234, param_specs(mc))
    # the vocabulary is padded (500 -> 512): the real rows are the ones
    # the reference draws over the real vocabulary
    rows = seeded.vocab_rows(1234, "embed", 500, 64, 0.02)
    _same(base["embed"][:500], rows)
    cols = seeded.vocab_rows(1234, "unembed", 500, 64, 1 / 8.0).T
    _same(base["unembed"][:, :500], cols)
    # a traced uint32 seed (as the reference's jitted step passes it)
    # draws what the python seed draws
    at = jax.jit(lambda s, g: seeded.layer_leaf(s, "groups/0/attn/wq", g,
                                                (64, 64)))
    flat = jax.tree_util.tree_flatten_with_path(base["groups"][0])[0]
    for kp, leaf in flat:
        path = "groups/0/" + "/".join(str(k.key) for k in kp)
        for g in range(3):
            _same(leaf[g], seeded.layer_leaf(1234, path, g, leaf.shape[1:]))
    _same(at(jnp.asarray(1234, jnp.uint32), 2),
          base["groups"][0]["attn"]["wq"][2])
    attn = base["groups"][0]["attn"]
    assert float(jnp.std(attn["wq"])) == pytest.approx(1 / 8.0, rel=0.1)
    assert not np.any(base["groups"][0]["norm1"])
    if "bq" in attn:
        assert float(jnp.std(attn["bq"])) == pytest.approx(0.02, rel=0.3)
    other = seeded.base_params(1235, param_specs(mc))
    assert not np.allclose(other["embed"], base["embed"])


def test_gossip_stream_is_the_seeds_own():
    adj = seeded.ring_adjacency(8)
    a = seeded.GossipSchedule(adj, 0.2, 7)
    b = seeded.GossipSchedule(adj, 0.2, 7)
    ws = [a.next_w(t) for t in range(20)]
    assert all(np.array_equal(w, b.next_w(t)) for t, w in enumerate(ws))
    for w in ws:                    # doubly stochastic, on the ring only
        np.testing.assert_allclose(w.sum(0), 1.0)
        np.testing.assert_allclose(w.sum(1), 1.0)
    assert any(not np.array_equal(w, np.eye(8)) for w in ws)
    T = seeded.switching_interval(adj, 0.2, 7)
    assert 1 <= T <= 64 and T == seeded.switching_interval(adj, 0.2, 7)


def test_the_program_runs_on_the_seeded_inputs(tiny_run):
    run = tiny_run("qwen2-7b", "train.ring8.s512", seed=2 ** 34 + 3,
                   seconds=0.5)
    run.setup()
    sess = run.sess
    want = seeded.base_params(run.seeds["init"], sess.base)
    for x, y in zip(jax.tree.leaves(sess.base), jax.tree.leaves(want)):
        np.testing.assert_array_equal(x, y)
    assert isinstance(sess.topo_schedule, seeded.GossipSchedule)
    assert sess.T == run.T
    assert run.feed.drawn == run.mix["check_rounds"]
    sess.step()
    assert run.feed.drawn == run.mix["check_rounds"] + 1
