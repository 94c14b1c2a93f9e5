"""Paged-KV serving core: page pool / block tables, the paged decode
kernel vs its oracle, paged-vs-contiguous bitwise parity, chunked
prefill, and the admission scheduler (DRR, quotas, preemption)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.serving import AdapterPool, ServingSession
from repro.configs import get_config
from repro.core.lora import build_lora_tree
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attn_decode
from repro.launch.serving import Request, ServeEngine, TenantQuota
from repro.models import transformer as tf
from repro.serving import BlockTables, PagePool, QuotaExceeded, Scheduler

TOLS = {jnp.float32: 2e-4, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# paging primitives
# ---------------------------------------------------------------------------
def test_page_pool_basics():
    pool = PagePool(5)                       # page 0 reserved -> capacity 4
    assert pool.capacity == 4 and pool.n_free == 4 and pool.n_used == 0
    got = [pool.alloc() for _ in range(4)]
    assert 0 not in got and len(set(got)) == 4
    assert pool.alloc() is None              # dry, no exception
    pool.free(got[:2])
    assert pool.n_free == 2
    with pytest.raises(ValueError):
        pool.free([got[0]])                  # double free
    with pytest.raises(ValueError):
        pool.free([0])                       # the null page is never owned


def test_page_pool_free_is_atomic():
    """`free` validates the WHOLE batch before mutating: a raising call
    (bad page mid-sequence, double free, intra-batch duplicate) leaves
    the pool exactly as it was — no stranded half-freed prefix."""
    pool = PagePool(8)
    got = pool.alloc_many(5)
    pool.free(got[:2])
    snap_list, snap_set = list(pool._free), set(pool._free_set)
    for bad_batch in (
        [got[2], got[3], 0],          # valid prefix, then the null page
        [got[2], 99, got[3]],         # out-of-range mid-sequence
        [got[2], got[0], got[3]],     # double free (already in the pool)
        [got[2], got[2]],             # duplicate within the batch
    ):
        with pytest.raises(ValueError):
            pool.free(bad_batch)
        assert pool._free == snap_list, f"pool mutated by {bad_batch}"
        assert pool._free_set == snap_set
    pool.free(got[2:])                # the valid remainder still frees
    assert pool.n_free == pool.capacity
    assert pool._free_set == set(pool._free)


def test_page_pool_free_set_tracks_alloc():
    """The membership set stays consistent through alloc/alloc_many/free
    cycles (it backs the O(1) double-free check)."""
    pool = PagePool(10)
    a = pool.alloc()
    many = pool.alloc_many(3)
    assert a not in pool._free_set
    assert not (set(many) & pool._free_set)
    assert pool._free_set == set(pool._free)
    pool.free([a, *many])
    assert pool._free_set == set(pool._free)
    assert pool.n_free == pool.capacity


def test_page_pool_alloc_many_all_or_nothing():
    pool = PagePool(4)
    assert pool.alloc_many(5) is None and pool.n_free == 3
    got = pool.alloc_many(3)
    assert len(got) == 3 and pool.n_free == 0


def test_block_tables_grow_release():
    pool = PagePool(9)
    tbl = BlockTables(n_slots=2, pages_per_seq=4)
    assert tbl.grow(0, 2, pool)              # pages 0..2 of slot 0
    assert tbl.n_pages(0) == 3 and pool.n_used == 3
    assert tbl.grow(0, 1, pool)              # idempotent, allocates nothing
    assert pool.n_used == 3
    assert (tbl.table[0, :3] > 0).all() and (tbl.table[0, 3:] == 0).all()
    assert (tbl.table[1] == 0).all()         # untouched slot maps to null
    with pytest.raises(ValueError):
        tbl.grow(0, 4, pool)                 # beyond pages_per_seq
    tbl.release(0, pool)
    assert pool.n_used == 0 and (tbl.table[0] == 0).all()


def test_block_tables_grow_all_or_nothing():
    pool = PagePool(3)                       # capacity 2
    tbl = BlockTables(n_slots=1, pages_per_seq=4)
    assert not tbl.grow(0, 3, pool)          # needs 4, pool has 2
    assert pool.n_used == 0 and (tbl.table[0] == 0).all()


# ---------------------------------------------------------------------------
# the kernel vs its oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,KV,G,hd,ps,P", [(2, 1, 4, 64, 8, 4),
                                            (3, 2, 2, 128, 16, 2),
                                            (1, 4, 1, 64, 8, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attn_kernel_vs_ref(B, KV, G, hd, ps, P, dtype, key):
    """Flash-decode paged-attention kernel (interpret mode) vs the gather
    oracle, over shuffled page tables and partial last pages."""
    H = KV * G
    n_pages = 1 + B * P
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), dtype)
    kp = jax.random.normal(ks[1], (n_pages, KV, ps, hd), dtype)
    vp = jax.random.normal(ks[2], (n_pages, KV, ps, hd), dtype)
    rng = np.random.default_rng(B * ps)
    perm = rng.permutation(np.arange(1, n_pages))      # non-trivial mapping
    table = jnp.asarray(perm.reshape(B, P), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, P * ps + 1, size=B), jnp.int32)

    yr = ref.paged_attn_decode_ref(q, kp, vp, table, lengths)
    qg = q.reshape(B, KV, G, hd)
    y = paged_attn_decode(qg, kp, vp, table, lengths,
                          interpret=True).reshape(B, 1, H, hd)
    tol = TOLS[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol * 4)


def test_paged_ref_matches_contiguous_attention(key):
    """The ref oracle IS the contiguous softmax-attention computation on
    the gathered pages — bitwise, not approximately."""
    B, KV, G, hd, ps, P = 2, 2, 3, 32, 4, 3
    H, L = KV * G, ps * P
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    kc = jax.random.normal(ks[1], (B, L, KV, hd))
    vc = jax.random.normal(ks[2], (B, L, KV, hd))
    # lay the contiguous cache into pages via an arbitrary table
    rng = np.random.default_rng(0)
    perm = rng.permutation(np.arange(1, 1 + B * P))
    table = perm.reshape(B, P)
    kp = jnp.zeros((1 + B * P, KV, ps, hd))
    vp = jnp.zeros((1 + B * P, KV, ps, hd))
    for b in range(B):
        for p in range(P):
            kp = kp.at[table[b, p]].set(
                kc[b, p * ps:(p + 1) * ps].swapaxes(0, 1))
            vp = vp.at[table[b, p]].set(
                vc[b, p * ps:(p + 1) * ps].swapaxes(0, 1))
    lengths = jnp.asarray([L, L - ps + 1], jnp.int32)

    y = ref.paged_attn_decode_ref(q, kp, vp, jnp.asarray(table, jnp.int32),
                                  lengths)
    # contiguous reference: same einsums on the flat cache
    import math
    mask = (jnp.arange(L)[None, :] < lengths[:, None])[:, None, None, None, :]
    qg = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bskgh,blkh->bkgsl", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) / math.sqrt(hd)
    s = jnp.where(mask, s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    yc = jnp.einsum("bkgsl,blkh->bskgh", pr, vc.astype(jnp.float32))
    yc = yc.reshape(B, 1, H, hd).astype(q.dtype)
    assert (np.asarray(y) == np.asarray(yc)).all()


# ---------------------------------------------------------------------------
# engine parity: paged == contiguous, chunked == teacher-forced
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    cfg = get_config("gemma3-1b").reduced()
    params = tf.init_params(jax.random.key(0), cfg)
    return cfg, params


def _drain_tokens(eng, prompts, max_new, adapters=None):
    rids = [eng.submit(p, max_new=max_new,
                       adapter=adapters[i % len(adapters)] if adapters
                       else None)
            for i, p in enumerate(prompts)]
    eng.run(max_ticks=5000)
    return [eng.requests[r].tokens_out for r in rids]


def test_paged_decode_step_bitwise_vs_contiguous(served):
    """Teacher-force the same tokens through a contiguous cache and a
    paged cache (shuffle-free table) — logits must be BITWISE equal at
    every step: the oracle reproduces `_attend`'s exact reduction."""
    cfg, params = served
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
    B, L, ps = 2, 32, 8
    cache_c = tf.init_cache(cfg, B, L)
    cache_p = tf.init_cache(cfg, B, L, paging=(1 + B * L // ps, ps))
    # populate the tables: slot b gets pages in allocation order
    pool = PagePool(1 + B * L // ps)
    tables = BlockTables(B, L // ps)
    for b in range(B):
        assert tables.grow(b, L // ps - 1, pool)
    cache_p["pages"]["table"] = jnp.asarray(tables.table)
    for t in toks:
        x = jnp.asarray([[t]] * B, jnp.int32)
        lc, cache_c = tf.decode_step(params, cfg, x, cache_c)
        lp, cache_p = tf.decode_step(params, cfg, x, cache_p)
        assert (np.asarray(lc) == np.asarray(lp)).all()


def test_engine_paged_matches_contiguous(served):
    """Full engine: same requests, paged vs contiguous KV, slot turnover
    included — identical generated tokens, one compile each."""
    cfg, params = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 3, 7, 2, 9)]
    eng_c = ServeEngine(params, cfg, n_slots=2, max_len=64)
    toks_c = _drain_tokens(eng_c, prompts, 6)
    eng_p = ServeEngine(params, cfg, n_slots=2, max_len=64, paged=True,
                        page_size=8)
    toks_p = _drain_tokens(eng_p, prompts, 6)
    assert toks_c == toks_p
    assert eng_c.compile_count == 1 and eng_p.compile_count == 1


@pytest.mark.parametrize("chunk", [4, 32])
def test_chunked_prefill_matches_teacher_forced(served, chunk):
    """Chunked prefill (both paged and rolling layer paths) produces the
    same generated tokens as teacher-forced prefill, including prompts
    shorter than one chunk and the length-1 prompt that skips chunking."""
    cfg, params = served
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (1, 3, 9, 13)]
    eng_tf = ServeEngine(params, cfg, n_slots=2, max_len=64)
    want = _drain_tokens(eng_tf, prompts, 5)

    eng_ck = ServeEngine(params, cfg, n_slots=2, max_len=64,
                         prefill_chunk=chunk)
    assert _drain_tokens(eng_ck, prompts, 5) == want
    eng_pg = ServeEngine(params, cfg, n_slots=2, max_len=64, paged=True,
                         page_size=8, prefill_chunk=chunk)
    assert _drain_tokens(eng_pg, prompts, 5) == want
    # one chunk trace + one decode trace, regardless of prompt lengths
    assert eng_ck.prefill.compile_count == 1
    assert eng_ck.compile_count == 1


def test_preemption_by_eviction_completes_exactly(served):
    """A pool too small for two full streams forces eviction; preempted
    requests recompute on re-admission and still produce the exact
    tokens of an uncontended run."""
    cfg, params = served
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=10).astype(np.int32)
               for _ in range(3)]
    eng_c = ServeEngine(params, cfg, n_slots=2, max_len=32)
    want = _drain_tokens(eng_c, prompts, 10)

    # each request needs 5 pages of 4; capacity 6 < 2*5 -> must preempt
    eng_e = ServeEngine(params, cfg, n_slots=2, max_len=32, paged=True,
                        page_size=4, n_pages=7)
    got = _drain_tokens(eng_e, prompts, 10)
    m = eng_e.metrics()
    assert got == want
    assert m["preemptions"] > 0
    assert eng_e.compile_count == 1
    assert eng_e.page_pool.n_used == 0          # all pages returned


def test_submit_rejects_request_that_can_never_fit(served):
    cfg, params = served
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, paged=True,
                      page_size=4, n_pages=4)    # capacity 3 pages
    with pytest.raises(ValueError):
        eng.submit(np.arange(10, dtype=np.int32), max_new=10)  # needs 5


# ---------------------------------------------------------------------------
# scheduler: DRR fairness, quotas, lifecycle metrics
# ---------------------------------------------------------------------------
def test_drr_single_queue_is_fifo():
    s = Scheduler()
    for i in range(4):
        s.submit(Request(rid=i, prompt=np.zeros(1, np.int32)), tick=i)
    order = [s.next_request({}).rid for _ in range(4)]
    assert order == [0, 1, 2, 3]


def test_drr_alternates_between_adapter_queues():
    """One flooding tenant cannot starve another: admission alternates
    between non-empty queues regardless of queue depth."""
    s = Scheduler()
    for i in range(6):
        s.submit(Request(rid=i, prompt=np.zeros(1, np.int32),
                         adapter="big"), tick=0)
    s.submit(Request(rid=100, prompt=np.zeros(1, np.int32),
                     adapter="small"), tick=0)
    s.submit(Request(rid=101, prompt=np.zeros(1, np.int32),
                     adapter="small"), tick=0)
    picked = [s.next_request({}).adapter for _ in range(4)]
    assert picked == ["big", "small", "big", "small"]


def test_quota_max_queued_rejects_submit():
    s = Scheduler(quotas={"a": TenantQuota(max_queued=2)})
    s.submit(Request(rid=0, prompt=np.zeros(1, np.int32), adapter="a"))
    s.submit(Request(rid=1, prompt=np.zeros(1, np.int32), adapter="a"))
    with pytest.raises(QuotaExceeded):
        s.submit(Request(rid=2, prompt=np.zeros(1, np.int32), adapter="a"))
    assert 2 not in s.requests                  # rejected = never registered
    # other tenants are unaffected
    s.submit(Request(rid=3, prompt=np.zeros(1, np.int32), adapter="b"))


def test_quota_max_active_holds_queue_back():
    s = Scheduler(quotas={"a": TenantQuota(max_active=1)})
    s.submit(Request(rid=0, prompt=np.zeros(1, np.int32), adapter="a"))
    s.submit(Request(rid=1, prompt=np.zeros(1, np.int32), adapter="b"))
    # tenant "a" already holds 1 slot -> its queue is skipped
    assert s.next_request({"a": 1}).adapter == "b"
    assert s.next_request({"a": 1}) is None
    assert s.next_request({"a": 0}).adapter == "a"


def test_engine_enforces_max_active_quota(served):
    cfg, params = served
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=3).astype(np.int32)
               for _ in range(3)]
    stacked = build_lora_tree(jax.random.key(3), params, cfg, n_clients=2)
    pool = AdapterPool.from_stacked(stacked, consensus=False)
    eng = ServeEngine(params, cfg, n_slots=4, max_len=32, adapters=pool,
                      quotas={"client_0": TenantQuota(max_active=1)})
    for p in prompts:
        eng.submit(p, max_new=4, adapter="client_0")
    eng.tick()
    active = [s.req for s in eng.slots if s.req is not None]
    assert len(active) == 1                     # held to 1 despite 4 slots
    eng.run()                                   # but all drain eventually
    assert all(r.done for r in eng.requests.values())


def test_lifecycle_metrics(served):
    cfg, params = served
    eng = ServeEngine(params, cfg, n_slots=1, max_len=32)
    r0 = eng.submit(np.asarray([1, 2, 3], np.int32), max_new=3)
    r1 = eng.submit(np.asarray([4, 5], np.int32), max_new=3)
    eng.run()
    q0, q1 = eng.requests[r0], eng.requests[r1]
    assert q0.queue_wait_ticks == 0
    assert q1.queue_wait_ticks > 0              # waited for the single slot
    # teacher-forced prefill: prompt[0] feeds on the admit tick, so the
    # first generated token lands len(prompt)-1 ticks after submit
    assert q0.ttft_ticks == len(q0.prompt) - 1
    m = eng.metrics()
    assert m["completed"] == 2 and m["queued"] == 0
    assert m["ttft_ticks"]["n"] == 2
    assert m["latency_s"]["p50"] > 0


# ---------------------------------------------------------------------------
# idle-awareness + the one-compile invariant under occupancy churn
# ---------------------------------------------------------------------------
def test_idle_engine_skips_device(served):
    cfg, params = served
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32)
    assert eng.tick() == 0
    eng.run()                                   # returns immediately
    assert eng.device_steps == 0 and eng.compile_count == 0
    eng.submit(np.asarray([1, 2], np.int32), max_new=2)
    eng.run()
    steps = eng.device_steps
    assert steps > 0
    eng.run()                                   # drained -> idle again
    assert eng.device_steps == steps


def test_one_compile_across_adapters_and_occupancy(served):
    """The acceptance invariant: {1,4,8} adapters x varying active-page
    occupancy (staggered lengths, turnover, idle gaps) through ONE traced
    decode step, paged + chunked."""
    cfg, params = served
    stacked = build_lora_tree(jax.random.key(3), params, cfg, n_clients=8)
    c = [0]

    def fill(x):
        c[0] += 1
        return 0.1 * jax.random.normal(jax.random.key(50 + c[0]), x.shape)
    pool = AdapterPool.from_stacked(jax.tree.map(fill, stacked),
                                    consensus=False)
    serving = ServingSession(model_cfg=cfg, params=params, adapters=pool,
                             n_slots=4, max_len=64, paged=True, page_size=8,
                             prefill_chunk=8)
    rng = np.random.default_rng(7)
    names = [f"client_{i}" for i in range(8)]
    for n_adapters in (1, 4, 8):
        for j in range(n_adapters + 2):         # staggered lengths/occupancy
            p = rng.integers(0, cfg.vocab_size,
                             size=2 + 5 * (j % 3)).astype(np.int32)
            serving.submit(p, adapter=names[j % n_adapters],
                           max_new=2 + 3 * (j % 2))
        serving.run()
    assert serving.compile_count == 1
    assert serving.engine.prefill.compile_count == 1
    assert serving.metrics()["completed"] == sum(n + 2 for n in (1, 4, 8))
