"""Paged decode-state pool: block-table slot memory end to end.

The tentpole pins, in dependency order:

1. the Pallas gather-attention kernel is **bit-for-bit** the blocked jnp
   oracle in interpret mode (same page walk, same f32 online softmax with
   ``q.dtype`` rounding barriers);
2. ``PagedPool`` accounting never leaks or double-frees pages — a seeded
   fuzz (and a hypothesis property when available) drives random
   admit→alloc→release lifecycles against the free-list invariants;
3. the paged engine decodes **token-identical** streams to the dense
   ``SlotPool`` engine on every dense-fit workload — host loop and
   device windowed loop — while admitting prompts longer than the dense
   per-slot cache (page-budget admission + parking backpressure);
4. paged migration snapshots (allocated pages only, in block-table order)
   resume bit-identically on the target replica, and injection applies
   the same worst-case page budgeting as admission.

Paging applies to homogeneous full-attention archs only (qwen here);
recurrent/mixed archs must keep the dense pool and refuse ``paged=True``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import split as SP
from repro.core.channel import MobilityChannel
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention
from repro.serving import (ContinuousBatchingEngine, PagedPool, Request,
                           SlotPool, default_orchestrator, extract_session,
                           inject_session)

DENSE_ARCHS = ["recurrentgemma-2b", "xlstm-125m"]


@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen2.5-3b")
    return cfg, SP.init_split_params(jax.random.PRNGKey(0), cfg)


def _prompt(cfg, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)


def _mobility(cross_at, *, n_ticks=64):
    cells = [0] * cross_at + [1] * n_ticks
    return MobilityChannel(cells, [2e6, 2e6], detach_factor=1.0)


# ---------------------------------------------------------------------------
# kernel: interpret-mode bit parity vs the blocked oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,nb,plen,n_kv,g,hd", [
    (1, 2, 8, 1, 2, 16),
    (3, 4, 8, 2, 3, 32),
    (2, 3, 16, 2, 1, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_parity(B, nb, plen, n_kv, g, hd, dtype):
    """pallas interpret vs the blocked jnp oracle, incl. junk in the
    scratch page (id 0) and in rows past each sequence's position:
    bit-for-bit in bf16 (the ``q.dtype`` rounding barriers quantize away
    fusion noise); a few ulp in f32, where the barriers are no-op casts
    and XLA may rematerialize the interpreted body with different FMA
    fusion than the oracle's eager op-by-op execution."""
    nq = n_kv * g
    n_pages = B * nb + 1
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (B, nq, hd)).astype(dtype)
    kp = jax.random.normal(keys[1], (n_pages, n_kv, plen, hd)).astype(dtype)
    vp = jax.random.normal(keys[2], (n_pages, n_kv, plen, hd)).astype(dtype)
    rng = np.random.default_rng(11)
    pos = rng.integers(0, nb * plen, size=B).astype(np.int32)
    bt = np.zeros((B, nb), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for b in range(B):
        for j in range(pos[b] // plen + 1):      # allocated prefix only
            bt[b, j] = free.pop()
    out_k = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos),
                            interpret=True)
    out_r = ref.paged_attention_ref(q, kp, vp, jnp.asarray(bt), pos)
    assert out_k.dtype == dtype
    if dtype == jnp.bfloat16:
        assert (np.asarray(out_k) == np.asarray(out_r)).all()
    else:
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# pool accounting: free-list guards + leak/double-free invariants
# ---------------------------------------------------------------------------

def test_slotpool_release_guards(qwen):
    cfg, _ = qwen
    pool = SlotPool(cfg, 2, 16)
    s = pool.acquire()
    pool.release(s)
    with pytest.raises(ValueError, match=f"double release of slot {s}"):
        pool.release(s)
    with pytest.raises(ValueError, match="out of range"):
        pool.release(7)


def test_pagedpool_release_guards_and_geometry(qwen):
    cfg, _ = qwen
    pool = PagedPool(cfg, 2, 16, page_len=8)
    assert pool.n_pages == 4 and pool.capacity == 32
    s = pool.acquire()
    pool.alloc_pages(s, 9)                     # 2 pages
    assert pool.pages_in_use == 2
    pool.release(s)
    assert pool.pages_in_use == 0
    with pytest.raises(ValueError, match="double release"):
        pool.release(s)
    with pytest.raises(ValueError, match="out of range"):
        pool.release(-1)


def test_pagedpool_exhaustion_raises(qwen):
    cfg, _ = qwen
    pool = PagedPool(cfg, 1, 16, page_len=8)   # 2 pages total
    s = pool.acquire()
    with pytest.raises(RuntimeError):
        pool.alloc_pages(s, pool.capacity + 1)


def _check_invariants(pool):
    used = int(pool.pages_used.sum())
    assert pool.pages_in_use == used
    assert used + len(pool._free_pages) == pool.n_pages
    assert len(set(pool._free_pages)) == len(pool._free_pages)
    seen = set()
    for slot in range(pool.n_slots):
        ids = [int(p) for p in pool.block_np[slot, :pool.pages_used[slot]]]
        assert 0 not in ids                     # scratch page never owned
        assert all(1 <= p <= pool.n_pages for p in ids)
        assert not (seen & set(ids))            # disjoint across slots
        seen |= set(ids)
    assert not (seen & set(pool._free_pages))   # owned ∩ free == ∅
    assert pool.pages_available >= 0


def _fuzz_lifecycle(pool, seed, n_ops=200):
    """Random admit→commit→incremental-alloc→release sequences under the
    engine's admission discipline; every step re-checks the invariants."""
    rng = np.random.default_rng(seed)
    live = {}                                   # slot -> (worst, rows)
    for _ in range(n_ops):
        op = rng.integers(0, 3)
        if op == 0 and pool.n_free:
            rows_total = int(rng.integers(1, pool.capacity + 1))
            worst = -(-rows_total // pool.page_len)
            if worst <= pool.pages_available:   # the admission rule
                slot = pool.acquire()
                pool.commit_pages(slot, worst)
                rows0 = int(rng.integers(1, rows_total + 1))
                pool.alloc_pages(slot, rows0)
                live[slot] = (rows_total, rows0)
        elif op == 1 and live:
            slot = int(rng.choice(list(live)))
            total, rows = live[slot]
            rows = min(rows + int(rng.integers(1, pool.page_len + 1)), total)
            pool.alloc_pages(slot, rows)        # idempotent past total
            live[slot] = (total, rows)
        elif op == 2 and live:
            slot = int(rng.choice(list(live)))
            pool.release(slot)
            del live[slot]
        _check_invariants(pool)
    for slot in list(live):
        pool.release(slot)
    _check_invariants(pool)
    assert pool.pages_in_use == 0
    assert sorted(pool._free_pages) == list(range(1, pool.n_pages + 1))


def test_pagedpool_never_leaks_seeded_fuzz(qwen):
    cfg, _ = qwen
    for seed in range(5):
        _fuzz_lifecycle(PagedPool(cfg, 3, 24, page_len=4), seed)


def test_pagedpool_never_leaks_property(qwen):
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    cfg, _ = qwen

    @given(st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=25, deadline=None)
    def prop(seed):
        _fuzz_lifecycle(PagedPool(cfg, 3, 24, page_len=4), seed, n_ops=60)

    prop()


def test_write_read_rows_round_trip(qwen):
    """``write_rows(read_rows(s), s, pos)`` is a bit-exact identity on the
    paged pool (the migration/admission scatter is the gather's inverse)."""
    cfg, params = qwen
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, cache_len=32,
                                   host_loop=True)
    assert eng.paged
    eng.submit(Request(rid=0, prompt=_prompt(cfg, seed=3), max_new_tokens=6))
    for _ in range(4):
        eng.step()
    pool, slot = eng.pool, 0
    before = jax.tree.map(np.asarray, pool.states)
    rows = pool.read_rows([slot])
    pool.write_rows(rows, [slot], [int(pool.positions[slot])])
    after = jax.tree.map(np.asarray, pool.states)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert (a == b).all()
    eng.close()


# ---------------------------------------------------------------------------
# engine: paged == dense token identity; long prompts; arch gating
# ---------------------------------------------------------------------------

def _run_engine(params, cfg, *, host_loop, paged, n=6):
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, cache_len=32,
                                   orchestrator=default_orchestrator(cfg),
                                   host_loop=host_loop, paged=paged)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=_prompt(cfg, seed=i),
                    max_new_tokens=int(rng.integers(2, 8)),
                    arrival_tick=i // 2) for i in range(n)]
    done = eng.run(reqs)
    st = eng.stats()
    assert eng.pool.n_free == eng.pool.n_slots
    if paged:
        assert eng.pool.pages_in_use == 0
    eng.close()
    return {s.request.rid: s for s in done}, st


def test_paged_token_identity_both_loops(qwen):
    """Paged and dense engines emit identical tokens / modes / accounting
    for every dense-fit request, on the host loop and the device loop."""
    cfg, params = qwen
    base, base_st = _run_engine(params, cfg, host_loop=True, paged=False)
    for host_loop in (True, False):
        cur, st = _run_engine(params, cfg, host_loop=host_loop, paged=True)
        assert st["paged"] is True and base_st["paged"] is False
        assert cur.keys() == base.keys()
        for rid in base:
            for attr in ("tokens", "mode_counts", "wire_bytes",
                         "admitted_tick", "finished_tick"):
                assert getattr(cur[rid], attr) == getattr(base[rid], attr), \
                    (host_loop, rid, attr)
        for k in ("decode_ticks", "wire_bytes", "prefill_calls",
                  "generated_tokens", "deadline_misses"):
            assert st[k] == base_st[k], (host_loop, k)


@pytest.mark.parametrize("host_loop", [True, False])
def test_long_prompt_beyond_dense_cache(qwen, host_loop):
    """Page-budget admission serves a prompt LONGER than the dense per-slot
    cache (the dense engine rejects it), and parks excess long prompts
    until pages free up instead of rejecting them."""
    cfg, params = qwen
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, cache_len=32,
                                   host_loop=host_loop)
    assert eng.max_context == 96                 # 6 pages * 16 rows
    rng = np.random.default_rng(0)
    reqs = [Request(rid=0, prompt=rng.integers(
                1, cfg.vocab_size, 50).astype(np.int32), max_new_tokens=8)]
    reqs += [Request(rid=i, prompt=rng.integers(
                1, cfg.vocab_size, 40).astype(np.int32), max_new_tokens=6)
             for i in (1, 2)]
    done = eng.run(reqs)
    st = eng.stats()
    assert len(done) == 3
    assert all(len(s.tokens) == s.request.max_new_tokens for s in done)
    assert st["requests_over_capacity"] == 0
    assert st["requests_truncated"] == 0
    assert st["requests_parked"] >= 1            # 3 * 57 rows > 96 rows
    assert eng.pool.pages_in_use == 0
    eng.close()

    dense = ContinuousBatchingEngine(params, cfg, n_slots=3, cache_len=32,
                                     paged=False)
    assert len(dense.run([reqs[0]])) == 0
    assert dense.stats()["requests_over_capacity"] == 1
    dense.close()


@pytest.mark.parametrize("arch", DENSE_ARCHS)
def test_recurrent_archs_stay_dense(arch):
    """Paging is a full-attention concept: recurrent / mixed archs keep the
    dense pool by default and refuse ``paged=True`` loudly."""
    cfg = get_reduced(arch)
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, cache_len=16)
    assert not eng.paged and isinstance(eng.pool, SlotPool)
    assert eng.stats()["paged"] is False
    eng.close()
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingEngine(params, cfg, n_slots=2, cache_len=16,
                                 paged=True)


# ---------------------------------------------------------------------------
# migration: pages-only snapshots, bit-exact resume, budgeted injection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("host_loop", [True, False])
def test_paged_migration_bit_identity(qwen, host_loop):
    """A raw paged snapshot (allocated pages only) resumes bit-identically
    on the target — including extraction mid-window on the device loop."""
    cfg, params = qwen

    def _req():
        return Request(rid=0, prompt=_prompt(cfg, seed=2), max_new_tokens=12,
                       channel=_mobility(60))

    base_eng = ContinuousBatchingEngine(
        params, cfg, n_slots=2, cache_len=32,
        orchestrator=default_orchestrator(cfg), host_loop=host_loop)
    base = base_eng.run([_req()])[0].tokens
    base_eng.close()

    src = ContinuousBatchingEngine(
        params, cfg, n_slots=2, cache_len=32,
        orchestrator=default_orchestrator(cfg), host_loop=host_loop,
        max_window=2)
    dst = ContinuousBatchingEngine(
        params, cfg, n_slots=2, cache_len=32,
        orchestrator=default_orchestrator(cfg), host_loop=host_loop)
    src.submit(_req())
    for _ in range(3):
        src.step()
    snap = extract_session(src, rid=0)
    assert snap.paged and snap.page_len == src.pool.page_len
    assert src.pool.pages_in_use == 0            # extraction freed them
    nbu = snap.wire[0][1].shape[1]
    assert nbu * snap.page_len <= 32             # pages-only payload
    assert inject_session(dst, snap)
    mig = dst.run()[0].tokens
    assert dst.pool.pages_in_use == 0
    src.close(), dst.close()
    assert mig == base


def test_paged_inject_budget_refusal(qwen):
    """Injection is admission-equivalent: a free slot is NOT enough — the
    target must also cover the session's worst-case remaining pages, else
    inject returns False (park-and-retry) without touching the pool."""
    cfg, params = qwen
    src = ContinuousBatchingEngine(params, cfg, n_slots=2, cache_len=32,
                                   orchestrator=default_orchestrator(cfg),
                                   host_loop=True)
    dst = ContinuousBatchingEngine(params, cfg, n_slots=2, cache_len=32,
                                   orchestrator=default_orchestrator(cfg),
                                   n_pages=1)    # 16 rows < 4+20-1 worst
    src.submit(Request(rid=0, prompt=_prompt(cfg, seed=2),
                       max_new_tokens=20, channel=_mobility(60)))
    for _ in range(3):
        src.step()
    snap = extract_session(src, rid=0)
    assert not inject_session(dst, snap)
    assert dst.pool.pages_in_use == 0 and dst.pool.n_free == 2
    src.close(), dst.close()


def test_pool_kind_mismatch_raises(qwen):
    """Paged↔dense migration is a config error, not backpressure."""
    cfg, params = qwen
    src = ContinuousBatchingEngine(params, cfg, n_slots=2, cache_len=32,
                                   orchestrator=default_orchestrator(cfg),
                                   host_loop=True)
    dense_dst = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                         cache_len=32, paged=False)
    src.submit(Request(rid=0, prompt=_prompt(cfg, seed=2), max_new_tokens=8,
                       channel=_mobility(60)))
    for _ in range(3):
        src.step()
    snap = extract_session(src, rid=0)
    with pytest.raises(ValueError, match="pool"):
        inject_session(dense_dst, snap)
    src.close(), dense_dst.close()
