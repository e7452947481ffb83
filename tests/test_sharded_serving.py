"""Mesh-sharded serving: dp slot/page pools + mp heads, bit-identical.

The headline pin: a ``ContinuousBatchingEngine`` on a ``('dp','mp')``
serving mesh must produce **token-bit-identical** streams to the
single-device (``mesh=None``) engine — same tokens, same wire bytes,
same per-mode counts, same finished ticks — for the attention family and
one recurrent family, under both the host-driven and device-resident
loops, dense and paged pools. Data-parallel slot sharding carries a hard
bit-exactness guarantee (the boundary runs in a fully-replicated
shard_map region; see ``docs/sharding.md``). Tensor parallelism over
``mp`` reassociates reductions and is pinned to *schedule/accounting*
equality instead — numerically equivalent, not bit-exact.

Migration must be mesh-blind: a snapshot extracted from a sharded engine
is bit-identical to one from an unsharded engine, and a live migration
between two sharded replicas on *disjoint device subsets* resumes the
exact unmigrated stream.

Mesh tests skip unless >= 8 devices are visible — CI runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the flag must be
set before jax import, so it cannot be applied from inside this file).
Validation tests run on any device count.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import split as SP
from repro.core.channel import MobilityChannel
from repro.models import sharding
from repro.models.sharding import serving_mesh
from repro.serving import (ContinuousBatchingEngine, EdgeCluster,
                           PagedPool, Request, SlotPool,
                           default_orchestrator, extract_session)

NEED8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

ARCHS = ["qwen2.5-3b", "recurrentgemma-2b"]


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch in ARCHS:
        cfg = get_reduced(arch)
        out[arch] = (cfg, SP.init_split_params(jax.random.PRNGKey(0), cfg))
    return out


def _reqs(cfg, n=6, gen=12, seed=0, channel=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        (5 + i % 3,)).astype(np.int32),
                    max_new_tokens=gen,
                    channel=channel(i) if channel else None)
            for i in range(n)]


def _run(cfg, params, mesh, *, host_loop=False, paged=None, n=6):
    eng = ContinuousBatchingEngine(
        params, cfg, n_slots=4, cache_len=48,
        orchestrator=default_orchestrator(cfg), host_loop=host_loop,
        mesh=mesh, paged=paged)
    with eng:
        done = eng.run(_reqs(cfg, n=n))
    return {s.request.rid: (tuple(s.tokens), s.wire_bytes,
                            tuple(sorted(s.mode_counts.items())),
                            s.finished_tick) for s in done}


# ---------------------------------------------------------------------------
# sharded-vs-unsharded bit identity
# ---------------------------------------------------------------------------

@NEED8
@pytest.mark.parametrize("host_loop", [True, False],
                         ids=["host", "device"])
@pytest.mark.parametrize("arch", ARCHS)
def test_dp_sharded_stream_bit_identical(arch, host_loop, models):
    """Every dp factor of the slot pool decodes the exact mesh=None
    stream — tokens, wire bytes, mode counts, finished ticks."""
    cfg, params = models[arch]
    base = _run(cfg, params, None, host_loop=host_loop)
    for dp in (2, 4):
        got = _run(cfg, params, serving_mesh(dp, 1), host_loop=host_loop)
        assert got == base, (arch, host_loop, dp)


@NEED8
def test_dp8_full_mesh_bit_identical(models):
    """dp=8: one slot-shard per device (n_slots=4 < dp — the slot axis
    does not divide, the spec is dropped, and the run must STILL be
    bit-identical rather than crash or diverge)."""
    cfg, params = models["qwen2.5-3b"]
    base = _run(cfg, params, None)
    assert _run(cfg, params, serving_mesh(8, 1)) == base


@NEED8
def test_dp_mp_mesh_completes_same_schedule(models):
    """The full ('dp','mp') = (4,2) mesh: tensor parallelism over mp
    reassociates head/FFN reductions, so token bits may legitimately
    differ at greedy-argmax ties (bit-identity is the dp guarantee, not
    the mp one — see docs/sharding.md). What must hold: every request
    completes its full budget on the same tick schedule with identical
    wire-byte and per-mode accounting."""
    cfg, params = models["qwen2.5-3b"]
    base = _run(cfg, params, None)
    got = _run(cfg, params, serving_mesh(4, 2))
    assert set(got) == set(base)
    for rid in base:
        b_tok, b_wire, b_modes, b_tick = base[rid]
        g_tok, g_wire, g_modes, g_tick = got[rid]
        assert len(g_tok) == len(b_tok)
        assert (g_wire, g_modes, g_tick) == (b_wire, b_modes, b_tick)


@NEED8
@pytest.mark.parametrize("dp", [2, 8])
def test_paged_pool_sharded_bit_identical(dp, models):
    """Paged pools: the block-table arena shards over dp (page count
    padded to divide) and streams stay bit-identical to both the
    unsharded paged AND dense engines."""
    cfg, params = models["qwen2.5-3b"]
    dense = _run(cfg, params, None)
    base = _run(cfg, params, None, paged=True)
    assert base == dense
    assert _run(cfg, params, serving_mesh(dp, 1), paged=True) == base


# ---------------------------------------------------------------------------
# migration is mesh-blind
# ---------------------------------------------------------------------------

def _mobility(cross_at, *, n_ticks=64, cap=2e6):
    cells = [0] * cross_at + [1] * n_ticks
    return MobilityChannel(cells, [cap, cap], detach_factor=1.0)


@NEED8
@pytest.mark.parametrize("arch", ARCHS)
def test_sharded_migration_round_trip(arch, models):
    """Live migration between two sharded replicas on DISJOINT device
    subsets decodes exactly what an unsharded single engine decodes."""
    cfg, params = models[arch]

    def reqs():
        rng = np.random.default_rng(3)
        return [Request(rid=0,
                        prompt=rng.integers(1, cfg.vocab_size,
                                            (4,)).astype(np.int32),
                        max_new_tokens=12, channel=_mobility(5))]

    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, cache_len=48,
                                   orchestrator=default_orchestrator(cfg))
    with eng:
        base = {s.request.rid: s for s in eng.run(reqs())}

    cluster = EdgeCluster(params, cfg, n_replicas=2, n_slots=2,
                          cache_len=48, placement="best-channel",
                          handover="migrate", dp=2)
    meshes = [e.mesh for e in cluster.replicas]
    assert all(m is not None for m in meshes)
    # replicas own disjoint device subsets of the same process
    devs = [set(d.id for d in m.devices.flat) for m in meshes]
    assert devs[0].isdisjoint(devs[1])
    got = {s.request.rid: s for s in cluster.run(reqs())}
    st = cluster.stats()
    cluster.close()

    assert st["migrations"] == 1
    assert got[0].tokens == base[0].tokens
    assert got[0].mode_counts == base[0].mode_counts
    assert got[0].wire_bytes == base[0].wire_bytes


@NEED8
def test_snapshot_wire_bits_mesh_invariant(models):
    """``extract_session`` from a sharded engine serializes the exact
    bytes the unsharded engine serializes: the snapshot wire format (and
    therefore resume behavior) is independent of device placement."""
    cfg, params = models["qwen2.5-3b"]

    def engine(mesh):
        # host loop: one tick per step, so the session is deterministically
        # live (and at the same position) when the snapshot is taken
        return ContinuousBatchingEngine(
            params, cfg, n_slots=2, cache_len=48,
            orchestrator=default_orchestrator(cfg), host_loop=True,
            mesh=mesh)

    def snap_after(mesh, n_steps=5):
        eng = engine(mesh)
        with eng:
            rng = np.random.default_rng(9)
            eng.submit(Request(
                rid=0,
                prompt=rng.integers(1, cfg.vocab_size, (4,)).astype(np.int32),
                max_new_tokens=20))
            for _ in range(n_steps):
                eng.step()
            return extract_session(eng, rid=0)

    a = snap_after(None)
    b = snap_after(serving_mesh(4, 1))
    assert a.position == b.position
    np.testing.assert_array_equal(a.cur_token, b.cur_token)
    assert len(a.wire) == len(b.wire)
    for ea, eb in zip(a.wire, b.wire):
        assert ea[0] == eb[0] == "raw"
        np.testing.assert_array_equal(ea[1], eb[1])


# ---------------------------------------------------------------------------
# pool placement + padding mechanics
# ---------------------------------------------------------------------------

@NEED8
def test_pool_states_carry_dp_sharding(models):
    """SlotPool leaves actually land sharded: slot axis -> 'dp' whenever
    it divides, and gathered migration rows stay host-addressable."""
    cfg, _ = models["qwen2.5-3b"]
    mesh = serving_mesh(4, 1)
    pool = SlotPool(cfg, n_slots=4, cache_len=16, mesh=mesh)
    specs = jax.tree.leaves(
        jax.tree.map(lambda a: a.sharding.spec, pool.states))
    assert any(len(s) > 1 and s[1] == "dp"
               for s in specs)                     # slot axis is axis 1
    rows = pool.read_rows([2, 0])
    for leaf in jax.tree.leaves(rows):
        np.asarray(leaf)                           # host-addressable


@NEED8
def test_meshless_replicas_one_device_each(models):
    """Without dp/mp, a host with enough devices gives each replica its own
    device (a 1x1 mesh) instead of stacking every replica on device 0."""
    cfg, params = models["qwen2.5-3b"]
    cluster = EdgeCluster(params, cfg, n_replicas=4, n_slots=2,
                          cache_len=16)
    homes = [{d for leaf in jax.tree.leaves(r.params)
              for d in leaf.devices()} for r in cluster.replicas]
    cluster.close()
    assert all(len(h) == 1 for h in homes)
    assert len(set().union(*homes)) == 4


@NEED8
def test_paged_arena_padded_to_dp(models):
    """The paged arena's natural page count (n_pages+1, usually odd) is
    padded up to a dp-divisible count; the free list never hands out the
    padding pages."""
    cfg, _ = models["qwen2.5-3b"]
    mesh = serving_mesh(8, 1)
    pool = PagedPool(cfg, n_slots=4, cache_len=32, mesh=mesh)
    ref = PagedPool(cfg, n_slots=4, cache_len=32)
    arena_pages = jax.tree.leaves(pool.states)[0].shape[1]
    assert arena_pages % 8 == 0
    assert pool.n_pages == ref.n_pages            # allocatable pages equal
    assert len(pool._free_pages) == len(ref._free_pages)


# ---------------------------------------------------------------------------
# validation (no mesh needed — run on any device count)
# ---------------------------------------------------------------------------

def test_serving_mesh_validates_axes():
    with pytest.raises(ValueError):
        serving_mesh(0, 1)
    with pytest.raises(ValueError):
        serving_mesh(1, -2)


def test_serving_mesh_device_count_error_mentions_flag():
    with pytest.raises(ValueError, match="host_platform_device_count"):
        serving_mesh(4096, 1)


def test_cluster_rejects_oversubscribed_mesh(models):
    cfg, params = models["qwen2.5-3b"]
    with pytest.raises(ValueError, match="device"):
        EdgeCluster(params, cfg, n_replicas=2, n_slots=2, cache_len=32,
                    dp=4096)
