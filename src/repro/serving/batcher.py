"""Continuous-batching split-serving: slot pool + mixed-mode decode loop.

The engine keeps a fixed pool of ``n_slots`` decode slots (KV caches /
recurrent states allocated once, recycled as sequences finish). Every engine
tick it:

1. admits pending requests from the bounded queue into free slots — all
   newly admitted prompts prefill in one batched full-sequence forward per
   prompt-length bucket (pad to power-of-two buckets to bound recompiles),
   routed through each request's admission-chosen bottleneck mode, and the
   resulting per-layer states scatter into the slots. Requests whose
   ``prompt_len + max_new_tokens`` cannot fit a full-attention cache are
   truncated or rejected (counted) instead of silently wrapping the rolling
   cache over the prompt;
2. steps each active request's *own* simulated mmWave channel and picks
   that request's bottleneck mode under the configured mode policy —
   ``adaptive`` (a ``ModeController``: vectorized re-selection from the
   link EWMA with dwell-time damping and deadline-aware escalation),
   ``per-tick`` (the orchestrator's scalar loop, the legacy default), or
   ``frozen`` (the admission-chosen mode for the session's whole life, the
   baseline the paper's dynamic claim is measured against) — for every
   tick of the next *decode window* (mode choice depends only on channel
   observations and token counts, never on decoded token values, so whole
   windows are decidable up front); and
3. dispatches the window as ONE jitted ``lax.scan`` of the mixed-mode
   decode step for the whole pool — per-slot positions (sequences are at
   different depths), per-slot mode indices (the bottleneck head is a
   gather over the stacked mode bank, not a Python branch), argmax + token
   feedback + position increments fused on device against donated pool
   buffers — and reads the window's int32 token block back one window
   late, overlapping the host sync and all host bookkeeping with the next
   window's device compute (see ``_step_device``);
4. accounts uplink bytes and simulated transfer latency per request at
   window-decision time and retires finished sessions at dispatch time,
   freeing their slots (token values land at materialization).

Free slots still ride through the decode step (the batch shape is static for
jit); their outputs are ignored and their state is fully overwritten at the
next admission. ``host_loop=True`` preserves the legacy synchronous
per-tick loop (one blocking argmax round-trip per tick) as the measured
baseline and equivalence oracle — ``tests/test_device_loop.py`` pins the
two loops token-identical.

For homogeneous full-attention archs the pool is *paged* by default
(``PagedPool``): KV rows live in fixed ``page_len``-row pages of ONE global
arena per leaf, each slot maps logical row ``t`` to arena page
``block_table[slot, t // page_len]``, and admission is page-budget-based —
a request is admitted when its worst-case page count fits the arena's
uncommitted pages (long prompts are admissible up to the whole arena, far
past the dense per-slot ``cache_len``), pages are allocated on demand tick
by tick, and requests PARK at the queue head under arena pressure instead
of being rejected. ``paged=False`` forces the dense pool (the legacy
capacity semantics); on every shape the dense pool can fit, the decoded
streams are pinned bit-identical between the two (``tests/test_paged.py``).

``mesh`` (a ``models.sharding.serving_mesh`` ``('dp','mp')`` mesh) shards
the whole data plane: pool state rides slot-over-``dp`` / KV-heads-over-
``mp`` (``pool_pspecs``), params ride TP-over-``mp`` (replicated over
``dp``), and the compiled steps — including the donated ``lax.scan``
device window — run under GSPMD with the bottleneck boundary pinned in a
replicated ``shard_map`` region. ``mesh=None`` (the default) is the
single-device engine, byte-for-byte unchanged; a dp-only mesh is pinned
token-bit-identical to it (``tests/test_sharded_serving.py``); ``mp > 1``
reassociates head reductions (numerically equivalent, not bit-exact) —
see ``docs/sharding.md``.
"""
from __future__ import annotations

import concurrent.futures as _cf
import functools
import heapq
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import bottleneck
from repro.core import split as SP
from repro.core.channel import Channel, tx_seconds
from repro.core.orchestrator import Orchestrator
from repro.models import sharding
from repro.models import transformer as T
from repro.serving.controller import ModeController
from repro.serving.session import Request, RequestQueue, Session
from repro.serving.telemetry import Telemetry, now as _now, span


def _slot_axis(cfg: ModelConfig) -> int:
    # homogeneous archs stack per-layer states into [L, B, ...] leaves;
    # heterogeneous archs keep a tuple of per-layer [B, ...] pytrees
    return 1 if cfg.homogeneous else 0


def _put_rows(pool_states, batch_states, idx, axis: int):
    """Scatter rows 0..len(idx)-1 of a batched state pytree into the pool
    rows ``idx`` (distinct by construction) — the one shared scatter every
    admission/inject path builds on."""
    n = idx.shape[0]

    def put(p, b):
        rows = jnp.moveaxis(b, axis, 0)[:n]
        pb = jnp.moveaxis(p, axis, 0).at[idx].set(rows)
        return jnp.moveaxis(pb, 0, axis)

    return jax.tree.map(put, pool_states, batch_states)


@functools.partial(jax.jit, static_argnums=(3,))
def scatter_rows(pool_states, batch_states, idx, axis: int):
    """THE pool row scatter, shared by both pools: dense slots
    (``SlotPool.write_rows``, ``axis = _slot_axis(cfg)``) and arena pages
    (``PagedPool.write_pages``, ``axis = 1`` — a page is just a row of the
    page axis). One jitted dispatch; sharding-aware by construction: on a
    serving mesh the donated/updated pool operand carries its
    ``pool_pspecs`` sharding and GSPMD keeps ``.at[].set`` output sharding
    equal to the operand's, so scatters never unshard the pool."""
    return _put_rows(pool_states, batch_states, idx, axis)


@functools.partial(jax.jit, static_argnums=(2,))
def gather_rows(pool_states, idx, axis: int):
    """The gather inverse of :func:`scatter_rows`, shared the same way
    (``SlotPool.read_rows`` on the slot axis, ``PagedPool.read_pages`` on
    the page axis): pull rows ``idx`` out of the pool as a batched state
    pytree with batch = ``len(idx)`` on ``axis``. Sharded pools gather
    into fully host-addressable outputs — the migration snapshot path
    reads them with plain ``np.asarray`` regardless of mesh."""
    def take(p):
        return jnp.moveaxis(jnp.moveaxis(p, axis, 0)[idx], 0, axis)

    return jax.tree.map(take, pool_states)


@functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(0, 1))
def _admit_scatter(pool_states, positions, cur_tokens, batch_states, slots,
                   pos_vals, axis: int, first_tokens):
    """Device-resident admission: install a prefilled batch's states,
    positions, and first generated tokens into their pool slots in one
    dispatch. The pool state and positions are donated — admission updates
    the resident pool in place instead of copying it. ``cur_tokens`` is
    deliberately NOT donated: the engine's one-tick-lagged sync may still
    hold that buffer for a pending host read (and it is tiny)."""
    n = slots.shape[0]
    new_states = _put_rows(pool_states, batch_states, slots, axis)
    positions = positions.at[slots].set(pos_vals)
    cur_tokens = cur_tokens.at[slots].set(
        first_tokens[:n].reshape((n,) + cur_tokens.shape[1:]))
    return new_states, positions, cur_tokens


@functools.partial(jax.jit, donate_argnums=(0,))
def _admit_meta(positions, cur_tokens, slots, pos_vals, first_tokens):
    """Paged device-resident admission: the prefill already wrote the arena
    through the group's block tables, so only positions and first tokens
    scatter (``cur_tokens`` not donated — same pending-read caveat as
    :func:`_admit_scatter`)."""
    n = slots.shape[0]
    positions = positions.at[slots].set(pos_vals)
    cur_tokens = cur_tokens.at[slots].set(
        first_tokens[:n].reshape((n,) + cur_tokens.shape[1:]))
    return positions, cur_tokens


def _bucket_len(n: int, lo: int = 8) -> int:
    """Pad ``n`` up to the next power-of-two bucket (>= ``lo``) so the
    jitted prefill sees O(log max_prompt) distinct shapes, not one per
    prompt length."""
    b = lo
    while b < n:
        b <<= 1
    return b


def _group_by_bucket(admits):
    """Group (req, slot, mode) admissions by prompt-length bucket."""
    groups: Dict[int, list] = {}
    for a in admits:
        groups.setdefault(_bucket_len(a[0].prompt_len), []).append(a)
    return groups


class _EngineSteps:
    """The jitted step/prefill callables one engine configuration needs."""

    def __init__(self, mono_step, mono_step_dev, mono_prefill,
                 mixed_step=None, mixed_step_dev=None, mixed_prefill=None):
        self.mono_step = mono_step
        self.mono_step_dev = mono_step_dev
        self.mono_prefill = mono_prefill
        self.mixed_step = mixed_step
        self.mixed_step_dev = mixed_step_dev
        self.mixed_prefill = mixed_prefill


def _window_scan_body(cfg: ModelConfig, mesh, *, mixed: bool,
                      fused_tail: bool):
    """The ONE place the device-resident decode window's scan body is
    defined — shared by the dense and paged step builders (``bt=None``
    selects dense) and by the plain and mixed variants.

    A [K, B] mode matrix drives K whole ticks in one ``lax.scan``: token
    feedback, position increments and per-tick mode gathers all stay on
    device. With ``fused_tail`` (the default) each tick asks the model step
    for tokens directly (``return_tokens=True`` ->
    ``ops.decode_tail_op``), so a tick lowers to the boundary kernel plus
    ONE fused norm/head/argmax tail kernel with the token fed straight back
    into the next tick's embed — no separate head/argmax/feedback HLOs and
    no [B, V] f32 logits in HBM. ``fused_tail=False`` keeps the legacy
    logits+argmax body: the equivalence oracle ``tests/test_device_loop.py``
    pins token streams against.

    A model with experts also returns, after the [K, B] token block, the
    served MoE layer's counts of each tick, summed over the layers: [K, B]
    rows each slot's token routed to held experts, and [K] held experts
    that got a row. They come back with the token block, in the same
    materialization."""
    stats = cfg.is_moe

    def run(params, stacked, tok, states, positions, modes_k, bt):
        def body(carry, modes):
            tok, states, positions = carry
            if mixed:
                out, new_states, *st = SP.split_decode_step_mixed(
                    params, stacked, tok, states, positions, cfg, modes,
                    block_table=bt, mesh=mesh, return_tokens=fused_tail,
                    with_stats=stats)
            else:
                out, new_states, *st = T.decode_step(
                    params, tok, states, positions, cfg, block_table=bt,
                    return_tokens=fused_tail, with_stats=stats)
            nxt = out if fused_tail else jnp.argmax(out, axis=-1)
            nxt = nxt.astype(jnp.int32).reshape(tok.shape)
            ys = (nxt, st[0]["rows"].reshape(-1), st[0]["touched"]) \
                if stats else (nxt,)
            return (nxt, new_states, positions + 1), ys

        carry, ys = jax.lax.scan(body, (tok, states, positions), modes_k)
        return (*carry, *ys)

    return run


def _paged_steps(cfg: ModelConfig, mixed: bool, mesh=None,
                 fused_tail: bool = True) -> _EngineSteps:
    """Paged variants of the engine closures: every decode step threads the
    ``[B, nb]`` block table through to the paged attention path, and
    prefill writes straight into the (donated) page arena through the
    group's block tables instead of materializing dense per-row caches.
    The closures are shape-polymorphic in the table width (pow2-bucketed by
    the pool), so one set serves every arena size. ``mesh`` builds the
    sharded variants (see :func:`_compiled_steps`)."""
    run_mono = _window_scan_body(cfg, mesh, mixed=False,
                                 fused_tail=fused_tail)

    @jax.jit
    def mono_step(params, tok, states, pos, bt):
        return T.decode_step(params, tok, states, pos, cfg, block_table=bt)

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def mono_step_dev(params, tok, states, positions, modes_k, bt):
        return run_mono(params, None, tok, states, positions, modes_k, bt)

    @functools.partial(jax.jit, donate_argnums=(3,))
    def mono_prefill(params, toks, lengths, arena, bt):
        logits, new_arena = T.prefill(params, toks, cfg, arena,
                                      lengths=lengths, block_table=bt)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_arena

    if not mixed:
        return _EngineSteps(mono_step, mono_step_dev, mono_prefill)

    run_mixed = _window_scan_body(cfg, mesh, mixed=True,
                                  fused_tail=fused_tail)

    @jax.jit
    def mixed_step(params, stacked, tok, states, positions, modes, bt):
        return SP.split_decode_step_mixed(params, stacked, tok, states,
                                          positions, cfg, modes,
                                          block_table=bt, mesh=mesh)

    @functools.partial(jax.jit, donate_argnums=(3, 4))
    def mixed_step_dev(params, stacked, tok, states, positions, modes_k,
                       bt):
        return run_mixed(params, stacked, tok, states, positions, modes_k,
                         bt)

    @functools.partial(jax.jit, donate_argnums=(4,))
    def mixed_prefill(params, stacked, toks, lengths, arena, modes, bt):
        logits, new_arena = SP.split_prefill_mixed(
            params, stacked, toks, arena, cfg, modes, lengths=lengths,
            block_table=bt, mesh=mesh)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_arena

    return _EngineSteps(mono_step, mono_step_dev, mono_prefill,
                        mixed_step, mixed_step_dev, mixed_prefill)


@functools.lru_cache(maxsize=None)
def _compiled_steps(cfg: ModelConfig, cache_len: int, mixed: bool,
                    paged: bool = False, mesh=None,
                    fused_tail: bool = True) -> _EngineSteps:
    """Build (once per ``(cfg, cache_len)``) the jitted decode/prefill
    closures every ``ContinuousBatchingEngine`` runs on. Cached at module
    level so N engines of the same configuration — a cluster's replicas,
    an A/B benchmark's paired engines — share ONE set of function objects
    and therefore ONE XLA compile cache, instead of re-tracing per engine.
    The closures are pure functions of their arguments (params ride in as
    an argument), so sharing them across engines is sound; donation is a
    per-call property and composes with sharing.

    ``mesh`` (hashable, part of the cache key: mesh shape AND device
    assignment, since the ``shard_map`` boundary region binds concrete
    devices) builds the mesh-aware variants: the mixed steps thread the
    mesh into ``split_decode_step_mixed`` / ``split_prefill_mixed``, and
    sharding of the donated scan carries follows the ``NamedSharding``-
    annotated inputs the engine places (GSPMD propagates input shardings
    through the whole step, donation included). Engines on the SAME mesh —
    e.g. benchmark A/B pairs — still share one compile cache; cluster
    replicas on disjoint device subsets get one entry each.

    ``fused_tail`` (part of the cache key) selects the fused decode-tail
    window body — see :func:`_window_scan_body`; ``False`` builds the
    legacy logits+argmax loop the device-loop equivalence tests run."""
    if paged:
        return _paged_steps(cfg, mixed, mesh, fused_tail)

    run_mono = _window_scan_body(cfg, mesh, mixed=False,
                                 fused_tail=fused_tail)

    @jax.jit
    def mono_step(params, tok, states, pos):
        return T.decode_step(params, tok, states, pos, cfg)

    # device-resident decode window: a [K, B] mode matrix drives K
    # whole ticks in ONE jitted lax.scan — argmax + token feedback +
    # position increments all on device, slot-pool state and positions
    # donated so XLA updates the resident pool in place instead of
    # copying the whole KV/recurrent pool every tick. Mode choice and
    # budget-based retirement depend only on channels and counts (never
    # on token values), so the host precomputes the window and reads
    # the [K, B] token block back one window late. Free slots ride
    # along (their positions drift, but admission rewrites them).
    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def mono_step_dev(params, tok, states, positions, modes_k):
        return run_mono(params, None, tok, states, positions, modes_k, None)

    @jax.jit
    def mono_prefill(params, toks, lengths):
        # fresh zero states materialize inside the jit (shapes are
        # static per bucket) — no per-admission host allocation; the
        # argmax rides inside the jit so only int32 tokens cross the
        # host boundary
        states = T.init_decode_state(cfg, toks.shape[0], cache_len)
        logits, new_states = T.prefill(params, toks, cfg, states,
                                       lengths=lengths)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_states

    if not mixed:
        return _EngineSteps(mono_step, mono_step_dev, mono_prefill)

    @jax.jit
    def mixed_step(params, stacked, tok, states, positions, modes):
        return SP.split_decode_step_mixed(params, stacked, tok,
                                          states, positions, cfg, modes,
                                          mesh=mesh)

    run_mixed = _window_scan_body(cfg, mesh, mixed=True,
                                  fused_tail=fused_tail)

    @functools.partial(jax.jit, donate_argnums=(3, 4))
    def mixed_step_dev(params, stacked, tok, states, positions, modes_k):
        return run_mixed(params, stacked, tok, states, positions, modes_k,
                         None)

    @jax.jit
    def mixed_prefill(params, stacked, toks, lengths, modes):
        states = T.init_decode_state(cfg, toks.shape[0], cache_len)
        logits, new_states = SP.split_prefill_mixed(
            params, stacked, toks, states, cfg, modes,
            lengths=lengths, mesh=mesh)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_states

    return _EngineSteps(mono_step, mono_step_dev, mono_prefill,
                        mixed_step, mixed_step_dev, mixed_prefill)


class SlotPool:
    """Fixed pool of decode slots with recycled cache/recurrent state.

    ``mesh``: serving ``('dp','mp')`` mesh — the state tree is placed with
    ``sharding.pool_pspecs`` (slot axis over ``dp``, KV head groups over
    ``mp``, non-dividing dims replicated) and every ``read_rows``/
    ``write_rows`` keeps that placement (the shared jitted gather/scatter
    preserves operand sharding)."""

    paged = False

    def __init__(self, cfg: ModelConfig, n_slots: int, cache_len: int, *,
                 mesh=None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.mesh = mesh
        self.states = T.init_decode_state(cfg, n_slots, cache_len)
        if mesh is not None:
            self.states = sharding.shard_pool(self.states, mesh,
                                              slot_axis=_slot_axis(cfg))
        self.positions = np.zeros(n_slots, np.int32)
        self._free = list(range(n_slots - 1, -1, -1))

    @property
    def n_free(self) -> int:
        return len(self._free)

    def acquire(self) -> Optional[int]:
        return self._free.pop() if self._free else None

    def release(self, slot: int):
        if not 0 <= slot < self.n_slots:
            raise ValueError(
                f"slot {slot} out of range [0, {self.n_slots})")
        if slot in self._free:
            raise ValueError(f"double release of slot {slot}")
        self.positions[slot] = 0
        self._free.append(slot)

    def write_rows(self, batch_states, slots, positions):
        """Install rows 0..len(slots)-1 of a freshly prefilled batched state
        into the given slots in one scatter (full overwrite — whatever a
        previous occupant left behind is gone)."""
        self.states = scatter_rows(self.states, batch_states,
                                   jnp.asarray(slots, jnp.int32),
                                   _slot_axis(self.cfg))
        for s, p in zip(slots, positions):
            self.positions[s] = p

    def read_rows(self, slots):
        """The gather inverse of :meth:`write_rows`: extract the given
        slots' decode state (KV cache rows / recurrent carries, attention
        cache contents included) as a batched state pytree with batch =
        ``len(slots)`` on the slot axis — the exact shape ``write_rows``
        accepts, so ``write_rows(read_rows(s), s, pos)`` is an identity and
        a row read here injects bit-exactly into any same-config pool (the
        live-migration snapshot path)."""
        return gather_rows(self.states, jnp.asarray(slots, jnp.int32),
                           _slot_axis(self.cfg))


@functools.partial(jax.jit, static_argnums=(3,))
def _gather_pages(arena, bt, used, plen: int):
    """Gather block-table pages into logical row order: arena leaves
    ``[L, n_pages + 1, n_kv, plen, hd]`` + table ``[n, nb]`` -> dense
    ``[L, n, nb * plen, n_kv, hd]`` blocks. Chunks at or past each row's
    allocation (``used``) are zeroed — they point at the scratch page,
    whose contents are drifting-write junk."""
    nb = bt.shape[1]
    keep = jnp.arange(nb)[None, :] < used[:, None]        # [n, nb]

    def take(a):
        g = a[:, bt]                              # [L, n, nb, n_kv, plen, hd]
        g = jnp.where(keep[None, :, :, None, None, None], g, 0)
        g = g.swapaxes(3, 4)                      # [L, n, nb, plen, n_kv, hd]
        return g.reshape(g.shape[:2] + (nb * plen,) + g.shape[4:])

    return jax.tree.map(take, arena)


@functools.partial(jax.jit, static_argnums=(4,))
def _scatter_pages(arena, rows, bt, used, plen: int):
    """The inverse of :func:`_gather_pages`: scatter dense logical-row
    blocks ``[L, n, nb * plen, n_kv, hd]`` back through the block table;
    chunks past a row's allocation get an out-of-bounds page index and
    drop."""
    nb = bt.shape[1]
    keep = jnp.arange(nb)[None, :] < used[:, None]        # [n, nb]

    def put(a, r):
        rc = r.reshape(r.shape[:2] + (nb, plen) + r.shape[3:]).swapaxes(3, 4)
        pg = jnp.where(keep, bt, a.shape[1])
        return a.at[:, pg].set(rc, mode="drop")

    return jax.tree.map(put, arena, rows)


class PagedPool:
    """Paged decode-state pool: one global page arena per KV leaf, per-slot
    block tables, and a page free list.

    The arena holds ``n_pages + 1`` pages of ``page_len`` rows per leaf
    (``[L, n_pages + 1, n_kv, page_len, hd]``, ``T.init_paged_state``);
    page 0 is the reserved
    scratch page — free slots carry all-zero block-table rows, so their
    drifting decode writes land there and are never read unmasked. Real
    pages are 1..n_pages. A slot's logical row ``t`` (== absolute position
    ``t``; full attention never wraps) lives at
    ``arena[block_np[slot, t // page_len], :, t % page_len]``.

    Admission-side accounting: ``commit_pages`` reserves a session's
    worst-case page count up front and ``pages_available`` subtracts every
    resident session's still-undrawn reservation from the free list, so the
    engine only admits what on-demand ``alloc_pages`` growth can always
    satisfy — backpressure parks requests in the queue instead of
    deadlocking mid-decode.

    ``mesh``: serving mesh — the arena shards its PAGE axis over ``dp``
    (pages are this pool's slot axis) and KV head groups over ``mp``. The
    arena allocation is padded up to a ``dp``-divisible page count (extra
    pages never enter the free list, so capacity semantics are unchanged)
    because the natural ``n_pages + 1`` (scratch page 0 included) is
    usually odd and would silently fall back to a replicated arena.
    """

    paged = True

    def __init__(self, cfg: ModelConfig, n_slots: int, cache_len: int, *,
                 page_len: int = 16, n_pages: Optional[int] = None,
                 mesh=None):
        if not (T.full_attention_arch(cfg) and cfg.homogeneous):
            raise ValueError(
                "paged pools need a homogeneous full-attention arch — "
                "windowed/recurrent decode state is bounded by construction "
                "and keeps the dense SlotPool")
        self.cfg = cfg
        self.n_slots = n_slots
        self.cache_len = cache_len           # dense-equivalent per-slot rows
        self.page_len = page_len
        self.mesh = mesh
        per_slot = -(-cache_len // page_len)
        self.n_pages = n_pages if n_pages is not None else n_slots * per_slot
        #: arena rows — ONE session's max context (it may claim every page)
        self.capacity = self.n_pages * page_len
        n_arena = self.n_pages + 1
        if mesh is not None:
            dp = mesh.shape["dp"]
            n_arena = -(-n_arena // dp) * dp
        self.states = T.init_paged_state(cfg, n_arena, page_len)
        if mesh is not None:
            self.states = sharding.shard_pool(self.states, mesh, slot_axis=1,
                                              paged=True)
        self.positions = np.zeros(n_slots, np.int32)
        self._free = list(range(n_slots - 1, -1, -1))
        self.block_np = np.zeros((n_slots, self.n_pages), np.int32)
        self.pages_used = np.zeros(n_slots, np.int32)
        self._committed = np.zeros(n_slots, np.int32)
        self._free_pages = list(range(self.n_pages, 0, -1))  # pop -> 1, 2, ..
        self._free_page_set = set(self._free_pages)
        self.peak_pages_in_use = 0

    # -- slot lifecycle (the SlotPool contract) -------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    def acquire(self) -> Optional[int]:
        return self._free.pop() if self._free else None

    def release(self, slot: int):
        if not 0 <= slot < self.n_slots:
            raise ValueError(
                f"slot {slot} out of range [0, {self.n_slots})")
        if slot in self._free:
            raise ValueError(f"double release of slot {slot}")
        for i in range(int(self.pages_used[slot])):
            self._push_free_page(int(self.block_np[slot, i]))
        self.block_np[slot, :] = 0
        self.pages_used[slot] = 0
        self._committed[slot] = 0
        self.positions[slot] = 0
        self._free.append(slot)

    # -- page accounting ------------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free_pages)

    @property
    def pages_available(self) -> int:
        """Pages a NEW admission may claim: the free list minus pages
        already promised (committed) to resident sessions but not drawn."""
        reserved = int(self._committed.sum()) - int(self.pages_used.sum())
        return len(self._free_pages) - reserved

    def _push_free_page(self, page: int):
        if not 1 <= page <= self.n_pages:
            raise ValueError(
                f"page {page} out of range [1, {self.n_pages}]")
        if page in self._free_page_set:
            raise ValueError(f"double free of page {page}")
        self._free_pages.append(page)
        self._free_page_set.add(page)

    def commit_pages(self, slot: int, n_total: int):
        """Reserve a session's worst-case page count (the engine admits only
        when :attr:`pages_available` covers it), so later on-demand
        :meth:`alloc_pages` growth can never exhaust the arena mid-decode."""
        self._committed[slot] = max(int(n_total), int(self.pages_used[slot]))

    def alloc_pages(self, slot: int, n_rows: int):
        """Ensure pages covering logical rows ``0..n_rows-1`` are allocated
        to the slot (idempotent; growth draws from the free list)."""
        need = -(-max(int(n_rows), 1) // self.page_len)
        have = int(self.pages_used[slot])
        if need <= have:
            return
        if need - have > len(self._free_pages):
            raise RuntimeError(
                f"page arena exhausted: slot {slot} needs {need - have} more "
                f"pages, {len(self._free_pages)} free (admission commitment "
                f"accounting should have prevented this)")
        for i in range(have, need):
            page = self._free_pages.pop()
            self._free_page_set.discard(page)
            self.block_np[slot, i] = page
        self.pages_used[slot] = need
        self._committed[slot] = max(int(self._committed[slot]), need)
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)

    # -- block tables ---------------------------------------------------------
    def table_width(self) -> int:
        """Pow2 bucket (>= 1, <= n_pages) covering every slot's allocated
        pages — the block-table width the compiled steps see, so the decode
        gather cost tracks the longest LIVE sequence, not the whole arena,
        and the jit sees O(log n_pages) distinct widths."""
        hi = max(int(self.pages_used.max()), 1)
        b = 1
        while b < hi:
            b <<= 1
        return min(b, self.n_pages)

    def block_table(self):
        """Device copy of the pool block table at the current bucketed width
        (a fresh buffer per call — never donated; the host-side ``block_np``
        stays authoritative). On a mesh the slot axis rides ``dp`` like
        every other per-slot decode input."""
        return sharding.shard_batch(
            jnp.asarray(self.block_np[:, :self.table_width()]), self.mesh)

    # -- row/page I/O ---------------------------------------------------------
    def write_rows(self, batch_states, slots, positions):
        """Block-table-aware scatter: install dense logical-row blocks
        ``[L, n, R, ...]`` into each slot's pages, allocating on demand for
        the given positions — ``write_rows(read_rows(s), s, pos)`` is
        bit-exact over every allocated page."""
        R = jax.tree.leaves(batch_states)[0].shape[2]
        nb = R // self.page_len
        for s, p in zip(slots, positions):
            if -(-max(int(p), 1) // self.page_len) > nb:
                raise ValueError(
                    f"{R} rows cannot cover position {p} at page_len "
                    f"{self.page_len}")
            self.alloc_pages(s, max(int(p), 1))
            self.positions[s] = int(p)
        sl = np.asarray(slots, np.int64)
        self.states = _scatter_pages(
            self.states, batch_states,
            jnp.asarray(self.block_np[sl][:, :nb], jnp.int32),
            jnp.asarray(np.minimum(self.pages_used[sl], nb), jnp.int32),
            self.page_len)

    def read_rows(self, slots):
        """The gather inverse of :meth:`write_rows`: each slot's logical
        rows in order, ``[L, n, table_width() * page_len, ...]`` per leaf,
        with unallocated chunks zeroed."""
        sl = np.asarray(slots, np.int64)
        nb = self.table_width()
        return _gather_pages(
            self.states, jnp.asarray(self.block_np[sl][:, :nb], jnp.int32),
            jnp.asarray(self.pages_used[sl], jnp.int32), self.page_len)

    def read_pages(self, slot: int):
        """A slot's ALLOCATED pages in block-table order — ``[L, nbu, n_kv,
        plen, hd]`` per leaf, the migration payload (pages only, no dense
        expansion, no scratch junk)."""
        nbu = max(int(self.pages_used[slot]), 1)
        bt = jnp.asarray(self.block_np[slot, :nbu], jnp.int32)
        return gather_rows(self.states, bt, 1)

    def write_pages(self, slot: int, blocks, position: int):
        """Install a migrated-in session's page block (the exact
        :meth:`read_pages` layout) into freshly allocated local pages."""
        nbu = jax.tree.leaves(blocks)[0].shape[1]
        self.alloc_pages(slot, nbu * self.page_len)
        bt = jnp.asarray(self.block_np[slot, :nbu], jnp.int32)
        self.states = scatter_rows(self.states, blocks, bt, 1)
        self.positions[slot] = int(position)


class ContinuousBatchingEngine:
    """Split-inference engine with per-request dynamic bottleneck modes.

    ``orchestrator`` is shared (mode calibration is global) but tracks one
    link state per request id; ``default_channel`` serves requests that
    arrive without their own ``Channel``.
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int = 8,
                 cache_len: int = 128,
                 orchestrator: Optional[Orchestrator] = None,
                 controller: Optional[ModeController] = None,
                 freeze_modes: bool = False,
                 default_channel: Optional[Channel] = None,
                 max_pending: int = 64,
                 host_loop: bool = False,
                 max_window: int = 16,
                 paged: Optional[bool] = None,
                 page_len: int = 16,
                 n_pages: Optional[int] = None,
                 mesh=None,
                 fused_tail: bool = True,
                 telemetry: Optional[Telemetry] = None):
        if controller is not None:
            if freeze_modes:
                raise ValueError("controller and freeze_modes are mutually "
                                 "exclusive mode policies")
            if orchestrator is not None and orchestrator is not controller.orch:
                raise ValueError("pass either the controller (which owns its "
                                 "orchestrator) or an orchestrator, not both")
            orchestrator = controller.orch
        # mesh placement first: params ride TP-over-mp (replicated over
        # dp), so every jitted step below sees committed inputs
        self.mesh = mesh
        self.params = sharding.shard_params(params, mesh)
        self.cfg = cfg
        self.orch = orchestrator
        self.controller = controller
        self.freeze_modes = freeze_modes
        self.default_channel = default_channel
        # homogeneous full-attention archs page their KV by default (paged
        # admission lifts the per-slot cache_len cap to the whole arena);
        # windowed / recurrent archs keep the dense pool — their decode
        # state is bounded by construction and has nothing to page
        paged_ok = T.full_attention_arch(cfg) and cfg.homogeneous
        self.paged = paged_ok if paged is None else bool(paged)
        if self.paged and not paged_ok:
            raise ValueError(
                "paged=True needs a homogeneous full-attention arch; "
                "windowed/recurrent decode state is bounded by construction")
        self.pool = (PagedPool(cfg, n_slots, cache_len, page_len=page_len,
                               n_pages=n_pages, mesh=mesh)
                     if self.paged
                     else SlotPool(cfg, n_slots, cache_len, mesh=mesh))
        self.queue = RequestQueue(max_pending)
        self.active: Dict[int, Session] = {}          # slot -> session
        self.finished: List[Session] = []
        self.tick = 0
        self.mode_mix_ticks = 0       # decode ticks with >= 2 distinct modes
        self.decode_ticks = 0
        self.decoded_slot_ticks = 0   # sum over decode ticks of live slots:
        #                               tokens decoded ON this engine (a
        #                               migrated-in session's earlier tokens
        #                               were decoded elsewhere)
        self.prefill_calls = 0        # jitted batched-prefill dispatches
        self.prefill_tokens = 0       # true prompt tokens prefilled
        self.prefill_padded_tokens = 0  # incl. bucket/batch padding
        self.requests_over_capacity = 0  # rejected: prompt can't fit cache
        self.requests_truncated = 0   # max_new_tokens clipped to cache
        self.requests_parked = 0      # deferred at least once: arena pressure
        self._parked_rids: set = set()
        # full-attention archs must fit prompt + generation in the cache —
        # the whole page arena when paged (one session may claim every
        # page), the per-slot cache_len when dense; windowed/recurrent
        # archs are bounded-state by construction
        self.max_context: Optional[int] = (
            self.pool.capacity if self.paged
            else cache_len if T.full_attention_arch(cfg) else None)
        bank = params.get("bneck_modes") or ()
        self.stacked_bank = (bottleneck.bank_stack(bank, cfg.split)
                             if len(bank) else None)
        if self.stacked_bank is not None:
            # the boundary's shard_map region consumes the bank fully
            # replicated (every shard runs the full-batch boundary)
            self.stacked_bank = sharding.replicate(self.stacked_bank, mesh)
        if controller is not None and self.stacked_bank is None:
            raise ValueError("adaptive mode control needs a bottleneck mode "
                             "bank in params (init_split_params)")
        self._tok_shape = ((n_slots, cfg.n_codebooks, 1)
                           if cfg.frontend == "audio" and cfg.n_codebooks > 1
                           else (n_slots, 1))
        # fused_tail: window ticks end in the fused norm/head/argmax tail
        # kernel (see _window_scan_body); False keeps the legacy
        # logits+argmax window — the token-identity oracle in tests
        self.fused_tail = bool(fused_tail)
        # telemetry is OPTIONAL and host-only: a Telemetry object records
        # the phase spans and the guarded host-side observations below;
        # the engine runs the same compiled programs with or without it
        self._tel = telemetry
        steps = _compiled_steps(cfg, cache_len,
                                self.stacked_bank is not None, self.paged,
                                mesh, self.fused_tail)
        self.host_loop = host_loop
        self.max_window = max(int(max_window), 1)
        if not host_loop:
            # the device loop donates the pool state pytree; freshly
            # initialized states may alias one zeros buffer across several
            # leaves (XLA rejects donating the same buffer twice), so force
            # each leaf onto its own buffer once, up front
            self.pool.states = jax.tree.map(lambda a: a.copy(),
                                            self.pool.states)
        # device loop: tokens and positions are device-resident; the host
        # only ever receives small int32 token arrays, one tick late
        self.cur_tokens = (np.zeros(self._tok_shape, np.int32) if host_loop
                           else sharding.shard_batch(
                               jnp.zeros(self._tok_shape, jnp.int32), mesh))
        self._positions = sharding.shard_batch(
            jnp.zeros(n_slots, jnp.int32), mesh)
        #: (snapshot of (slot, session) pairs, step future) for the most
        #: recently dispatched tick — materialized one tick later so the
        #: host<->device sync overlaps the NEXT tick's device compute
        self._inflight: Optional[tuple] = None
        #: future of the last dispatched device step; while it is pending,
        #: ``pool.states`` / ``cur_tokens`` / ``_positions`` are stale (and
        #: possibly donated) — ``_sync_device_state`` re-homes them
        self._future: Optional[_cf.Future] = None
        #: per-ENGINE pipeline worker (lazily created): jitted decode steps
        #: execute here so the XLA call (which releases the GIL) overlaps
        #: the main thread's per-tick orchestrator / controller / channel
        #: bookkeeping. A single worker keeps execution strictly FIFO —
        #: step t+1's closure reads step t's future, so device-side
        #: ordering (and therefore every decoded token) is deterministic.
        #: Per-engine (not module-global) so N cluster replicas pipeline
        #: their device loops CONCURRENTLY instead of serializing through
        #: one shared FIFO thread — and so one engine's donated-buffer
        #: lifetime can never interleave with another's. ``close()`` (or
        #: the context manager) shuts it down.
        self._exec: Optional[_cf.ThreadPoolExecutor] = None
        self._mode_pb: Dict[int, int] = {}   # per-mode wire bytes memo
        #: not-yet-"arrived" requests as a min-heap on (arrival_tick, seq):
        #: a fleet-scale load script submits thousands of future arrivals
        #: up front, so the per-tick due-scan and the idle-skip peek must
        #: be O(log n)/O(1), not O(n) list scans
        self._pending: List[Tuple[int, int, Request]] = []
        self._pending_seq = 0                         # FIFO tiebreak

        self._mono_step = steps.mono_step
        self._mono_step_dev = steps.mono_step_dev
        self._mono_prefill = steps.mono_prefill
        self._mixed_step = steps.mixed_step
        self._mixed_step_dev = steps.mixed_step_dev
        self._mixed_prefill = steps.mixed_prefill

        if self._tel is not None and self.controller is not None:
            tel = self._tel
            self.controller.on_escalate = (
                lambda rid, tick, frm, to: (
                    tel.inc("engine.mode_escalations"),
                    tel.instant("mode_escalate", rid=rid, tick=tick,
                                cat="mode", frm=frm, to=to)))

    # -- submission -----------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request for its arrival tick. Returns False if the
        admission queue rejected it (back-pressure)."""
        req.t_submit = _now()
        if req.arrival_tick > self.tick:
            heapq.heappush(self._pending,
                           (req.arrival_tick, self._pending_seq, req))
            self._pending_seq += 1
            return True
        return self.queue.submit(req)

    def _deliver_arrivals(self):
        # heap order == (arrival_tick, submission order): identical to the
        # old sort-by-arrival_tick drain (Python sorts are stable)
        while self._pending and self._pending[0][0] <= self.tick:
            r = heapq.heappop(self._pending)[2]
            r.t_submit = _now()
            self.queue.submit(r)

    # -- admission ------------------------------------------------------------
    def _admit(self):
        """Pop admissible requests into free slots, then prefill every new
        prompt in one batched full-sequence forward per length bucket.

        Loops because a budget-1 session completes inside its own prefill
        (the prefill argmax is its whole generation) and frees its slot for
        the next queued request within the same tick."""
        with span("engine.admit", self._tel):
            while self.pool.n_free and len(self.queue):
                if not self.host_loop:
                    # admission scatters into the resident pool buffers —
                    # the pipeline must land the in-flight step first
                    self._sync_device_state()
                with span("engine.collect_admits", self._tel):
                    admits = self._collect_admits()
                if not admits:        # everything popped was over capacity
                    break
                for blen, group in sorted(_group_by_bucket(admits).items()):
                    self._prefill_group(blen, group)

    def _collect_admits(self) -> List[tuple]:
        admits: List[tuple] = []      # (req, slot, mode, budget, capacity)
        while self.pool.n_free and len(self.queue):
            req = self.queue.peek()
            budget = req.max_new_tokens
            if self.max_context is not None:
                if req.prompt_len > self.max_context:
                    # the prompt alone cannot fit: admitting would wrap the
                    # rolling cache over its own context — reject instead
                    self.queue.pop()
                    self.requests_over_capacity += 1
                    if self._tel is not None:
                        self._tel.instant("reject_over_capacity",
                                          cat="admission", rid=req.rid,
                                          prompt_len=req.prompt_len)
                    continue
                # the first generated token is the prefill argmax (no cache
                # write); decode writes land at prompt_len..prompt_len+b-2,
                # so b <= max_context - prompt_len + 1 never wraps
                fit = self.max_context - req.prompt_len + 1
                budget = min(budget, fit)  # session-level clip; the caller's
                #                            Request is not mutated
            worst = 0
            if self.paged:
                # worst-case footprint: prompt rows + every decode write
                worst = -(-(req.prompt_len + budget - 1)
                          // self.pool.page_len)
                if worst > self.pool.pages_available:
                    # arena backpressure: PARK at the queue head (FIFO)
                    # until retirements free enough pages, instead of
                    # rejecting a request the arena could serve later
                    if req.rid not in self._parked_rids:
                        self._parked_rids.add(req.rid)
                        self.requests_parked += 1
                        if self._tel is not None:
                            self._tel.instant(
                                "park_arena", cat="admission", rid=req.rid,
                                pages_needed=worst,
                                pages_available=self.pool.pages_available)
                    break
            self.queue.pop()
            req.t_admit = _now()
            if budget < req.max_new_tokens:
                self.requests_truncated += 1
            slot = self.pool.acquire()
            if self.paged:
                self.pool.commit_pages(slot, worst)
                self.pool.alloc_pages(slot, req.prompt_len)
            if req.channel is None:
                req.channel = self.default_channel
            mode, cap = 0, None
            if self.orch is not None:
                if self.controller is not None:
                    if req.channel is not None:
                        cap = req.channel.step()
                    mode = self.controller.admit(req.rid, req.requirement,
                                                 cap, self.tick)
                else:
                    self.orch.register(req.rid, req.requirement)
                    if req.channel is not None:
                        cap = req.channel.step()
                        self.orch.observe_capacity(cap, rid=req.rid)
                    if self._mixed_prefill is not None:
                        mode = self.orch.choose_mode(rid=req.rid)
            admits.append((req, slot, mode, budget, cap))
        return admits

    def _launch_prefill(self, blen: int, group: List[tuple]):
        """Dispatch the bucket's jitted prefill: prompts right-padded to
        ``blen``, batch padded to a power of two, each row's boundary
        routed through its admission-chosen mode. Returns the device's
        first tokens and the prefilled states (paged: the updated arena,
        already installed)."""
        n = len(group)
        bp = _bucket_len(n, lo=1)          # pow2 batch: bounded compile set
        audio = (self.cfg.frontend == "audio" and self.cfg.n_codebooks > 1)
        shape = (bp, self.cfg.n_codebooks, blen) if audio else (bp, blen)
        toks = np.zeros(shape, np.int32)
        lens = np.ones(bp, np.int32)       # pad rows: harmless length-1 rows
        modes = np.zeros(bp, np.int32)
        for i, (req, _, mode, _, _) in enumerate(group):
            toks[i, ..., :req.prompt_len] = req.prompt
            lens[i] = req.prompt_len
            modes[i] = mode
        if self.paged:
            # per-row block tables at the bucket's static width (pad rows
            # get all-zero rows: their one valid position lands in the
            # scratch page); the prefill scatters prompt K/V straight into
            # the admit-time-allocated arena pages
            nb_p = max(-(-blen // self.pool.page_len), 1)
            bt_np = np.zeros((bp, nb_p), np.int32)
            for i, (_, slot, _, _, _) in enumerate(group):
                bt_np[i] = self.pool.block_np[slot, :nb_p]
            bt = jnp.asarray(bt_np)
            if self._mixed_prefill is not None:
                first_dev, new_states = self._mixed_prefill(
                    self.params, self.stacked_bank, jnp.asarray(toks),
                    jnp.asarray(lens), self.pool.states,
                    jnp.asarray(modes), bt)
            else:
                first_dev, new_states = self._mono_prefill(
                    self.params, jnp.asarray(toks), jnp.asarray(lens),
                    self.pool.states, bt)
            self.pool.states = new_states      # the updated (donated) arena
        elif self._mixed_prefill is not None:
            first_dev, new_states = self._mixed_prefill(
                self.params, self.stacked_bank, jnp.asarray(toks),
                jnp.asarray(lens), jnp.asarray(modes))
        else:
            first_dev, new_states = self._mono_prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens))
        self.prefill_calls += 1
        self.prefill_tokens += int(lens[:n].sum())
        self.prefill_padded_tokens += bp * blen
        return first_dev, new_states

    def _prefill_group(self, blen: int, group: List[tuple]):
        """ONE jitted full-sequence prefill for every request in a bucket
        (:meth:`_launch_prefill`), then the admitted rows' first tokens,
        positions and sessions."""
        with span("engine.prefill", self._tel, "engine.prefill_s",
                  rids=[a[0].rid for a in group], bucket=blen):
            first_dev, new_states = self._launch_prefill(blen, group)
        # admission-time sync: the argmax already ran inside the jit, so
        # this materializes a tiny int32 array (once per admitted bucket,
        # not once per decode tick)
        with span("engine.prefill_wait", self._tel):
            first = np.asarray(first_dev, np.int32)
        now = _now()
        slots = [a[1] for a in group]
        plens = [a[0].prompt_len for a in group]
        if self.paged:
            # the prefill already scattered the arena through the block
            # tables — only positions (and, on the device loop, the
            # device-resident token/position buffers) remain
            for s, p in zip(slots, plens):
                self.pool.positions[s] = p
            if not self.host_loop:
                self._positions, self.cur_tokens = _admit_meta(
                    self._positions, self.cur_tokens,
                    jnp.asarray(slots, jnp.int32),
                    jnp.asarray(plens, jnp.int32), first_dev)
        elif self.host_loop:
            # ONE scatter moves every admitted row into its pool slot
            self.pool.write_rows(new_states, slots, plens)
        else:
            # device-resident admission: states, positions, and first
            # tokens land in the donated pool buffers in one dispatch
            self.pool.states, self._positions, self.cur_tokens = \
                _admit_scatter(self.pool.states, self._positions,
                               self.cur_tokens, new_states,
                               jnp.asarray(slots, jnp.int32),
                               jnp.asarray(plens, jnp.int32),
                               _slot_axis(self.cfg), first_dev)
            for s, p in zip(slots, plens):
                self.pool.positions[s] = p          # host-side bookkeeping
        for i, (req, slot, mode, budget, cap) in enumerate(group):
            tok = first[i]
            if self.host_loop:
                self.cur_tokens[slot] = tok
            sess = Session(request=req, slot=slot, admitted_tick=self.tick,
                           gen_budget=budget, admission_mode=mode,
                           mode_trace=[(self.tick, mode)])
            sess.pos = req.prompt_len
            # the prefill's argmax IS the first generated token — deliver it
            sess.tokens.append(int(tok.reshape(-1)[0]) if tok.ndim
                               else int(tok))
            sess.ttft_s = now - req.t_submit if req.t_submit else 0.0
            if self._tel is not None:
                if req.t_submit:
                    self._tel.observe("engine.ttft_s", sess.ttft_s)
                if req.t_admit:
                    self._tel.observe("engine.admit_to_first_token_s",
                                      now - req.t_admit)
                self._tel.instant("admit", cat="admission", rid=req.rid,
                                  slot=slot, mode=mode, t=now)
            # the prompt's boundary activations cross the uplink once, in
            # the admission-chosen mode (and the prefill really ran them
            # through that mode's bottleneck head), with the transfer
            # simulated against the link capacity observed at admission
            pb = bottleneck.mode_payload_bytes(self.cfg, 1, req.prompt_len,
                                               mode)
            sess.prefill_wire_bytes = pb
            sess.wire_bytes += pb
            if self.orch is not None:
                link = self.orch.register(req.rid)
                sess.transfer_s += tx_seconds(
                    pb, cap if cap is not None else link.capacity_ema)
            if sess.done:                # budget == 1: already complete
                sess.finished_tick = self.tick
                self._release_links(sess)
                self.pool.release(slot)
                self.finished.append(sess)
            else:
                self.active[slot] = sess

    def _release_links(self, sess: Session):
        """Drop a retiring session's orchestrator/controller state, folding
        the controller's escalation count into the session record (its
        switch trace is already on the session)."""
        if self.controller is not None:
            ctl = self.controller.finish(sess.request.rid)
            if ctl is not None:
                sess.escalations = ctl.escalations
        elif self.orch is not None:
            self.orch.release(sess.request.rid)

    # -- decode ---------------------------------------------------------------
    def _payload_bytes(self, mode: int) -> int:
        """Per-token wire bytes for ``mode`` — a pure function of the fixed
        config, memoized because mode accounting runs K x B times per decode
        window on the host, squarely on the dispatch critical path."""
        pb = self._mode_pb.get(mode)
        if pb is None:
            pb = self._mode_pb[mode] = bottleneck.mode_payload_bytes(
                self.cfg, 1, 1, mode)
        return pb

    def _choose_modes(self, tick: Optional[int] = None,
                      items=None) -> np.ndarray:
        """Per-slot mode selection for ONE decode tick (``tick`` defaults
        to the current one; the device loop calls this for each tick of a
        decode window before dispatching the whole window — mode selection
        depends only on channel observations and counts, never on decoded
        token values, so whole windows are decidable up front).

        Every live session's own channel advances exactly one tick
        regardless of policy (identical observation streams make
        adaptive-vs-frozen comparisons apples-to-apples); the policy only
        decides what to do with the observation:

        * ``controller`` set — adaptive: one vectorized
          ``ModeController.step_modes`` call re-selects the whole pool;
        * ``freeze_modes`` — the admission-chosen mode for the session's
          whole life (the EMA still tracks, for transfer accounting);
        * otherwise — the orchestrator's scalar per-request loop (legacy).

        Also accounts per-token wire bytes/transfer under the time-varying
        mode, records mode-switch traces, and counts a deadline miss for
        every decode token whose simulated transfer exceeded the session's
        latency budget.
        """
        tick = self.tick if tick is None else tick
        modes = np.zeros(self.pool.n_slots, np.int32)
        if items is None:                          # deterministic slot order
            items = sorted(self.active.items())    # (window loops hoist this)
        caps = [sess.request.channel.step()
                if self.orch is not None and sess.request.channel is not None
                else None
                for _, sess in items]
        chosen = None
        if self.controller is not None and items:
            chosen = self.controller.step_modes(
                [sess.request.rid for _, sess in items], caps, tick)
        for i, (slot, sess) in enumerate(items):
            mode = 0
            if self.orch is not None:
                rid = sess.request.rid
                cap = caps[i]
                if chosen is not None:
                    mode = int(chosen[i])
                else:
                    if cap is not None:
                        self.orch.observe_capacity(cap, rid=rid)
                    if self._mixed_step is not None:
                        mode = (sess.admission_mode if self.freeze_modes
                                else self.orch.choose_mode(rid=rid))
                    # else: no bottleneck bank in params — the decode path
                    # can only transmit the raw boundary, so account mode 0
                    # rather than charging for compression that never runs
                pb = self._payload_bytes(mode)
                link = self.orch.register(rid)
                tx = tx_seconds(pb, cap if cap is not None
                                else link.capacity_ema)
                sess.account(mode, pb, tx)
                # deadline misses are only meaningful against an observed
                # link: with no channel the capacity EMA is a phantom 0.0
                # and every token would count as a miss
                if link.ticks > 0 and \
                        tx > self.orch.requirement_for(rid).latency_budget_s:
                    sess.deadline_misses += 1
            else:
                sess.account(0, self._payload_bytes(0), 0.0)
            if sess.mode_trace and sess.mode_trace[-1][1] != mode:
                if self._tel is not None:
                    self._tel.inc("engine.mode_switches")
                    self._tel.instant("mode_switch", cat="mode",
                                      rid=sess.request.rid, tick=tick,
                                      frm=sess.mode_trace[-1][1], to=mode)
                sess.mode_trace.append((tick, mode))
            modes[slot] = mode
        return modes

    def step(self) -> bool:
        """One engine tick: admit, then one mixed-mode decode step over the
        pool. Returns False when there is nothing left to do.

        The default loop is *device-resident*: argmax, token feedback, and
        position increments happen inside the jitted step against donated
        buffers, and the host only materializes the PREVIOUS tick's int32
        tokens after dispatching the current one — so orchestrator /
        controller / channel bookkeeping overlaps device compute instead of
        serializing with it. ``host_loop=True`` keeps the legacy
        synchronous loop (one argmax dispatch + blocking host round-trip
        per tick) as the measured baseline and equivalence oracle.
        """
        return self._step_host() if self.host_loop else self._step_device()

    def _step_host(self) -> bool:
        """Legacy synchronous tick (the pre-device-loop engine, preserved
        verbatim for A/B benchmarks and token-identity tests)."""
        self._deliver_arrivals()
        self._admit()
        if not self.active:
            if self._pending:          # idle until the next arrival
                self.tick = self._pending[0][0]
                return True
            return False

        t0 = _now() if self._tel is not None else 0.0
        modes = self._choose_modes()
        bt = None
        if self.paged:
            # on-demand growth: this tick writes each live slot's row at
            # its current position
            for slot in self.active:
                self.pool.alloc_pages(slot,
                                      int(self.pool.positions[slot]) + 1)
            bt = self.pool.block_table()
        positions = sharding.shard_batch(jnp.asarray(self.pool.positions),
                                         self.mesh)
        toks = sharding.shard_batch(jnp.asarray(self.cur_tokens), self.mesh)
        modes_dev = sharding.shard_batch(jnp.asarray(modes), self.mesh)
        if self._mixed_step is not None:
            if bt is not None:
                logits, new_states = self._mixed_step(
                    self.params, self.stacked_bank, toks, self.pool.states,
                    positions, modes_dev, bt)
            else:
                logits, new_states = self._mixed_step(
                    self.params, self.stacked_bank, toks, self.pool.states,
                    positions, modes_dev)
        elif bt is not None:
            logits, new_states = self._mono_step(self.params, toks,
                                                 self.pool.states, positions,
                                                 bt)
        else:                          # no bottleneck bank: raw mode only
            logits, new_states = self._mono_step(self.params, toks,
                                                 self.pool.states, positions)
        self.pool.states = new_states
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)

        if self._tel is not None:
            # one synchronous host tick == one token per live slot
            self._tel.observe("engine.intertoken_s", _now() - t0,
                              len(self.active))
            self._tel.set("engine.queue_depth", len(self.queue))
            self._tel.set("engine.slot_occupancy",
                          len(self.active) / self.pool.n_slots)
            self._tel.inc("engine.decode_wire_bytes",
                          sum(self._payload_bytes(int(modes[s]))
                              for s in self.active))
            self._tel.inc("engine.decode_tokens", len(self.active))
            if self.paged:
                self._tel.set("engine.page_occupancy",
                              self.pool.pages_in_use
                              / max(self.pool.n_pages, 1))

        self.decode_ticks += 1
        self.decoded_slot_ticks += len(self.active)
        if len({int(m) for s, m in enumerate(modes) if s in self.active}) > 1:
            self.mode_mix_ticks += 1

        for slot in list(self.active):
            sess = self.active[slot]
            tok = nxt[slot]
            sess.tokens.append(int(tok.reshape(-1)[0]) if tok.ndim
                               else int(tok))
            self.cur_tokens[slot] = tok
            self.pool.positions[slot] += 1
            sess.pos += 1
            if sess.done:
                sess.finished_tick = self.tick
                self._release_links(sess)
                del self.active[slot]
                self.pool.release(slot)
                self.finished.append(sess)
        self.tick += 1
        return True

    def _window_len(self) -> int:
        """How many ticks the next device dispatch may cover: bounded by
        the earliest session completion (retirement frees a slot — an
        admission opportunity), the next pending arrival, and
        ``max_window``; floored to a power of two so the jitted scan sees
        O(log max_window) distinct lengths."""
        rem = min((sess.gen_budget or sess.request.max_new_tokens)
                  - (sess.pos - sess.request.prompt_len + 1)
                  for sess in self.active.values())
        k = max(rem, 1)
        if self._pending:
            k = min(k, max(self._pending[0][0] - self.tick, 1))
        k = min(k, self.max_window)
        return 1 << (k.bit_length() - 1)

    def _step_device(self) -> bool:
        """Device-resident decode window with a one-window-lagged host sync.

        Mode selection and budget-based retirement depend only on channel
        observations and token COUNTS — never on decoded token VALUES — so
        the host decides a whole window of ticks up front ([K, B] mode
        matrix, K from ``_window_len``) and dispatches it as ONE jitted
        lax.scan on the pipeline worker (XLA releases the GIL, so the next
        window's orchestrator / controller / channel bookkeeping overlaps
        device compute). Slot lifecycle stays tick-exact with the host
        loop; token values land one window late, materialized while the
        device crunches the next window. The decoded streams are
        token-identical to ``host_loop=True`` — pinned by tests.
        """
        self._deliver_arrivals()
        self._admit()
        if not self.active:
            self._materialize_inflight()
            self._sync_device_state()
            if self._pending:          # idle until the next arrival
                self.tick = self._pending[0][0]
                return True
            return False

        with span("engine.plan", self._tel):
            k = self._window_len()
            bt = None
            if self.paged:
                # the host precomputes the window's page appends exactly
                # like the [K, B] mode matrix: every row the window will
                # write (positions pos..pos+k-1 per live slot) gets its
                # page BEFORE dispatch, and the block table ships as a
                # fresh device copy
                for slot in self.active:
                    self.pool.alloc_pages(slot,
                                          int(self.pool.positions[slot]) + k)
                bt = self.pool.block_table()
        # the live-session set is frozen for the whole window (retirement
        # is budget-driven and happens after dispatch), so sort once and
        # reuse the ordering for every tick's mode selection AND as the
        # materialization snapshot
        snapshot = sorted(self.active.items())
        with span("engine.choose_modes", self._tel, k=k,
                  live=len(snapshot)):
            modes_k = np.stack([self._choose_modes(self.tick + i,
                                                   items=snapshot)
                                for i in range(k)])
        prev = self._inflight
        with span("engine.dispatch", self._tel, "engine.window_dispatch_s",
                  k=k, live=len(snapshot), tick=self.tick):
            fut = self._dispatch_device_step(modes_k, bt)
        # snapshot BEFORE retirement: these sessions each emit one token
        # per window tick, whose values land at the next materialization
        self._inflight = (snapshot, fut, k, _now() if self._tel is not None
                          else 0.0)
        if self._tel is not None:
            self._tel.set("engine.queue_depth", len(self.queue))
            self._tel.set("engine.slot_occupancy",
                          len(snapshot) / self.pool.n_slots)
            if self.paged:
                self._tel.set("engine.page_occupancy",
                              self.pool.pages_in_use
                              / max(self.pool.n_pages, 1))
            # the host's own accounting of the window (_choose_modes
            # charged each session these bytes), as the host loop counts
            self._tel.inc("engine.decode_wire_bytes",
                          sum(self._payload_bytes(int(m))
                              for slot, _ in snapshot
                              for m in modes_k[:, slot]))
            self._tel.inc("engine.decode_tokens", k * len(snapshot))

        with span("engine.retire", self._tel):
            self.decode_ticks += k
            self.decoded_slot_ticks += k * len(snapshot)
            active_slots = set(self.active)
            for i in range(k):
                if len({int(m) for s, m in enumerate(modes_k[i])
                        if s in active_slots}) > 1:
                    self.mode_mix_ticks += 1

            # budget-based retirement at dispatch time: frees slots for
            # the next tick's admission without waiting for token values
            # (sessions can only complete at the window's last tick —
            # _window_len never overshoots the earliest completion)
            for slot, sess in snapshot:
                sess.pos += k
                self.pool.positions[slot] += k
                emitted = sess.pos - sess.request.prompt_len + 1  # +prefill
                budget = sess.gen_budget or sess.request.max_new_tokens
                if emitted >= budget:
                    sess.finished_tick = self.tick + k - 1
                    self._release_links(sess)
                    del self.active[slot]
                    self.pool.release(slot)
        # sync the PREVIOUS window's tokens while the device runs this one
        if prev is not None:
            self._materialize(prev)
        self.tick += k
        return True

    def _dispatch_device_step(self, modes_k: np.ndarray,
                              bt=None) -> _cf.Future:
        """Enqueue one fused decode window on the pipeline worker. The
        closure chains on the previous window's future (single worker =
        FIFO, so ``prev.result()`` never blocks the worker on unfinished
        work); the main thread returns immediately and keeps doing host
        bookkeeping while XLA executes. ``bt`` (paged pools) is the
        window's frozen block table — a fresh device buffer, never
        donated. The worker's ``engine.launch`` span covers the wait for
        the previous window's call and this window's dispatch."""
        prev, cur = self._future, (self.cur_tokens, self.pool.states,
                                   self._positions)
        # [K, B]: the slot axis is axis 1 inside the window scan
        modes_dev = sharding.shard_batch(jnp.asarray(modes_k), self.mesh,
                                         axis=1)
        params, stacked = self.params, self.stacked_bank
        mixed, mono = self._mixed_step_dev, self._mono_step_dev
        tail = () if bt is None else (bt,)

        def work():
            with span("engine.launch"):
                tok, states, positions = prev.result()[:3] \
                    if prev is not None else cur
                if mixed is not None:
                    return mixed(params, stacked, tok, states, positions,
                                 modes_dev, *tail)
                return mono(params, tok, states, positions, modes_dev,
                            *tail)

        fut = self._pipeline().submit(work)
        self._future = fut
        return fut

    def _pipeline(self) -> _cf.ThreadPoolExecutor:
        if self._exec is None:
            self._exec = _cf.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="decode-pipeline")
            # callers that drop the engine without close() must not pin a
            # worker thread for the life of the process: shut the executor
            # down (non-blocking) when the engine is garbage-collected
            self._exec_finalizer = weakref.finalize(
                self, self._exec.shutdown, False)
        return self._exec

    def close(self):
        """Land any in-flight window (tokens are materialized, buffers
        re-homed) and shut this engine's pipeline worker down. Idempotent;
        the engine remains usable afterwards (a new worker spawns lazily on
        the next dispatch)."""
        self._materialize_inflight()
        self._sync_device_state()
        if self._exec is not None:
            self._exec_finalizer.detach()
            self._exec.shutdown(wait=True)
            self._exec = None

    def __enter__(self) -> "ContinuousBatchingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _sync_device_state(self):
        """Land the last dispatched window's buffers back on the engine.
        Must run before anything reads (or scatters into) ``pool.states``,
        ``cur_tokens``, or ``_positions`` — admission, warm/reset, end of
        run — because while a window is in flight those attributes point at
        stale (donated) buffers."""
        if self._future is not None:
            with span("engine.sync_wait", self._tel):
                self.cur_tokens, self.pool.states, self._positions = \
                    self._future.result()[:3]
            self._future = None

    def _materialize(self, inflight):
        """Host side of the lagged pipeline: copy one window's [K, B]
        int32 token block off the device and append it to the snapshot's
        sessions; sessions whose budget completed in that window move to
        ``finished`` here (their slots were already freed at dispatch)."""
        snapshot, fut, k, t_disp = inflight
        with span("engine.materialize", self._tel,
                  "engine.window_materialize_s", k=k):
            with span("engine.materialize_wait", self._tel):
                # the token block [K, B, ...] and, with experts, the MoE
                # counts of the same window, in one transfer
                arr, *moe = jax.device_get(fut.result()[3:])
            if self._tel is not None:
                # window wall clock (dispatch -> tokens on host) over k
                # ticks IS the device loop's inter-token latency, weighted
                # by the tokens the window produced
                self._tel.observe("engine.intertoken_s",
                                  (_now() - t_disp) / k, k * len(snapshot))
            if moe:
                self._record_moe(snapshot, *moe)
            for slot, sess in snapshot:
                for i in range(k):
                    tok = arr[i, slot]
                    sess.tokens.append(int(tok.reshape(-1)[0]) if tok.ndim
                                       else int(tok))
                budget = sess.gen_budget or sess.request.max_new_tokens
                if len(sess.tokens) >= budget:
                    self.finished.append(sess)

    def _record_moe(self, snapshot, rows, touched):
        """A window's MoE counts (``_window_scan_body``) onto its sessions,
        one entry per decoded token, and into the telemetry registry."""
        for slot, sess in snapshot:
            sess.moe_rows.extend(int(r) for r in rows[:, slot])
            sess.moe_touched.extend(int(t) for t in touched)
        if self._tel is not None:
            self._tel.inc("moe.rows_here",
                          int(sum(rows[:, slot].sum() for slot, _ in snapshot)))
            self._tel.inc("moe.touched_experts", int(touched.sum()))
            self._tel.inc("moe.decode_ticks", len(touched))

    def _materialize_inflight(self):
        if self._inflight is not None:
            prev, self._inflight = self._inflight, None
            self._materialize(prev)

    def warm(self, prompt: np.ndarray, gen: int = 2):
        """Trace every compiled path a measured run can hit — decode plus
        each power-of-two prefill batch bucket up to the slot pool, and (on
        the device loop) each power-of-two decode-window length up to
        ``max_window`` — then zero the counters. ``prompt`` should have the
        measured run's prompt length so the same length bucket compiles."""
        k = 1
        while True:
            n = min(k, self.pool.n_slots)
            self.run([Request(rid=-1 - i, prompt=np.asarray(prompt),
                              max_new_tokens=gen) for i in range(n)])
            if k >= self.pool.n_slots:
                break
            k <<= 1
        if not self.host_loop:
            w = 1
            while w <= self.max_window:
                # budget w+1 = prefill token + exactly one window of w ticks
                # (w starts at 1: single-tick windows occur at stream tails,
                # and their scan otherwise compiles inside the measured run)
                self.run([Request(rid=-1 - i, prompt=np.asarray(prompt),
                                  max_new_tokens=w + 1)
                          for i in range(self.pool.n_slots)])
                w <<= 1
        self.reset_counters()

    def reset_counters(self):
        """Zero every aggregate stat (after a warm-up run) while keeping the
        compiled paths, pool state, and orchestrator calibration."""
        self._materialize_inflight()
        self._sync_device_state()
        self.finished.clear()
        self.tick = 0
        self.decode_ticks = self.mode_mix_ticks = 0
        self.decoded_slot_ticks = 0
        self.prefill_calls = self.prefill_tokens = 0
        self.prefill_padded_tokens = 0
        self.requests_over_capacity = self.requests_truncated = 0
        self.requests_parked = 0
        self._parked_rids.clear()
        if self.paged:
            self.pool.peak_pages_in_use = self.pool.pages_in_use
        self.queue.submitted = self.queue.rejected = 0
        if self._tel is not None:
            # shared across a cluster's replicas — a reset between warm-up
            # and measurement clears everyone's warm data, which is what
            # every caller wants (warm() runs before the measured window)
            self._tel.registry.reset()

    def run(self, requests: Optional[List[Request]] = None,
            max_ticks: int = 100_000) -> List[Session]:
        """Drive the engine until every submitted request completes (or the
        tick budget runs out). Returns the finished sessions."""
        for r in requests or []:
            self.submit(r)
        for _ in range(max_ticks):
            if not self.step():
                break
        self._materialize_inflight()   # tick-budget exhaustion: don't drop
        self._sync_device_state()      # the last dispatched tick's tokens
        return self.finished

    # -- aggregate stats ------------------------------------------------------
    def stats(self) -> dict:
        toks = sum(len(s.tokens) for s in self.finished)
        # the first token of every session came from its prefill, not a
        # decode tick — decode-side rates divide by decode-tick tokens only
        dec_toks = sum(max(len(s.tokens) - 1, 0) for s in self.finished)
        wire = sum(s.wire_bytes for s in self.finished)
        prefill_wire = sum(s.prefill_wire_bytes for s in self.finished)
        decode_wire = wire - prefill_wire
        mix: Dict[int, int] = {}
        for s in self.finished:
            for m, c in s.mode_counts.items():
                mix[m] = mix.get(m, 0) + c
        switches = sum(max(len(s.mode_trace) - 1, 0) for s in self.finished)
        misses = sum(s.deadline_misses for s in self.finished)
        policy = ("adaptive" if self.controller is not None
                  else "frozen" if self.freeze_modes
                  else "per-tick" if self.orch is not None else "static")
        paged_stats = {}
        if self.paged:
            paged_stats = {
                "page_len": self.pool.page_len,
                "n_pages": self.pool.n_pages,
                "pages_in_use": int(self.pool.pages_in_use),
                "peak_pages_in_use": int(self.pool.peak_pages_in_use),
                "page_occupancy": (self.pool.peak_pages_in_use
                                   / max(self.pool.n_pages, 1)),
                "requests_parked": self.requests_parked,
            }
        out = {
            "mode_policy": policy,
            "paged": self.paged,
            **paged_stats,
            "mode_switches": switches,
            "mode_escalations": sum(s.escalations for s in self.finished),
            "deadline_misses": misses,
            "deadline_miss_rate": misses / max(dec_toks, 1),
            "requests_finished": len(self.finished),
            "requests_rejected": self.queue.rejected,
            "requests_over_capacity": self.requests_over_capacity,
            "requests_truncated": self.requests_truncated,
            "generated_tokens": toks,
            "decode_tokens": dec_toks,
            "wire_bytes": wire,
            # prefill bytes scale with prompt length, decode bytes with
            # generated tokens — folding them into one per-token figure
            # skewed mode comparisons, so they are reported separately
            "prefill_wire_bytes": prefill_wire,
            "decode_wire_bytes": decode_wire,
            "decode_wire_bytes_per_token": decode_wire / max(dec_toks, 1),
            "mode_counts": mix,
            "decode_ticks": self.decode_ticks,
            "decoded_slot_ticks": self.decoded_slot_ticks,
            "mixed_mode_ticks": self.mode_mix_ticks,
            "prefill_calls": self.prefill_calls,
            "prefill_tokens": self.prefill_tokens,
            "prefill_padded_tokens": self.prefill_padded_tokens,
            "mean_ttft_s": (float(np.mean([s.ttft_s for s in self.finished]))
                            if self.finished else 0.0),
        }
        if self._tel is not None:
            # mirror the legacy totals into the registry so the JSON /
            # Prometheus exports always agree with this dict (the dict
            # itself is computed exactly as before — key/value parity
            # with telemetry off is pinned by tests)
            self._tel.registry.ingest("engine.stats", out)
        return out
