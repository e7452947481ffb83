"""Compile-only checks of the served Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
chip that is described, not attached, at the widths the served models use
(qwen2.5-3b for the boundary, the decode tail and paged attention;
mellum2-12b-a2.5b for the sliding-window paged attention and the grouped
expert kernel; recurrentgemma-2b's ``d_rnn`` for the RG-LRU scan). Interpret-mode parity
(``test_kernels.py``, ``test_paged.py``) cannot see what Mosaic refuses:
unsupported contractions, dynamic slices of loaded values, misaligned tiles.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import bottleneck
from repro.kernels import boundary_mixed as BM
from repro.kernels import moe_gmm as MG
from repro.kernels import ops
from repro.kernels import paged_attention as PA
from repro.kernels import rglru_scan as RS

QWEN = get_config("qwen2.5-3b")
MELLUM = get_config("mellum2-12b-a2.5b")
RG = get_config("recurrentgemma-2b")
SLOTS = 32                      # decode rows of one serving tick


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text, name):
    assert "tpu_custom_call" in text
    assert f"%{name}" in text, f"no custom call named {name}"


def test_boundary_mixed_compiles(one_chip):
    stacked = jax.eval_shape(
        lambda k: bottleneck.bank_stack(bottleneck.bank_init(k, QWEN),
                                        QWEN.split), jax.random.PRNGKey(0))
    M, d, wmax = stacked["down_w"].shape
    block_r = 16
    G = -(-SLOTS // block_r) + M + 1                  # ops._group_rows
    text = _compiled_text(
        lambda xp, dw, uw, ns, h, n, w, b: BM.boundary_mixed_grouped(
            xp, dw, uw, ns, h, n, w, b, block_r=block_r),
        one_chip, ((G * block_r, d), jnp.bfloat16),
        ((M, d, wmax), jnp.bfloat16), ((M, wmax, d), jnp.bfloat16),
        ((M, d), jnp.bfloat16), ((G,), jnp.int32), ((G,), jnp.int32),
        ((G,), jnp.int32), ((G,), jnp.int32))
    _assert_kernel(text, "boundary_mixed")


def test_decode_tail_compiles(one_chip):
    d, V, block_r = QWEN.d_model, QWEN.vocab_size, 16
    P = (-(-SLOTS // block_r) + 1) * block_r          # ops.head_layout
    text = _compiled_text(
        lambda xp, hv, s, b, hid: BM.decode_tail_grouped(
            xp, hv, s, b, hid, block_r=block_r,
            block_v=ops._pick_block(V, 512)),
        one_chip, ((P, d), jnp.bfloat16), ((1, d, V), jnp.bfloat16),
        ((d,), jnp.bfloat16), ((d,), jnp.bfloat16),
        ((P // block_r,), jnp.int32))
    _assert_kernel(text, "decode_tail")


def test_paged_attention_compiles(one_chip):
    nq, nkv, hd = QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim
    page_len = 16                                     # the engine default
    nb = 4096 // page_len                             # 4k-token context
    n_pages = SLOTS * 8 + 1
    text = _compiled_text(
        PA.paged_attention, one_chip, ((SLOTS, nq, hd), jnp.bfloat16),
        ((n_pages, nkv, page_len, hd), jnp.bfloat16),
        ((n_pages, nkv, page_len, hd), jnp.bfloat16),
        ((SLOTS, nb), jnp.int32), ((SLOTS,), jnp.int32))
    _assert_kernel(text, "paged_attention")


def test_windowed_paged_attention_compiles(one_chip):
    nq, nkv, hd = MELLUM.n_heads, MELLUM.n_kv_heads, MELLUM.head_dim
    page_len, slots = 16, 16
    nb = 512                                          # up to 8k rows
    n_pages = 2881
    text = _compiled_text(
        PA.paged_attention, one_chip, ((slots, nq, hd), jnp.bfloat16),
        ((n_pages, nkv, page_len, hd), jnp.bfloat16),
        ((n_pages, nkv, page_len, hd), jnp.bfloat16),
        ((slots, nb), jnp.int32), ((slots,), jnp.int32),
        ((slots,), jnp.int32))
    _assert_kernel(text, "paged_attention")


@pytest.mark.parametrize("M,tm", [(128, 128), (16384, 256)],
                         ids=["decode", "prefill-chunk"])
def test_moe_gmm_compiles(one_chip, M, tm):
    d, f, held = MELLUM.d_model, MELLUM.d_ff, 16
    text = _compiled_text(
        lambda x, g, u, dn, sz: MG.moe_gmm(x, g, u, dn, sz, tm=tm),
        one_chip, ((M, d), jnp.bfloat16), ((held, d, f), jnp.bfloat16),
        ((held, d, f), jnp.bfloat16), ((held, f, d), jnp.bfloat16),
        ((held + 1,), jnp.int32))
    _assert_kernel(text, "moe_gmm")


@pytest.mark.parametrize("S", [64, 512])
def test_rglru_scan_compiles(one_chip, S):
    D = RG.d_rnn
    text = _compiled_text(
        lambda a, b: RS.rglru_scan(
            a, b, block_s=ops._pick_block(S, 256, align=8),
            block_d=ops._pick_block(D, 512)),
        one_chip, ((2, S, D), jnp.float32), ((2, S, D), jnp.float32))
    _assert_kernel(text, "rglru_scan")
