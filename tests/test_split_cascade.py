"""The paper's technique: split model exactness, Algorithm 1 phase masks,
cascade training, and the DPI/Ensure ordering."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced
from repro.configs.base import TrainConfig
from repro.core import bottleneck as BN
from repro.core import cascade as C
from repro.core import split as SP
from repro.data import lumos5g
from repro.models import lstm as LSTM
from repro.models import transformer as T


def test_split_mode0_equals_full_forward():
    cfg = get_reduced("granite-8b")
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                             cfg.vocab_size)
    full, _ = T.forward(params, tok, cfg)
    split, _, info = SP.split_forward(params, tok, cfg, mode=0)
    np.testing.assert_allclose(np.asarray(split), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
    assert info["payload_bytes"] == 2 * 16 * cfg.d_model * 2


def test_split_mode1_compresses_payload():
    cfg = get_reduced("granite-8b")
    assert BN.compression_ratio(cfg, 1) < 0.3
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                             cfg.vocab_size)
    logits, _, info1 = SP.split_forward(params, tok, cfg, mode=1)
    _, _, info0 = SP.split_forward(params, tok, cfg, mode=0)
    assert info1["payload_bytes"] < 0.3 * info0["payload_bytes"]
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_split_decode_matches_monolithic_mode0():
    cfg = get_reduced("mixtral-8x7b")
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    B = 2
    s1 = T.init_decode_state(cfg, B, 32)
    s2 = T.init_decode_state(cfg, B, 32)
    tok = jnp.zeros((B, 1), jnp.int32)
    for t in range(4):
        l_ref, s1 = T.decode_step(params, tok, s1, jnp.int32(t), cfg)
        l_split, s2, _ = SP.split_decode_step(params, tok, s2, jnp.int32(t),
                                              cfg, mode=0)
        np.testing.assert_allclose(np.asarray(l_split), np.asarray(l_ref),
                                   rtol=1e-4, atol=1e-4)
        tok = jnp.argmax(l_ref, -1).astype(jnp.int32)


def test_phase_mask_freezes_base_in_phase2():
    cfg = get_reduced("stablelm-3b")
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    m1 = C.transformer_phase_mask(params, 1)
    m2 = C.transformer_phase_mask(params, 2)
    assert all(jax.tree.leaves(m1["layers"]))
    assert not any(jax.tree.leaves(m2["layers"]))
    assert not any(jax.tree.leaves(m1["bneck_modes"]))
    assert all(jax.tree.leaves(m2["bneck_modes"][0]))


def test_cascade_on_paper_lstm_poc():
    """Run Algorithm 1 end-to-end on the (reduced) paper model with the
    synthetic Lumos5G twin; phase 2 must NOT move frozen weights and the
    Ensure ordering must hold."""
    lcfg = get_reduced("lumos5g-lstm")
    dcfg = lumos5g.Lumos5GConfig(n_samples=3000, seq_len=lcfg.seq_len,
                                 seed=0)
    data = lumos5g.generate(dcfg)
    train, test = lumos5g.train_test_split(data, dcfg)
    params = LSTM.init_params(jax.random.PRNGKey(0), lcfg)

    def loss_fn(params, batch, mode):
        return LSTM.loss_fn(params, batch, lcfg, mode)

    it = lumos5g.batch_iterator(train, 128)
    batches = [next(it) for _ in range(160)]

    def data_iter(step):
        b = batches[step % len(batches)]
        return {"x": jnp.asarray(b["x"]), "y": jnp.asarray(b["y"])}

    test_b = {"x": jnp.asarray(test["x"][:512]),
              "y": jnp.asarray(test["y"][:512])}

    def eval_fn(params, mode):
        loss, m = LSTM.loss_fn(params, test_b, lcfg, mode)
        return {"loss": loss, "acc": m["acc"]}

    enc_before = None
    tcfg = TrainConfig(learning_rate=5e-3, warmup_steps=5, total_steps=160,
                       weight_decay=0.0)

    def mask_fn(params, phase):
        return LSTM.phase_mask(params, phase)

    params, hist = C.train_cascade(
        params, loss_fn, data_iter, tcfg, n_modes=2, steps_per_phase=80,
        phase_mask_fn=mask_fn, eval_fn=eval_fn, verbose=False)

    # mode 0 learned something (better than chance = -log(1/3) ~ 1.0986)
    assert hist["phases"][0]["eval"]["loss"] < 1.05
    # Ensure: mode 1 (bottleneck) at most as good as mode 0
    assert hist["ensure"]["losses"][1] >= hist["ensure"]["losses"][0] - 0.02
    # both modes beat chance accuracy
    assert hist["ensure"]["accs"][1] > 0.40


def test_cascade_phase2_frozen_weights_unchanged():
    lcfg = get_reduced("lumos5g-lstm")
    params = LSTM.init_params(jax.random.PRNGKey(0), lcfg)
    from repro.training import optimizer as opt
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=10)
    step = C.make_train_step(
        lambda p, b, m: LSTM.loss_fn(p, b, lcfg, m), tcfg)
    state = opt.init(params)
    batch = {"x": jnp.ones((8, lcfg.seq_len, lcfg.n_features)),
             "y": jnp.zeros((8, lcfg.seq_len), jnp.int32)}
    mask = LSTM.phase_mask(params, 2)
    p2, _, _ = step(params, state, batch, mask, mode=1)
    # encoder + decoder identical; bottleneck/adapter moved
    for a, b in zip(jax.tree.leaves(params["enc"]), jax.tree.leaves(p2["enc"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params["bneck"]),
                        jax.tree.leaves(p2["bneck"])))
    assert moved


# ---------------------------------------------------------------------------
# serving split by layer range: no half-stack copies, same bits as slicing
# ---------------------------------------------------------------------------

def _split8_cfg():
    """Reduced qwen2.5-3b widened so its layer stack dominates the window's
    buffers: 8 layers, d 256, d_ff 1024, split after layer 4, float32."""
    import dataclasses
    base = get_reduced("qwen2.5-3b")
    return dataclasses.replace(
        base, n_layers=8, d_model=256, d_ff=1024, dtype="float32",
        split=dataclasses.replace(base.split, split_at=4))


_B, _K, _CACHE, _PLEN, _NB = 3, 6, 64, 16, 4


def _random_tree(key, tree):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])


def _window_inputs(cfg, paged: bool, modes):
    """Engine window inputs with non-zero caches: (params, stacked bank,
    tokens, states, positions, [K, B] modes, block table or None)."""
    from repro.serving.batcher import _compiled_steps
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    stacked = BN.bank_stack(params["bneck_modes"], cfg.split)
    tok = jax.random.randint(jax.random.PRNGKey(1), (_B, 1), 0,
                             cfg.vocab_size)
    positions = jnp.array([5, 17, 30], jnp.int32)
    modes_k = jnp.tile(jnp.asarray(modes[:_B], jnp.int32), (_K, 1))
    if paged:
        states = T.init_paged_state(cfg, 1 + _B * _NB, _PLEN)
        bt = 1 + jnp.arange(_B * _NB, dtype=jnp.int32).reshape(_B, _NB)
    else:
        states = T.init_decode_state(cfg, _B, _CACHE)
        bt = None
    states = _random_tree(jax.random.PRNGKey(2), states)
    step = _compiled_steps(cfg, _CACHE, True, paged=paged).mixed_step_dev
    return step, (params, stacked, tok, states, positions, modes_k, bt)


def _sliced_run_layers(block_fn, layers, x, states):
    """The layer scan as it was: the group's stacked params and states are
    the scan's xs and the new states its ys."""
    def body(h, inp):
        lp, st = inp
        return block_fn(lp, h, st)
    return jax.lax.scan(body, x, (layers, states))


def _sliced_split(cfg, params, x, states, boundary, block_fn):
    """The split as it was: slice params and states at ``split_at``, run each
    half, concatenate the states."""
    s = cfg.split.split_at
    enc_l, dec_l = SP.slice_layers(params["layers"], cfg)
    x, enc = _sliced_run_layers(block_fn, enc_l, x,
                                jax.tree.map(lambda a: a[:s], states))
    x = boundary(x)
    x, dec = _sliced_run_layers(block_fn, dec_l, x,
                                jax.tree.map(lambda a: a[s:], states))
    return x, jax.tree.map(lambda a, b: jnp.concatenate([a, b]), enc, dec)


def _sliced_window(cfg, params, stacked, tok, states, positions, modes_k,
                   bt):
    """Reference decode window: the engine's fused-tail scan over ticks with
    the slice-and-concatenate split step."""
    def tick(carry, modes):
        tok, states, positions = carry
        x = T.embed_tokens(params, tok, cfg, None)
        x, states = _sliced_split(
            cfg, params, x, states,
            lambda h: BN.boundary_mixed(stacked, h, modes,
                                        dtype=T.model_dtype(cfg)),
            lambda lp, h, st: T.block_apply_decode(lp, h, st, positions, cfg,
                                                   "attn", bt))
        nxt = T.decode_tail_tokens(params, x, cfg).reshape(tok.shape)
        return (nxt, states, positions + 1), nxt

    (tok, states, positions), out = jax.lax.scan(
        tick, (tok, states, positions), modes_k)
    return tok, states, positions, out


def _assert_trees_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("modes", [(0, 0, 0, 0), (0, 1, 0, 1)],
                         ids=["mode0", "mixed"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_split_window_matches_slicing_reference(paged, modes):
    """The engine's mixed decode window, which runs the split as two layer
    ranges of the whole stack, gives the tokens and final states of the
    old slice-and-concatenate split step bit for bit."""
    cfg = _split8_cfg()
    step, args = _window_inputs(cfg, paged, modes)
    want = jax.jit(functools.partial(_sliced_window, cfg))(*args)
    copied = jax.tree.map(jnp.copy, args)       # the step donates its state
    got = step(*(copied if paged else copied[:-1]))
    assert np.asarray(got[3]).shape == (_K, _B, 1)
    _assert_trees_bitwise(got, want)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_split_prefill_matches_slicing_reference(paged):
    """``split_prefill_mixed`` populates the same states and gives the same
    last-position logits as the old slice-and-concatenate prefill."""
    cfg = _split8_cfg()
    params = SP.init_split_params(jax.random.PRNGKey(0), cfg)
    stacked = BN.bank_stack(params["bneck_modes"], cfg.split)
    S = 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (_B, S), 0,
                              cfg.vocab_size)
    lengths = jnp.array([16, 9, 5], jnp.int32)
    modes = jnp.array([0, 1, 0], jnp.int32)
    if paged:
        states = T.init_paged_state(cfg, 1 + _B * _NB, _PLEN)
        bt = 1 + jnp.arange(_B * _NB, dtype=jnp.int32).reshape(_B, _NB)
    else:
        states = T.init_decode_state(cfg, _B, _CACHE)
        bt = None
    states = _random_tree(jax.random.PRNGKey(2), states)

    def sliced(params, stacked, toks, states, lengths, modes, bt):
        x = T.embed_tokens(params, toks, cfg, None)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (_B, S))
        x, states = _sliced_split(
            cfg, params, x, states,
            lambda h: BN.boundary_mixed(stacked, h, modes,
                                        dtype=T.model_dtype(cfg)),
            lambda lp, h, st: T.block_apply_prefill(
                lp, h, positions, st, cfg, "attn", lengths, bt))
        x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        x = T.norm_apply_final(params, x, cfg)
        return T.lm_logits(params, x, cfg), states

    def ranged(params, stacked, toks, states, lengths, modes, bt):
        return SP.split_prefill_mixed(params, stacked, toks, states, cfg,
                                      modes, lengths=lengths, block_table=bt)

    args = (params, stacked, toks, states, lengths, modes, bt)
    _assert_trees_bitwise(jax.jit(ranged)(*args), jax.jit(sliced)(*args))


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_split_window_copies_no_half_stack(paged):
    """The compiled mixed decode window makes no copy of either half of the
    layer stack: its scratch stays below half the layer weights' bytes, and
    no ``slice`` in the optimized HLO yields a layer leaf cut at
    ``split_at``."""
    cfg = _split8_cfg()
    step, args = _window_inputs(cfg, paged, (0, 1, 0, 1))
    compiled = step.lower(*(args if paged else args[:-1])).compile()
    params, states = args[0], args[3]
    half_stack = sum(a.nbytes for a in jax.tree.leaves(params["layers"])) // 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < half_stack, (temp, half_stack)

    s, L = cfg.split.split_at, cfg.n_layers
    cut_shapes = {
        f"{a.dtype.name.replace('float', 'f')}"
        f"[{','.join(str(d) for d in (n,) + a.shape[1:])}]"
        for a in jax.tree.leaves((params["layers"], states))
        for n in (s, L - s)}
    slices = [line for line in compiled.as_text().splitlines()
              if " slice(" in line
              and line.split("=", 1)[1].split("{", 1)[0].strip() in cut_shapes]
    assert not slices, slices[:3]
