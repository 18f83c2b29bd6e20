"""KV-cache decode path (models/decode.py) — the incremental dataflow
must match the full training forward exactly: per-position prefill
logits, and greedy continuations token-for-token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.decode import (
    assign_slot, decode_step, generate, init_cache, prefill,
    prefill_scan, reset_slot,
)
from horovod_tpu.models.transformer import gpt


def _model(**overrides):
    common = dict(num_layers=2, num_heads=4, emb_dim=64, max_len=32,
                  vocab_size=256, dtype=jnp.float32,
                  attention_impl="reference")
    common.update(overrides)
    return gpt("nano", **common)


# Two schedules of the same float32 arithmetic (one causal forward, a
# token-by-token scan, the training forward) agree to rounding, not to
# the bit: XLA orders each reduction per program.  Observed on this
# model (logits up to 4.4, cached K/V up to 4.2 in magnitude): at most
# 2.98e-6 between either prefill and the training forward, 1.43e-6
# between the two caches.  The limit is ten times the largest.
SCHEDULE_ATOL = 3e-5


def _prompt(model, b=2, s=12, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(
            0, model.cfg.vocab_size, (b, s)
        ),
        jnp.int32,
    )


@pytest.mark.parametrize("overrides", [
    {},                                        # MHA, learned positions
    {"pos_embedding": "rope"},                 # rotary
    {"num_kv_heads": 2},                       # GQA
    {"num_kv_heads": 1, "pos_embedding": "rope"},  # MQA + rope
])
def test_prefill_matches_full_forward(overrides):
    model = _model(**overrides)
    prompt = _prompt(model)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)
    want = jax.jit(model.apply)(params, prompt)
    got, cache = jax.jit(
        lambda p, t: prefill(model.cfg, p, t)
    )(params, prompt)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4
    )
    np.testing.assert_array_equal(
        np.asarray(cache["pos"]), prompt.shape[1]
    )


@pytest.mark.parametrize("overrides", [
    {},                                        # MHA, learned positions
    {"pos_embedding": "rope"},                 # rotary
    {"num_kv_heads": 2},                       # GQA
    {"num_kv_heads": 1, "pos_embedding": "rope"},  # MQA + rope
])
def test_prefill_single_forward_matches_scanned(overrides):
    """The satellite contract: the one-shot causal prefill and the
    token-by-token scanned path are the SAME computation — both hold
    the training forward's logits, and fill the same cache, to float32
    rounding (``SCHEDULE_ATOL``); shapes, dtypes and positions exactly."""
    model = _model(**overrides)
    prompt = _prompt(model, s=12, seed=9)
    params = jax.jit(model.init)(jax.random.PRNGKey(9), prompt)
    want = np.asarray(jax.jit(model.apply)(params, prompt))
    single, c1 = jax.jit(
        lambda p, t: prefill(model.cfg, p, t)
    )(params, prompt)
    scanned, c2 = jax.jit(
        lambda p, t: prefill_scan(model.cfg, p, t)
    )(params, prompt)
    for got in (single, scanned):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=SCHEDULE_ATOL)
    for name in ("k", "v"):
        assert c1[name].dtype == c2[name].dtype
        assert c1[name].shape == c2[name].shape
        np.testing.assert_allclose(np.asarray(c1[name]),
                                   np.asarray(c2[name]), rtol=0,
                                   atol=SCHEDULE_ATOL)
    np.testing.assert_array_equal(np.asarray(c1["pos"]),
                                  np.asarray(c2["pos"]))


def test_prefill_supports_zigzag_models():
    """A zigzag-layout model's forward demands explicit positions, but
    decode prompts are always contiguous — the single-forward prefill
    must supply them itself (review finding: it used to delegate
    positions=None into the zigzag guard) and stay equal, to float32
    rounding, to the scanned path, whose attend override never ran the
    zigzag schedule either."""
    from dataclasses import replace

    model = _model(pos_embedding="rope")
    prompt = _prompt(model, s=10, seed=17)
    params = jax.jit(model.init)(jax.random.PRNGKey(17), prompt)
    zig = replace(model.cfg, attention_impl="zigzag")
    single, c1 = jax.jit(
        lambda p, t: prefill(zig, p, t)
    )(params, prompt)
    scanned, c2 = jax.jit(
        lambda p, t: prefill_scan(zig, p, t)
    )(params, prompt)
    want = np.asarray(jax.jit(model.apply)(params, prompt))
    for got in (single, scanned):
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=SCHEDULE_ATOL)
    np.testing.assert_allclose(np.asarray(c1["k"]), np.asarray(c2["k"]),
                               rtol=0, atol=SCHEDULE_ATOL)
    # and identical to the reference-impl decode, to the bit: the cache
    # path never runs the attention schedule the impl names, so the two
    # are one program
    ref, _ = jax.jit(
        lambda p, t: prefill(model.cfg, p, t)
    )(params, prompt)
    np.testing.assert_array_equal(np.asarray(single), np.asarray(ref))


def test_decode_step_extends_prefill():
    """One decode_step after prefill equals the full forward over the
    extended sequence's last position."""
    model = _model()
    prompt = _prompt(model, s=10, seed=1)
    nxt = _prompt(model, s=1, seed=2)[:, 0]
    params = jax.jit(model.init)(jax.random.PRNGKey(1), prompt)
    _, cache = prefill(model.cfg, params, prompt)
    got, cache = decode_step(model.cfg, params, cache, nxt)
    full = jax.jit(model.apply)(
        params, jnp.concatenate([prompt, nxt[:, None]], axis=1)
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full[:, -1]), atol=2e-4, rtol=2e-4
    )
    np.testing.assert_array_equal(
        np.asarray(cache["pos"]), prompt.shape[1] + 1
    )


def test_generate_matches_full_forward_greedy():
    """Greedy cache decoding produces the same tokens as re-running the
    full forward at every step (the O(S^2)-per-token oracle)."""
    model = _model()
    prompt = _prompt(model, s=8, seed=3)
    params = jax.jit(model.init)(jax.random.PRNGKey(2), prompt)
    steps = 6
    got = jax.jit(
        lambda p, t: generate(model.cfg, p, t, steps)
    )(params, prompt)

    seq = prompt
    want = []
    for _ in range(steps):
        logits = model.apply(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.stack(want, axis=1))
    )


def test_cache_validation():
    model = _model(moe_experts=4)
    with pytest.raises(ValueError, match="dense blocks only"):
        init_cache(model.cfg, 2)


def test_prefill_matches_windowed_forward():
    """Sliding-window models decode with the same band: cached-attention
    masking must match the flash kernel's window (review finding: a
    silently-full-context decode would drift from the trained model)."""
    model = _model(attention_impl="flash", attention_window=4,
                   flash_block_q=8, flash_block_k=8)
    prompt = _prompt(model, s=16, seed=4)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), prompt)
    want = jax.jit(model.apply)(params, prompt)
    got, _ = jax.jit(
        lambda p, t: prefill(model.cfg, p, t)
    )(params, prompt)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4
    )


def test_decode_past_cache_end_poisons():
    """Writing past the cache clamps in XLA — the step must poison its
    logits instead of silently overwriting the last slot."""
    model = _model()
    prompt = _prompt(model, s=4, seed=5)
    params = jax.jit(model.init)(jax.random.PRNGKey(4), prompt)
    _, cache = prefill(model.cfg, params, prompt, max_len=4)  # full
    logits, _ = decode_step(model.cfg, params, cache,
                            prompt[:, 0])  # pos == cache size
    assert not np.isfinite(np.asarray(logits)).any()


def test_sampled_generation():
    """Sampling: reproducible under a fixed key, top_k=1 degenerates to
    greedy, temperature>0 without a key raises."""
    model = _model()
    prompt = _prompt(model, s=6, seed=6)
    params = jax.jit(model.init)(jax.random.PRNGKey(5), prompt)

    key = jax.random.PRNGKey(7)
    a = generate(model.cfg, params, prompt, 5, temperature=1.0, key=key)
    b = generate(model.cfg, params, prompt, 5, temperature=1.0, key=key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) < model.cfg.vocab_size).all()

    greedy = generate(model.cfg, params, prompt, 5)
    topk1 = generate(model.cfg, params, prompt, 5, temperature=0.5,
                     top_k=1, key=key)
    np.testing.assert_array_equal(np.asarray(topk1), np.asarray(greedy))

    with pytest.raises(ValueError, match="requires a PRNG key"):
        generate(model.cfg, params, prompt, 5, temperature=1.0)


def test_generate_eos_freezes_finished_rows():
    """``eos_id=``: rows that emit it repeat it as pad while unfinished
    rows keep producing exactly the tokens the eos-free run produces —
    a frozen row must never perturb its batch peers."""
    model = _model(pos_embedding="rope")
    prompt = _prompt(model, b=3, s=6, seed=8)
    params = jax.jit(model.init)(jax.random.PRNGKey(8), prompt)
    steps = 6
    full = np.asarray(generate(model.cfg, params, prompt, steps))
    # Pick a token some row actually emits mid-stream so the freeze has
    # something real to freeze; fall back to an unused id (pure pad).
    eos = int(full[0, steps // 2])
    got = np.asarray(
        generate(model.cfg, params, prompt, steps, eos_id=eos)
    )
    for r in range(full.shape[0]):
        hits = np.flatnonzero(full[r] == eos)
        stop = hits[0] if hits.size else steps
        np.testing.assert_array_equal(got[r, :stop + 1],
                                      full[r, :stop + 1])
        assert (got[r, stop + 1:] == eos).all()


def test_generate_eos_unused_matches_plain():
    """An eos id the model never emits must leave generation untouched
    (the early-exit path is the same math, only gated)."""
    model = _model()
    prompt = _prompt(model, s=6, seed=10)
    params = jax.jit(model.init)(jax.random.PRNGKey(10), prompt)
    plain = np.asarray(generate(model.cfg, params, prompt, 5))
    eos = int(model.cfg.vocab_size - 1)
    if eos in plain:  # pragma: no cover - vanishingly unlikely
        pytest.skip("sentinel token emitted by chance")
    got = np.asarray(
        generate(model.cfg, params, prompt, 5, eos_id=eos)
    )
    np.testing.assert_array_equal(got, plain)


def test_assign_slot_isolated_and_matches_single_stream():
    """The serving primitives: admitting a request into one slot of a
    busy pool (prompt right-padded to a bucket) leaves every other
    slot's K/V bitwise untouched, and the slot's greedy continuation
    equals single-stream ``generate`` token-for-token."""
    model = _model(pos_embedding="rope", num_kv_heads=2)
    cfg = model.cfg
    prompt = _prompt(model, b=1, s=7, seed=11)
    params = jax.jit(model.init)(jax.random.PRNGKey(11), prompt)
    steps = 5
    want = np.asarray(generate(cfg, params, prompt, steps))[0]

    cache = init_cache(cfg, 4)
    other = _prompt(model, b=1, s=5, seed=12)[0]
    cache, _ = assign_slot(cfg, params, cache, 1, other)
    peer_k = np.asarray(cache["k"])[:, 1].copy()

    padded = jnp.zeros((16,), jnp.int32).at[:7].set(prompt[0])
    cache, last = assign_slot(cfg, params, cache, 2, padded, length=7)
    toks = [int(jnp.argmax(last))]
    cur = jnp.zeros((4,), jnp.int32).at[2].set(toks[0])
    active = jnp.zeros((4,), bool).at[2].set(True)
    for _ in range(steps - 1):
        logits, cache = decode_step(cfg, params, cache, cur,
                                    write_mask=active)
        toks.append(int(jnp.argmax(logits[2])))
        cur = cur.at[2].set(toks[-1])
    np.testing.assert_array_equal(np.asarray(toks), want)
    # peer slot bitwise untouched; frozen slots never advanced
    np.testing.assert_array_equal(np.asarray(cache["k"])[:, 1], peer_k)
    np.testing.assert_array_equal(
        np.asarray(cache["pos"]), [0, 5, 7 + steps - 1, 0]
    )


def test_reset_slot_clears_one_slot_only():
    model = _model()
    cfg = model.cfg
    params = jax.jit(model.init)(jax.random.PRNGKey(13), _prompt(model))
    cache = init_cache(cfg, 3)
    cache, _ = assign_slot(cfg, params, cache, 0,
                           _prompt(model, b=1, s=4, seed=14)[0])
    cache, _ = assign_slot(cfg, params, cache, 2,
                           _prompt(model, b=1, s=6, seed=15)[0])
    keep = np.asarray(cache["k"])[:, 2].copy()
    cache = reset_slot(cache, 0)
    assert not np.asarray(cache["k"])[:, 0].any()
    np.testing.assert_array_equal(np.asarray(cache["pos"]), [0, 0, 6])
    np.testing.assert_array_equal(np.asarray(cache["k"])[:, 2], keep)


def test_legacy_scalar_pos_cache_still_decodes():
    """Pre-slot caches (scalar ``pos``, e.g. a pytree restored from an
    old checkpoint) broadcast into the per-slot layout on first use."""
    model = _model()
    prompt = _prompt(model, s=4, seed=16)
    params = jax.jit(model.init)(jax.random.PRNGKey(16), prompt)
    _, cache = prefill(model.cfg, params, prompt)
    legacy = {"k": cache["k"], "v": cache["v"],
              "pos": jnp.asarray(4, jnp.int32)}
    want, _ = decode_step(model.cfg, params, cache, prompt[:, 0])
    got, out = decode_step(model.cfg, params, legacy, prompt[:, 0])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert out["pos"].shape == (prompt.shape[0],)
