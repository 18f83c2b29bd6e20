"""Flash-attention kernel + transformer model tests.

The Pallas kernel runs through the interpreter on the CPU test mesh
(identical program, no TPU needed); correctness is against the plain
softmax reference, gradients included — the kernel is advertised as
training-ready.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from flash_oracle import (flash_bwd_blockwise, folded_plan, force,
                          out_and_grads, pallas_calls, qkv)
from horovod_tpu.models import TransformerConfig, gpt
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel import local_attention


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        ref = local_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_uneven_blocks(self):
        # S=48 forces _pick_block to drop to a divisor
        q, k, v = qkv(s=48, seed=1)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        ref = local_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_grads_match_reference(self):
        q, k, v = qkv(seed=2)
        f = lambda *a: (
            flash_attention(*a, causal=True, block_q=16, block_k=16) ** 2
        ).sum()
        r = lambda *a: (local_attention(*a, causal=True) ** 2).sum()
        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
            )

    def test_bf16_inputs(self):
        q, k, v = qkv(seed=3, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        assert out.dtype == jnp.bfloat16
        ref = local_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), causal=True
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=0.05,
            rtol=0.05,
        )

    def test_shape_mismatch_rejected(self):
        q, k, v = qkv()
        with pytest.raises(ValueError, match="matching"):
            flash_attention(q, k[:, :32], v)


class TestGPT:
    def _cfg(self, **kw):
        return dict(size="nano", flash_block_q=16, flash_block_k=16, **kw)

    def test_forward_shapes_and_finite(self):
        model = gpt(**self._cfg())
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 1024, (2, 32))
        )
        params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
        logits = jax.jit(model.apply)(params, tokens)
        assert logits.shape == (2, 32, 1024)
        assert logits.dtype == jnp.float32
        assert np.isfinite(np.asarray(logits)).all()

    def test_flash_equals_reference_impl(self):
        tokens = jnp.asarray(
            np.random.RandomState(1).randint(0, 1024, (2, 32))
        )
        m_flash = gpt(**self._cfg(attention_impl="flash",
                                  dtype=jnp.float32))
        m_ref = gpt(**self._cfg(attention_impl="reference",
                                dtype=jnp.float32))
        params = jax.jit(m_flash.init)(jax.random.PRNGKey(0), tokens)
        np.testing.assert_allclose(
            np.asarray(jax.jit(m_flash.apply)(params, tokens)),
            np.asarray(jax.jit(m_ref.apply)(params, tokens)),
            atol=2e-4, rtol=2e-4,
        )

    def test_causality(self):
        """Changing a future token must not change past logits."""
        model = gpt(**self._cfg(dtype=jnp.float32))
        rng = np.random.RandomState(2)
        t1 = rng.randint(0, 1024, (1, 16))
        t2 = t1.copy()
        t2[0, -1] = (t2[0, -1] + 1) % 1024
        params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.asarray(t1))
        l1, l2 = (jax.jit(model.apply)(params, jnp.asarray(t))
                  for t in (t1, t2))
        np.testing.assert_allclose(
            np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]), atol=1e-5
        )
        assert np.abs(np.asarray(l1[:, -1]) - np.asarray(l2[:, -1])).max() > 1e-3

    def test_sequence_parallel_training_step(self):
        """One GPT training step with ring attention over an 8-way
        sequence-parallel mesh matches the single-device step."""
        S = 64
        cfg_sp = self._cfg(attention_impl="ring", sp_axis="sp",
                           dtype=jnp.float32)
        cfg_1d = self._cfg(attention_impl="reference", dtype=jnp.float32)
        model_sp, model_1d = gpt(**cfg_sp), gpt(**cfg_1d)
        tokens = jnp.asarray(np.random.RandomState(3).randint(0, 1024, (2, S)))
        targets = jnp.roll(tokens, -1, axis=1)
        params = jax.jit(model_1d.init)(jax.random.PRNGKey(0), tokens[:, :8])

        def loss_1d(p):
            logits = model_1d.apply(p, tokens)
            return -jnp.take_along_axis(
                jax.nn.log_softmax(logits), targets[..., None], -1
            ).mean()

        mesh = Mesh(np.asarray(jax.devices()[:8]), ("sp",))
        s_local = S // 8

        def local_loss(p, tok, tgt):
            off = jax.lax.axis_index("sp") * s_local
            logits = model_sp.apply(p, tok, pos_offset=off)
            nll = -jnp.take_along_axis(
                jax.nn.log_softmax(logits), tgt[..., None], -1
            ).mean()
            return jax.lax.pmean(nll, "sp")

        loss_sp = jax.jit(
            shard_map(
                local_loss,
                mesh=mesh,
                in_specs=(P(), P(None, "sp"), P(None, "sp")),
                out_specs=P(),
                check_vma=False,
            )
        )
        l1, g1 = jax.jit(jax.value_and_grad(loss_1d))(params)
        l2 = loss_sp(params, tokens, targets)
        np.testing.assert_allclose(float(l1), float(l2), atol=1e-5, rtol=1e-5)
        g2 = jax.jit(jax.grad(
            lambda p: loss_sp(p, tokens, targets)
        ))(params)
        flat1 = jax.tree_util.tree_leaves(g1)
        flat2 = jax.tree_util.tree_leaves(g2)
        for a, b in zip(flat2, flat1):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
            )

    def test_ring_requires_axis(self):
        with pytest.raises(ValueError, match="sp_axis"):
            cfg = TransformerConfig(attention_impl="ring")
            _attend_probe(cfg)


def _attend_probe(cfg):
    from horovod_tpu.models.transformer import _attend

    x = jnp.zeros((1, 8, cfg.num_heads, cfg.head_dim))
    _attend(cfg, x, x, x, 0)


class TestPallasBackward:
    """The fused Pallas backward must match the scan-fallback backward
    (its differential reference) bit-for-bit at fp32 tolerance, causal
    and bidirectional, including the block-skipping causal path."""

    @pytest.mark.parametrize("causal,window", [
        (False, None), (True, None), (True, 24),
    ])
    def test_pallas_bwd_matches_scan_bwd(self, causal, window):
        from horovod_tpu.ops.flash_attention import (
            _flash_bwd_pallas, _flash_fwd_kernel,
        )

        rng = np.random.RandomState(0)
        z, s, d, bq, bk = 3, 64, 16, 16, 16
        q, k, v, do = (
            jnp.asarray(rng.randn(z, s, d), jnp.float32) for _ in range(4)
        )
        scale = d ** -0.5
        plan = folded_plan(q, k, v, causal, bq, bk, window=window)
        o, lse = _flash_fwd_kernel(q, k, v, plan, scale, True)
        ref = flash_bwd_blockwise(q, k, v, o, lse, do, causal, scale, bk,
                                  window=window)
        got = _flash_bwd_pallas(q, k, v, o, lse, do, plan, scale, True)
        for name, a, b in zip(("dq", "dk", "dv"), got, ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5,
                err_msg=f"{name} mismatch (causal={causal}, "
                        f"window={window})",
            )

    def test_pallas_bwd_uneven_blocks(self):
        from horovod_tpu.ops.flash_attention import (
            _flash_bwd_pallas, _flash_fwd_kernel,
        )

        rng = np.random.RandomState(1)
        z, s, d, bq, bk = 2, 48, 8, 16, 8  # nq != nk
        q, k, v, do = (
            jnp.asarray(rng.randn(z, s, d), jnp.float32) for _ in range(4)
        )
        scale = d ** -0.5
        plan = folded_plan(q, k, v, True, bq, bk)
        o, lse = _flash_fwd_kernel(q, k, v, plan, scale, True)
        ref = flash_bwd_blockwise(q, k, v, o, lse, do, True, scale, bk)
        got = _flash_bwd_pallas(q, k, v, o, lse, do, plan, scale, True)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)


class TestGQA:
    """Native grouped-query attention: k/v with fewer heads route through
    the kernels' index maps (no broadcast materialization); outputs and
    ALL gradients must match the broadcast-k/v reference."""

    @pytest.mark.parametrize("hkv", [1, 2])  # MQA and GQA
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_broadcast_reference(self, hkv, causal):
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel import local_attention

        rng = np.random.RandomState(7)
        b, s, h, d = 2, 32, 4, 16
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.3
        k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32) * 0.3
        v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32) * 0.3
        w = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        rep = lambda t: jnp.repeat(t, h // hkv, axis=2)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal,
                                  block_q=16, block_k=16)
            return (out * w).sum()

        def loss_ref(q, k, v):
            out = local_attention(q, rep(k), rep(v), causal=causal)
            return (out * w).sum()

        (lf, gf) = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        (lr, gr) = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(lf), float(lr), rtol=2e-5)
        for name, a, b_ in zip(("dq", "dk", "dv"), gf, gr):
            assert a.shape == b_.shape  # dk/dv stay at hkv heads
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-5, rtol=3e-5,
                err_msg=f"{name} (hkv={hkv}, causal={causal})",
            )

    def test_bad_kv_heads_rejected(self):
        from horovod_tpu.ops.flash_attention import flash_attention

        q = jnp.zeros((1, 16, 4, 8))
        kv = jnp.zeros((1, 16, 3, 8))  # 4 % 3 != 0
        with pytest.raises(ValueError, match="multiple of num_kv_heads"):
            flash_attention(q, kv, kv)


class TestZigzagModel:
    """End-to-end model-level zigzag SP: a RoPE GPT with
    attention_impl='zigzag' on an 8-way mesh (zigzag-sharded tokens,
    positions from zigzag_positions) must reproduce the single-device
    model's logits."""

    @pytest.mark.parametrize("kv_heads", [None, 2])
    def test_zigzag_model_matches_single_device(self, kv_heads):
        from horovod_tpu.parallel import zigzag_positions, zigzag_shard, \
            zigzag_unshard

        S, P_SIZE = 64, 8
        s_local = S // P_SIZE
        common = dict(num_layers=2, num_heads=4, emb_dim=64, max_len=S,
                      vocab_size=512, dtype=jnp.float32,
                      pos_embedding="rope", num_kv_heads=kv_heads)
        model_1d = gpt("nano", attention_impl="reference", **common)
        model_zz = gpt("nano", attention_impl="zigzag", sp_axis="sp",
                       **common)
        tokens = jnp.asarray(
            np.random.RandomState(11).randint(0, 512, (2, S)), jnp.int32
        )
        params = jax.jit(model_1d.init)(jax.random.PRNGKey(0), tokens[:, :8])
        ref = jax.jit(model_1d.apply)(params, tokens)

        mesh = Mesh(np.asarray(jax.devices()[:P_SIZE]), ("sp",))

        def local_fwd(p, tok):
            pos = zigzag_positions(
                jax.lax.axis_index("sp"), P_SIZE, s_local
            )
            return model_zz.apply(p, tok, positions=pos)

        fwd = jax.jit(
            shard_map(
                local_fwd, mesh=mesh,
                in_specs=(P(), P(None, "sp")),
                out_specs=P(None, "sp"),
                check_vma=False,
            )
        )
        out = zigzag_unshard(
            fwd(params, zigzag_shard(tokens, P_SIZE, axis=1)),
            P_SIZE, axis=1,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
        )

    def test_rope_flash_matches_reference(self):
        """RoPE + flash vs RoPE + reference on one device (fp32)."""
        common = dict(num_layers=2, num_heads=4, emb_dim=64, max_len=64,
                      vocab_size=512, dtype=jnp.float32,
                      pos_embedding="rope")
        m_flash = gpt("nano", **common)
        m_ref = gpt("nano", attention_impl="reference", **common)
        tokens = jnp.asarray(
            np.random.RandomState(12).randint(0, 512, (2, 64)), jnp.int32
        )
        params = jax.jit(m_flash.init)(jax.random.PRNGKey(0), tokens)
        assert "wpe" not in params["params"], "rope model must have no wpe"
        np.testing.assert_allclose(
            np.asarray(jax.jit(m_flash.apply)(params, tokens)),
            np.asarray(jax.jit(m_ref.apply)(params, tokens)),
            atol=2e-4, rtol=2e-4,
        )


class TestSlidingWindow:
    """window=W masks each row to its last W keys; tiles outside the
    band are skipped in fwd and bwd — values and grads must match a
    dense masked-softmax oracle exactly (up to fp32 tolerance)."""

    @staticmethod
    def _oracle(q, k, v, scale, window):
        b, s, h, d = q.shape
        rep = h // k.shape[2]
        kf = jnp.repeat(k, rep, axis=2).astype(jnp.float32)
        vf = jnp.repeat(v, rep, axis=2).astype(jnp.float32)
        st = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kf) * scale
        q_pos = jnp.arange(s)[:, None]
        k_pos = jnp.arange(s)[None, :]
        mask = (k_pos > q_pos) | (k_pos < q_pos - (window - 1))
        st = jnp.where(mask, -1e30, st)
        p = jax.nn.softmax(st, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vf)

    def _qkv(self, s=64, h=4, hkv=4, d=16, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda hh: jnp.asarray(
            rng.randn(2, s, hh, d) * 0.5, jnp.float32
        )
        return mk(h), mk(hkv), mk(hkv)

    @pytest.mark.parametrize("window,bq,bk", [
        (8, 16, 16),    # band narrower than a tile
        (24, 16, 8),    # band spans several tiles, bq != bk
        (1, 8, 8),      # degenerate: attend to self only
        (64, 16, 16),   # window == S: plain causal
        (200, 16, 16),  # window > S: clamps to plain causal
    ])
    def test_forward_matches_oracle(self, window, bq, bk):
        q, k, v = self._qkv()
        scale = q.shape[-1] ** -0.5
        got = flash_attention(q, k, v, causal=True, block_q=bq,
                              block_k=bk, window=window)
        want = self._oracle(q, k, v, scale, min(window, q.shape[1]))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_gradients_match_oracle(self):
        q, k, v = self._qkv(seed=1)
        scale = q.shape[-1] ** -0.5
        window = 24

        def loss_flash(q, k, v):
            return (flash_attention(
                q, k, v, causal=True, block_q=16, block_k=8,
                window=window,
            ) ** 2).sum()

        def loss_oracle(q, k, v):
            return (self._oracle(q, k, v, scale, window) ** 2).sum()

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name} mismatch",
            )

    def test_gqa_window(self):
        q, k, v = self._qkv(h=8, hkv=2, seed=2)
        got = flash_attention(q, k, v, causal=True, block_q=16,
                              block_k=16, window=16)
        want = self._oracle(q, k, v, q.shape[-1] ** -0.5, 16)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )

    def test_window_validation(self):
        q, k, v = self._qkv()
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, window=8)
        with pytest.raises(ValueError, match=">= 1"):
            flash_attention(q, k, v, causal=True, window=0)

    def test_model_plumbing(self):
        """attention_window reaches the kernel through the GPT config;
        the reference schedule takes it as an explicit mask, the
        sequence-parallel schedules reject it."""
        from horovod_tpu.models.transformer import gpt

        toks = jnp.asarray(
            np.random.RandomState(3).randint(0, 512, (2, 32)), jnp.int32
        )
        win = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                  vocab_size=512, max_len=32, dtype=jnp.float32,
                  attention_window=8)
        full = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                   vocab_size=512, max_len=32, dtype=jnp.float32)
        params = jax.jit(full.init)(jax.random.PRNGKey(0), toks)
        out_w = jax.jit(win.apply)(params, toks)
        out_f = jax.jit(full.apply)(params, toks)
        assert out_w.shape == out_f.shape
        # the band must actually bite (different logits)...
        assert not np.allclose(np.asarray(out_w), np.asarray(out_f))
        # ...and rows 0..7 (inside the window from position 0) agree
        np.testing.assert_allclose(
            np.asarray(out_w[:, :8]), np.asarray(out_f[:, :8]),
            atol=2e-4, rtol=2e-4,
        )
        ref = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                  vocab_size=512, max_len=32, dtype=jnp.float32,
                  attention_impl="reference", attention_window=8)
        np.testing.assert_allclose(
            np.asarray(jax.jit(ref.apply)(params, toks)), np.asarray(out_w),
            atol=2e-4, rtol=2e-4,
        )
        for impl in ("ring", "zigzag", "ulysses"):
            sp = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                     vocab_size=512, max_len=32, dtype=jnp.float32,
                     attention_impl=impl, sp_axis="sp", attention_window=8)
            with pytest.raises(ValueError, match="flash-only"):
                sp.apply(params, toks, positions=jnp.arange(32))


@functools.cache
def _head_256():
    """The operands at head size 256 and what the three backward forms
    are compared with, computed once: the plain attention's output and
    gradients."""
    q, k, v = qkv(b=1, s=128, h=2, d=256, seed=5)
    weight = jnp.asarray(np.random.RandomState(6).randn(*q.shape),
                         jnp.float32)
    plain = functools.partial(local_attention, causal=True)
    return (q, k, v), weight, out_and_grads(plain, weight, q, k, v)


@pytest.mark.parametrize("backward",
                         ["one_kernel", "dq_resident", "two_passes"])
def test_head_size_256_matches_the_plain_attention(monkeypatch, backward):
    """Twice the head size of any older case and four times a GPT cell's
    (``mla_mixer`` hands the kernels q, k, v of ``[b, s, 20, 256]``):
    the forward and the three gradients against ``local_attention``,
    through the one-kernel backward in both its forms (the second is
    what 8192 keys take at this head size) and through the two passes."""
    names = force(monkeypatch, backward)
    (q, k, v), weight, (plain, want) = _head_256()

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=32)

    kernels = list(pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: flash(*a).sum(), argnums=(0, 1, 2)))(q, k, v).jaxpr))
    assert kernels == ["flash_fwd"] + names
    out, got = out_and_grads(flash, weight, q, k, v)
    np.testing.assert_allclose(out, plain, atol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


# The shapes ``trinitym_train_s8192`` brings, scaled down: 8 query heads a
# key/value head, head size 128, a window that is a multiple of neither
# tile (64 x 32) and, last, S = W + 1: only the first key of the last row
# falls out of the band.
_WINDOW_CASES = [
    ("window_72_of_256", 256, 72),
    ("s_is_window_plus_1", 128, 127),
]


@functools.cache
def _window_oracles(seq, window):
    """The operands of a (sequence, window) and what the three backward
    forms are compared with, computed once: output and gradients of the
    dense masked oracle and of ``local_attention(window=...)``."""
    rng = np.random.RandomState(7)
    mk = lambda heads: jnp.asarray(
        rng.randn(1, seq, heads, 128) * 0.5, jnp.float32)
    q, k, v = mk(8), mk(1), mk(1)
    weight = jnp.asarray(rng.randn(*q.shape), jnp.float32)

    def oracle(q, k, v):
        return TestSlidingWindow._oracle(q, k, v, 128 ** -0.5, window)

    def plain(q, k, v):
        rep = lambda x: jnp.repeat(x, 8, axis=2)
        return local_attention(q, rep(k), rep(v), causal=True,
                               window=window)

    return ((q, k, v), weight, out_and_grads(oracle, weight, q, k, v),
            out_and_grads(plain, weight, q, k, v))


@pytest.mark.parametrize("backward",
                         ["one_kernel", "dq_resident", "two_passes"])
@pytest.mark.parametrize("seq,window", [c[1:] for c in _WINDOW_CASES],
                         ids=[c[0] for c in _WINDOW_CASES])
def test_window_at_grouped_heads_of_128(monkeypatch, backward, seq, window):
    """The banded kernels at 8 query heads a key/value head and head size
    128: the forward and the three gradients against the dense masked
    oracle, through the one-kernel backward in both its forms and
    through the two passes, and ``local_attention(window=...)`` against
    the same oracle."""
    names = force(monkeypatch, backward)
    (q, k, v), weight, oracle, plain = _window_oracles(seq, window)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64,
                               block_k=32, window=window)

    kernels = list(pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: flash(*a).sum(), argnums=(0, 1, 2)))(q, k, v).jaxpr))
    assert kernels == ["flash_fwd"] + names
    out, got = out_and_grads(flash, weight, q, k, v)
    np.testing.assert_allclose(out, oracle[0], atol=2e-5)
    np.testing.assert_allclose(plain[0], oracle[0], atol=2e-5)
    # the band bites: the last row does not see key 0
    full = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    assert float(jnp.abs(full - out)[:, -1].max()) > 1e-4
    for name, a, b in zip(("dq", "dk", "dv"), got, oracle[1]):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)
    for name, a, b in zip(("dq", "dk", "dv"), plain[1], oracle[1]):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)
