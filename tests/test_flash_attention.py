"""Flash-attention kernel + transformer model tests.

The Pallas kernel runs through the interpreter on the CPU test mesh
(identical program, no TPU needed); correctness is against the plain
softmax reference, gradients included — the kernel is advertised as
training-ready.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from flash_oracle import (flash_bwd_blockwise, folded_plan, force_form,
                          live_pairs, plan_of, traced_calls)
from horovod_tpu.models import TransformerConfig, gpt
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel import local_attention


def _qkv(b=2, s=64, h=4, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d), dtype) * 0.3
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        ref = local_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_uneven_blocks(self):
        # S=48 forces _pick_block to drop to a divisor
        q, k, v = _qkv(s=48, seed=1)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        ref = local_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_grads_match_reference(self):
        q, k, v = _qkv(seed=2)
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
        q, k, v = _qkv(seed=3, dtype=jnp.bfloat16)
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
        q, k, v = _qkv()
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
        params = model.init(jax.random.PRNGKey(0), tokens)
        logits = model.apply(params, tokens)
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
        params = m_flash.init(jax.random.PRNGKey(0), tokens)
        np.testing.assert_allclose(
            np.asarray(m_flash.apply(params, tokens)),
            np.asarray(m_ref.apply(params, tokens)),
            atol=2e-4, rtol=2e-4,
        )

    def test_causality(self):
        """Changing a future token must not change past logits."""
        model = gpt(**self._cfg(dtype=jnp.float32))
        rng = np.random.RandomState(2)
        t1 = rng.randint(0, 1024, (1, 16))
        t2 = t1.copy()
        t2[0, -1] = (t2[0, -1] + 1) % 1024
        params = model.init(jax.random.PRNGKey(0), jnp.asarray(t1))
        l1 = model.apply(params, jnp.asarray(t1))
        l2 = model.apply(params, jnp.asarray(t2))
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
        params = model_1d.init(jax.random.PRNGKey(0), tokens[:, :8])

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
        l1, g1 = jax.value_and_grad(loss_1d)(params)
        l2 = loss_sp(params, tokens, targets)
        np.testing.assert_allclose(float(l1), float(l2), atol=1e-5, rtol=1e-5)
        g2 = jax.grad(
            lambda p: loss_sp(p, tokens, targets)
        )(params)
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
        params = model_1d.init(jax.random.PRNGKey(0), tokens[:, :8])
        ref = model_1d.apply(params, tokens)

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
        params = m_flash.init(jax.random.PRNGKey(0), tokens)
        assert "wpe" not in params["params"], "rope model must have no wpe"
        np.testing.assert_allclose(
            np.asarray(m_flash.apply(params, tokens)),
            np.asarray(m_ref.apply(params, tokens)),
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
        params = full.init(jax.random.PRNGKey(0), toks)
        out_w = win.apply(params, toks)
        out_f = full.apply(params, toks)
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
            np.asarray(ref.apply(params, toks)), np.asarray(out_w),
            atol=2e-4, rtol=2e-4,
        )
        for impl in ("ring", "zigzag", "ulysses"):
            sp = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                     vocab_size=512, max_len=32, dtype=jnp.float32,
                     attention_impl=impl, sp_axis="sp", attention_window=8)
            with pytest.raises(ValueError, match="flash-only"):
                sp.apply(params, toks, positions=jnp.arange(32))


# (id, causal, window, q heads, kv heads, block_q, block_k) at S=256, d=64:
# at least 2 Q tiles and 4 K tiles everywhere.
_ROW_STAT_CASES = [
    ("noncausal", False, None, 2, 2, 128, 64),
    ("causal", True, None, 2, 2, 128, 64),
    ("window96", True, 96, 2, 2, 128, 64),
    ("mqa_4_on_1", True, None, 4, 1, 128, 64),
    ("mqa_4_on_1_noncausal", False, None, 4, 1, 128, 64),
    ("window24_under_a_k_tile", True, 24, 2, 2, 128, 64),
    ("causal_4x8_tiles", True, None, 2, 2, 64, 32),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "causal,window,h,hkv,bq,bk", [c[1:] for c in _ROW_STAT_CASES],
    ids=[c[0] for c in _ROW_STAT_CASES],
)
def test_row_statistics_across_tiles(causal, window, h, hkv, bq, bk, dtype):
    """The per-query statistics (running max and sum, the saved
    logsumexp, the backward's delta) over several K tiles: scores GROW
    along the key axis, so the running max changes in every tile and
    every earlier partial sum is rescaled each time.  Output, saved lse
    and dq/dk/dv against float32 references on the same inputs."""
    from horovod_tpu.ops.flash_attention import _flash_fwd_kernel

    b, s, d = 1, 256, 64
    rng = np.random.RandomState(5)
    f32 = jnp.float32
    # q positive, k a ramp along the sequence: q.k rises by about 3 per
    # 64 keys after the 1/8 scale
    q = jnp.asarray(0.5 + 0.3 * np.abs(rng.randn(b, s, h, d)), dtype)
    ramp = (np.arange(s) / s)[None, :, None, None]
    k = jnp.asarray(2.0 * ramp + 0.1 * rng.randn(b, s, hkv, d), dtype)
    v = jnp.asarray(rng.randn(b, s, hkv, d), dtype)
    wgt = jnp.asarray(rng.randn(b, s, h, d), f32)
    qf, kf, vf = (x.astype(f32) for x in (q, k, v))
    rep = lambda t: jnp.repeat(t, h // hkv, axis=2)
    scale = d ** -0.5

    def scores(q, k):
        st = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) * scale
        q_pos = jnp.arange(s)[:, None]
        k_pos = jnp.arange(s)[None, :]
        if causal:
            st = jnp.where(k_pos > q_pos, -jnp.inf, st)
        if window is not None:
            st = jnp.where(k_pos < q_pos - (window - 1), -jnp.inf, st)
        return st

    def reference(q, k, v):
        if window is None:
            return local_attention(q, rep(k), rep(v), causal=causal)
        p = jax.nn.softmax(scores(q, k), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, rep(v))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk)

    loss = lambda f: lambda *a: (f(*a).astype(f32) * wgt).sum()
    out = flash(q, k, v)
    want = reference(qf, kf, vf)
    got_g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(loss(reference), argnums=(0, 1, 2))(qf, kf, vf)

    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, s, d)
    folded = fold(q), fold(k), fold(v)
    _, lse = _flash_fwd_kernel(
        *folded, folded_plan(*folded, causal, bq, bk, h, hkv, window),
        scale, True)
    want_lse = jax.nn.logsumexp(scores(qf, kf), axis=-1).reshape(-1, s)
    # the running max moved in every K tile of the last row
    last_row = np.asarray(scores(qf, kf))[0, 0, -1]
    tile_max = last_row.reshape(-1, bk).max(-1)
    live = np.isfinite(tile_max)
    assert live.sum() >= (1 if window else 4)
    assert np.all(np.diff(tile_max[live]) > 0)

    # float32: rounding of sums only (seen: 4e-7 out, 3e-6 gradients);
    # bfloat16: the outputs, and the o that the backward's delta reads,
    # are rounded to 8 bits of mantissa (seen: 0.004 out, 0.017 dq)
    tol, grad_tol = (5e-6, 2e-5) if dtype == jnp.float32 else (1e-2, 3e-2)
    assert out.dtype == dtype and lse.dtype == f32
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5, rtol=2e-5)

    def close(name, a, r, tol):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert a.shape == r.shape, name
        err = np.abs(a - r).max() / np.abs(r).max()
        assert err <= tol, f"{name}: {err:.3g} of the largest entry"

    close("out", out, want, tol)
    for name, a, r in zip(("dq", "dk", "dv"), got_g, want_g):
        close(name, a, r, grad_tol)


def _grouped_blockwise(q, k, v, o, lse, do, causal, scale, bk, window, h,
                       hkv):
    """`flash_bwd_blockwise` knows no grouped heads: give every query
    head its own copy of its kv row and fold dk and dv back (each at its
    own width: the values' need not be the keys')."""
    z, s, _ = q.shape
    b, group = z // h, h // hkv
    f32 = jnp.float32
    rep = lambda t: jnp.repeat(
        t.astype(f32).reshape(b, hkv, 1, s, t.shape[-1]), group, 2
    ).reshape(z, s, t.shape[-1])
    dq, dk, dv = flash_bwd_blockwise(q.astype(f32), rep(k), rep(v), o, lse,
                                     do, causal, scale, bk, window=window)
    fold = lambda t: t.reshape(b, hkv, group, s, t.shape[-1]).sum(2).reshape(
        -1, s, t.shape[-1])
    return dq, fold(dk), fold(dv)


# (id, causal, window, q heads, kv heads, S, block_q, block_k, scale)
_BWD_PATH_CASES = [
    ("noncausal", False, None, 2, 2, 64, 16, 16, None),
    ("causal", True, None, 2, 2, 64, 16, 16, None),
    ("window24_of_64", True, 24, 2, 2, 64, 16, 16, None),
    ("gqa_4_to_a_kv_head", True, None, 8, 2, 64, 16, 16, None),
    ("gqa_noncausal", False, None, 8, 2, 64, 16, 16, None),
    ("mqa_4_on_1", True, None, 4, 1, 64, 16, 16, None),
    ("stated_scale", True, None, 4, 1, 64, 16, 16, 0.015625),
    ("nq_3_nk_6", True, None, 2, 2, 48, 16, 8, None),
    ("gqa_window_nq_2_nk_8", True, 20, 4, 2, 64, 32, 8, 0.3),
    ("one_tile", True, None, 2, 1, 32, 32, 32, None),
]


@pytest.mark.parametrize("form", ["dkdv_resident", "dq_resident"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "causal,window,h,hkv,s,bq,bk,scale", [c[1:] for c in _BWD_PATH_CASES],
    ids=[c[0] for c in _BWD_PATH_CASES],
)
def test_one_kernel_backward_matches_two_passes_and_oracle(
        monkeypatch, causal, window, h, hkv, s, bq, bk, scale, dtype, form):
    """The backward as one kernel (dq, dk and dv from one p and ds a
    tile), in both its forms (a kv row's dk and dv accumulators resident
    under the Q tiles; a kv row's dq resident under the K tiles, which is
    what 8192 keys at head size 256 take), against the two passes it
    replaced and against the blockwise scan.  One kernel and two passes
    add the same float32 terms in the same order (a dk row block gets
    its terms by query head, then Q tile, a dq block by K tile, in all
    three), so they agree to the bit; the scan sums in another order."""
    from dataclasses import replace

    from horovod_tpu.ops import flash_attention as fa

    b, d = 2, 16
    rng = np.random.RandomState(11)
    mk = lambda heads: jnp.asarray(rng.randn(b * heads, s, d) * 0.7, dtype)
    q, do, k, v = mk(h), mk(h), mk(hkv), mk(hkv)
    scale = d ** -0.5 if scale is None else scale
    plan = folded_plan(q, k, v, causal, bq, bk, h, hkv, window)
    o, lse = fa._flash_fwd_kernel(q, k, v, plan, scale, True)

    def backward(plan):
        run = lambda: fa._flash_bwd_pallas(q, k, v, o, lse, do, plan, scale,
                                           True)
        return run(), list(_pallas_calls(jax.make_jaxpr(run)().jaxpr))

    assert plan.bwd_form == "dkdv_resident"
    one, names = backward(replace(plan, bwd_form=form))
    assert names == ["flash_bwd_dkdv"]
    _vmem_limits(monkeypatch, 0)
    plan = folded_plan(q, k, v, causal, bq, bk, h, hkv, window)
    assert (plan.bwd_form, plan.bwd_vmem_bytes) == ("two_passes", 0)
    two, names = backward(plan)
    assert names == ["flash_bwd_dkdv", "flash_bwd_dq"]
    ref = _grouped_blockwise(q, k, v, o, lse, do, causal, scale, bk, window,
                             h, hkv)
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    for name, a, t, r in zip(("dq", "dk", "dv"), one, two, ref):
        assert a.dtype == dtype and a.shape == r.shape, name
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(t, np.float32),
            err_msg=f"{name}: one kernel against two passes")
        for which, got in (("one kernel", a), ("two passes", t)):
            got, want = np.asarray(got, np.float32), np.asarray(r)
            err = np.abs(got - want).max() / np.abs(want).max()
            assert err <= tol, (
                f"{name}, {which}: {err:.3g} of the largest entry")


def _vmem_limits(monkeypatch, limit, ceiling=None):
    """The VMEM a one-kernel backward may state, as the shape gate reads
    it: ``limit`` for the forms in their order and ``ceiling`` for the
    smaller count above it (``None``: no room above the limit, so 0
    leaves the two passes alone)."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_LIMIT", limit)
    monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_CEILING",
                        limit if ceiling is None else ceiling)


def _pallas_calls(jaxpr, what=lambda params: params["name"]):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield what(eqn.params)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub, what)


# (id, q shape [B,S,H,D], kv heads, dtype, scale, the backward's form)
_ONE_KERNEL = ["flash_bwd_dkdv"]
_TWO_PASSES = ["flash_bwd_dkdv", "flash_bwd_dq"]
_GATE_CASES = [
    ("gpt2m_train_8x1024x16x64", (8, 1024, 16, 64), 16, jnp.bfloat16, None,
     "dkdv_resident"),
    ("granite4hm_1x8192x32on8x64", (1, 8192, 32, 64), 8, jnp.bfloat16,
     0.015625, "dkdv_resident"),
    ("trinitym_1x8192x32on4x128", (1, 8192, 32, 128), 4, jnp.bfloat16, None,
     "dkdv_resident"),
    ("longest_kv_row_8192x128", (1, 8192, 4, 128), 2, jnp.bfloat16, None,
     "dkdv_resident"),
    # past a kv row's dk and dv, the row's dq: 4 MiB at head size 64
    ("dq_fits_at_16384x64", (1, 16384, 4, 64), 4, jnp.bfloat16, None,
     "dq_resident"),
    ("over_the_budget_131072x128", (1, 131072, 4, 128), 2, jnp.bfloat16,
     None, "two_passes"),
    ("float32_8192x64_fits", (1, 8192, 2, 64), 2, jnp.float32, None,
     "dkdv_resident"),
    ("float32_16384x64_dq_fits", (1, 16384, 2, 64), 2, jnp.float32, None,
     "dq_resident"),
    # head size 256 (latent attention: 192 + 64 query and key channels,
    # values of 256): a kv row's dk and dv accumulators are four times
    # head size 64's and end at 4096 keys; glm47f_train_s8192's 8192 keep
    # dq resident (8 MiB), which ends at 26624 keys
    ("head_256_4096_keys_fit", (1, 4096, 20, 256), 20, jnp.bfloat16, None,
     "dkdv_resident"),
    ("glm47f_1x8192x20x256", (1, 8192, 20, 256), 20, jnp.bfloat16, None,
     "dq_resident"),
    ("head_256_longest_dq_26624", (1, 26624, 2, 256), 2, jnp.bfloat16, None,
     "dq_resident"),
    # past 32 MiB in both forms the smaller count decides, up to 48 MiB
    # (PR 44; these two ran the two passes before it): 32.5 MiB of dq
    # here, 37.5 MiB of dk and dv for eight query heads on one
    ("head_256_first_past_the_limit_27136", (1, 27136, 2, 256), 2,
     jnp.bfloat16, None, "dq_resident"),
    ("head_256_grouped_8_on_1_8192", (1, 8192, 8, 256), 1, jnp.bfloat16,
     None, "dkdv_resident"),
    # smallthinker_train_s16384: seven query heads a key/value head, a
    # kv row's dk and dv 36.25 MiB (its dq 60.5)
    ("smallthinker_1x16384x28on4x128", (1, 16384, 28, 128), 4, jnp.bfloat16,
     None, "dkdv_resident"),
    # the longest rows under the ceiling, 47.25 and 48 MiB, and the first
    # past it
    ("longest_under_the_ceiling_22016x128", (1, 22016, 7, 128), 1,
     jnp.bfloat16, None, "dkdv_resident"),
    ("first_over_the_ceiling_22528x128", (1, 22528, 7, 128), 1,
     jnp.bfloat16, None, "two_passes"),
    ("head_256_longest_dq_under_the_ceiling_43008", (1, 43008, 2, 256), 2,
     jnp.bfloat16, None, "dq_resident"),
    ("head_256_first_over_the_ceiling_43520", (1, 43520, 2, 256), 2,
     jnp.bfloat16, None, "two_passes"),
]


@pytest.mark.parametrize(
    "shape,kv_heads,dtype,scale,form", [c[1:] for c in _GATE_CASES],
    ids=[c[0] for c in _GATE_CASES],
)
def test_backward_path_follows_the_shape(shape, kv_heads, dtype, scale, form):
    """Which backward runs is read from the ``pallas_call`` names in the
    differentiated jaxpr, as a device trace would read it: one kernel
    (under the name ``flash_bwd_dkdv``) at every benchmark shape and up
    to the ceiling of what a call may state, ``flash_bwd_dq`` beside it
    only above; and the plan of the same call says the same."""
    from horovod_tpu.ops.flash_attention import flash_plan

    b, s, h, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype)
    kv = jax.ShapeDtypeStruct((b, s, kv_heads, d), dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               interpret=True).astype(jnp.float32).sum()

    assert flash_plan(q, kv, kv, causal=True).bwd_form == form
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    assert list(_pallas_calls(jaxpr.jaxpr)) == ["flash_fwd"] + (
        _TWO_PASSES if form == "two_passes" else _ONE_KERNEL)


# (id, the limit and the ceiling the gate reads, form, gauge
# flash.bwd_kernels, whether the plan keeps dq resident and the MiB it
# states, the backward's names and grids in a layer: a row of heads by the
# 6 live tiles of the 2 x 4 a head's mask holds) at 64 keys of 16
# channels in
# float32 and 32 x 16 tiles, where dk and dv resident count 334 KiB and dq
# resident 204: the limit as it stands; one that only dq fits; none, with
# the ceiling as it stands (the smaller count, stated itself: a whole
# MiB); none and no ceiling
_DQ_FITS = "what dq resident counts"
_GAUGE_CASES = [
    ("dkdv_resident", None, None, "dkdv_resident", 1, 0, 32,
     [("flash_bwd_dkdv", (8, 6))]),
    ("dq_resident", _DQ_FITS, None, "dq_resident", 1, 1, 1,
     [("flash_bwd_dkdv", (8, 6))]),
    ("over_the_limit_the_smaller_count", 0, 48 * 2 ** 20, "dq_resident",
     1, 1, 1, [("flash_bwd_dkdv", (8, 6))]),
    ("two_passes", 0, 0, "two_passes", 2, 0, 0,
     [("flash_bwd_dkdv", (8, 6)), ("flash_bwd_dq", (8, 6))]),
]


@pytest.mark.parametrize(
    "limit,ceiling,form,kernels,dq_resident,vmem_mib,backward",
    [c[1:] for c in _GAUGE_CASES], ids=[c[0] for c in _GAUGE_CASES])
def test_the_gauges_say_which_backward_the_step_holds(
        monkeypatch, limit, ceiling, form, kernels, dq_resident, vmem_mib,
        backward):
    """``flash.bwd_kernels``, set while a two-layer model is traced, and
    the plan of the traced calls (its form, the VMEM it states, the
    value width it was made for) against the ``pallas_call`` names, grids
    and stated VMEM of the model's differentiated jaxpr, at a shape on
    each side of the gates (the limit patched to what a form holds, or
    to nothing): gauge and kernel read one record, the call's
    ``FlashPlan``."""
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.ops import flash_attention as fa

    if limit == _DQ_FITS:
        limit = fa._dq_resident_bwd_vmem_bytes(64, 16, 32, 16, 4, 1)
    if limit is not None:
        _vmem_limits(monkeypatch, limit, ceiling)
    assert plan_of(64, 16, 1, 4, 32, 16).bwd_form == form
    model = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                vocab_size=512, max_len=64, dtype=jnp.float32,
                flash_block_q=32, flash_block_k=16)
    toks = jnp.asarray(
        np.random.RandomState(3).randint(0, 512, (2, 64)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)
    gauge = get_registry().gauge("flash.bwd_kernels", layer_type="attention")
    gauge.set(-1)
    qkv = jax.ShapeDtypeStruct((2, 64, 4, 16), jnp.float32)
    of_the_shape = fa.flash_plan(qkv, qkv, qkv, causal=True, block_q=32,
                                 block_k=16)
    traced = traced_calls(monkeypatch)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.apply(p, toks).sum()))(params)
    calls = list(_pallas_calls(
        jaxpr.jaxpr, lambda p: (p["name"], tuple(p["grid_mapping"].grid))))
    assert calls == [("flash_fwd", (8, 6))] * 2 + backward * 2
    # one plan, made twice a layer: for the gauges and for the kernels
    (shapes, plan), = set(traced)
    assert len(traced) == 4
    assert [gauge.value, plan.bwd_kernels, plan.bwd_form == "dq_resident",
            -(-plan.bwd_vmem_bytes // 2 ** 20)] == [
                kernels, kernels, bool(dq_resident), vmem_mib]
    # the plan holds what the one kernel states, the two passes nothing
    stated = [limit for name, limit in _pallas_calls(
        jaxpr.jaxpr, lambda p: (p["name"], _stated_vmem(p)))
        if name != "flash_fwd"]
    assert stated == ([None] * 4 if form == "two_passes" else
                      [plan.bwd_vmem_bytes] * 2)
    assert all(-(-b // 2 ** 20) == vmem_mib for b in stated if b)
    # values as wide as keys, the head size, 64 / 4
    assert shapes == ((2, 64, 4, 16),) * 3 and plan == of_the_shape
    assert sum(name != "flash_fwd" for name, _ in calls) == 2 * kernels


def _force(monkeypatch, backward):
    """Take the named backward whatever the shape says."""
    from horovod_tpu.ops import flash_attention as fa

    if backward == "two_passes":
        _vmem_limits(monkeypatch, 0)
    elif backward == "dq_resident":
        force_form(monkeypatch, backward)
    return _TWO_PASSES if backward == "two_passes" else _ONE_KERNEL


@pytest.mark.parametrize("backward",
                         ["one_kernel", "dq_resident", "two_passes"])
def test_head_size_256_matches_the_plain_attention(monkeypatch, backward):
    """Twice the head size of any older case and four times a GPT cell's
    (``mla_mixer`` hands the kernels q, k, v of ``[b, s, 20, 256]``):
    the forward and the three gradients against ``local_attention``,
    through the one-kernel backward in both its forms (the second is
    what 8192 keys take at this head size) and through the two passes."""
    names = _force(monkeypatch, backward)
    q, k, v = _qkv(b=1, s=128, h=2, d=256, seed=5)
    weight = jnp.asarray(np.random.RandomState(6).randn(*q.shape),
                         jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=32)

    def plain(q, k, v):
        return local_attention(q, k, v, causal=True)

    kernels = list(_pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: flash(*a).sum(), argnums=(0, 1, 2)))(q, k, v).jaxpr))
    assert kernels == ["flash_fwd"] + names
    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * weight).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * weight).sum(),
                    argnums=(0, 1, 2))(q, k, v)
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
    names = _force(monkeypatch, backward)
    rng = np.random.RandomState(7)
    mk = lambda heads: jnp.asarray(
        rng.randn(1, seq, heads, 128) * 0.5, jnp.float32)
    q, k, v = mk(8), mk(1), mk(1)
    weight = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    scale = 128 ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64,
                               block_k=32, window=window)

    def oracle(q, k, v):
        return TestSlidingWindow._oracle(q, k, v, scale, window)

    def plain(q, k, v):
        rep = lambda x: jnp.repeat(x, 8, axis=2)
        return local_attention(q, rep(k), rep(v), causal=True,
                               window=window)

    kernels = list(_pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: flash(*a).sum(), argnums=(0, 1, 2)))(q, k, v).jaxpr))
    assert kernels == ["flash_fwd"] + names
    np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v), atol=2e-5)
    np.testing.assert_allclose(plain(q, k, v), oracle(q, k, v), atol=2e-5)
    # the band bites: the last row does not see key 0
    full = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    assert float(jnp.abs(full - flash(q, k, v))[:, -1].max()) > 1e-4
    grads = lambda f: jax.grad(lambda *a: (f(*a) * weight).sum(),
                               argnums=(0, 1, 2))(q, k, v)
    want = grads(oracle)
    for name, a, b in zip(("dq", "dk", "dv"), grads(flash), want):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)
    for name, a, b in zip(("dq", "dk", "dv"), grads(plain), want):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)


# ------------------------------------------ values wider than keys (PR 42)
# The value width is the values' own: differential attention reads values
# twice as wide as its keys (phi4mf_train_s8192: 40 query rows of 64 over 20
# key/value rows, values 128).  Grouped heads 2:1 as there, tiles 16 x 8.
_WIDTH_MASKS = [
    ("causal", True, None),
    ("causal_window_20", True, 20),   # a multiple of neither tile
    ("noncausal", False, None),
]


@pytest.mark.parametrize("backward",
                         ["one_kernel", "dq_resident", "two_passes"])
@pytest.mark.parametrize("causal,window", [c[1:] for c in _WIDTH_MASKS],
                         ids=[c[0] for c in _WIDTH_MASKS])
@pytest.mark.parametrize("dv", [32, 8], ids=["values_2d", "values_half_d"])
def test_values_of_another_width_than_the_keys(monkeypatch, dv, causal,
                                               window, backward):
    """``v`` twice and half as wide as ``q`` and ``k`` (16): the forward
    against ``local_attention`` (an einsum, which never asked for one
    width), and dq, dk, dv of each of the three backward forms against the
    blockwise scan at the values' own width; the scale is the keys'."""
    from horovod_tpu.ops import flash_attention as fa

    names = _force(monkeypatch, backward)
    b, s, h, hkv, d, bq, bk = 2, 64, 4, 2, 16, 16, 8
    rng = np.random.RandomState(13)
    mk = lambda heads, width: jnp.asarray(
        rng.randn(b, s, heads, width) * 0.7, jnp.float32)
    q, k, v, do = mk(h, d), mk(hkv, d), mk(hkv, dv), mk(h, dv)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk)

    rep = lambda x: jnp.repeat(x, h // hkv, axis=2)
    out = flash(q, k, v)
    assert out.shape == (b, s, h, dv)
    np.testing.assert_allclose(
        out, local_attention(q, rep(k), rep(v), causal=causal,
                             window=window), atol=2e-5)
    grad = jax.grad(lambda *a: (flash(*a) * do).sum(), argnums=(0, 1, 2))
    assert list(_pallas_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr)) \
        == ["flash_fwd"] + names
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, s, x.shape[3])
    folded = fold(q), fold(k), fold(v)
    o, lse = fa._flash_fwd_kernel(
        *folded, folded_plan(*folded, causal, bq, bk, h, hkv, window),
        d ** -0.5, True)
    want = _grouped_blockwise(fold(q), fold(k), fold(v), o, lse, fold(do),
                              causal, d ** -0.5, bk, window, h, hkv)
    for name, a, r in zip(("dq", "dk", "dv"), grad(q, k, v), want):
        assert a.shape == (b, s, h if name == "dq" else hkv,
                           dv if name == "dv" else d), name
        np.testing.assert_allclose(fold(a), r, atol=2e-5, err_msg=name)


def test_flash_attention_refuses_k_and_v_of_different_rows():
    """The width is v's own; batch, sequence and key/value head count are
    not."""
    q, k, v = _qkv(h=4, d=16)
    with pytest.raises(ValueError, match="matching in batch, sequence"):
        flash_attention(q, k[:, :, :2], v)
    with pytest.raises(ValueError, match="matching in batch, sequence"):
        flash_attention(q, k, v[:, :32])
    with pytest.raises(ValueError, match="head_dim must match"):
        flash_attention(q, k[..., :8], v)


# (id, keys, head size, value width, group, the form, the Q-outermost
# count, the K-outermost count) in bfloat16 at 512 x 256 tiles: the
# benchmark cells' shapes read the bytes they read before the counts took
# a value width (PR 38's tree), and 8192 x (64, 128), the Phi cell's
# one-pass differential call, pads both widths to 128 lanes and reads
# 8192 x 64's count.
_COUNT_CASES = [
    ("gpt2m_1024x64", 1024, 64, None, 1, "dkdv_resident", 6422528,
     4980736),
    ("granite4hm_8192x64", 8192, 64, None, 4, "dkdv_resident", 21102592,
     13107200),
    ("trinitym_8192x128", 8192, 128, None, 8, "dkdv_resident", 21233664,
     38273024),
    ("glm47f_8192x256", 8192, 256, None, 1, "dq_resident", 39321600,
     14680064),
    ("phi4mf_8192x64_values_128", 8192, 64, 128, 2, "dkdv_resident",
     21102592, 8912896),
]


@pytest.mark.parametrize("seq,d,dv,group,form,q_outer,k_outer",
                         [c[1:] for c in _COUNT_CASES],
                         ids=[c[0] for c in _COUNT_CASES])
def test_vmem_counts_at_the_cells_shapes(seq, d, dv, group, form, q_outer,
                                         k_outer):
    from horovod_tpu.ops import flash_attention as fa

    assert fa._fused_bwd_vmem_bytes(seq, d, 512, 256, 2, dv) == q_outer
    assert fa._dq_resident_bwd_vmem_bytes(seq, d, 512, 256, 2, group,
                                          dv) == k_outer
    assert plan_of(seq, d, group, 2, value_dim=dv).bwd_form == form
    if dv is None:
        # a value width that is the head size changes nothing
        assert fa._fused_bwd_vmem_bytes(seq, d, 512, 256, 2, d) == q_outer
        assert fa._dq_resident_bwd_vmem_bytes(seq, d, 512, 256, 2, group,
                                              d) == k_outer
        assert plan_of(seq, d, group, 2) == plan_of(
            seq, d, group, 2, 512, 256, d)
    else:
        # the dk and dv halves each at their own padded lanes: values of
        # 512 put 8192 keys over the Q-outermost form's limit, and dq,
        # 64 wide, stays resident under the K tiles
        assert fa._fused_bwd_vmem_bytes(seq, d, 512, 256, 2, 512) \
            > fa._FUSED_BWD_VMEM_LIMIT > q_outer
        assert plan_of(seq, d, group, 2,
                       value_dim=512).bwd_form == "dq_resident"


def _stated_vmem(eqn_params):
    """The ``vmem_limit_bytes`` a ``pallas_call`` states (``None``: the
    compiler's default)."""
    return eqn_params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes


def _kernel_signature(eqn_params):
    """What a ``pallas_call`` holds that the chip would see: its name and
    grid, the kernel's block and scratch refs, its outputs and the VMEM
    it states."""
    return (eqn_params["name"], tuple(eqn_params["grid_mapping"].grid),
            [str(v.aval) for v in eqn_params["jaxpr"].invars],
            [str(a) for a in eqn_params["out_avals"]],
            _stated_vmem(eqn_params))


def test_a_call_with_values_as_wide_as_keys_is_the_program_it_was():
    """granite's call (32 query over 8 key/value heads of 64 at 8192
    tokens, bfloat16): the ``pallas_call``s of the differentiated jaxpr,
    listed as PR 38's tree made them but for the forward's K and V
    blocks, whole kv rows since PR 46, and for the grids, which since
    PR 49 walk a head's 272 live tiles of 16 x 32 from a table of three
    int32 columns in SMEM, the calls' first operands.  The five cells
    that send ``dv == d`` run this program; only the value width of a
    call that has one moves a block, a scratch buffer or an output."""
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16)

    def calls(v):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=True
            ).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, kv, v)
        return list(_pallas_calls(jaxpr.jaxpr, _kernel_signature))

    bf = lambda *shape: "Ref{bfloat16[%s]}" % ",".join(map(str, shape))
    stat = "Ref{float32[1,1,1,512]}"
    vmem = lambda *shape: "Ref<vmem>{float32[%s]}" % ",".join(map(str, shape))
    arr = lambda *shape: "bfloat16[%s]" % ",".join(map(str, shape))
    table = ["Ref<smem>{int32[272]}"] * 3

    def listed(dv):
        return [
            ("flash_fwd", (32, 272),
             table + [bf(1, 512, 64), bf(1, 8192, 64), bf(1, 8192, dv),
              bf(1, 512, dv), stat,
              vmem(dv, 512), vmem(1, 512), vmem(1, 512)],
             [arr(32, 8192, dv), "float32[32,16,1,512]"], None),
            ("flash_bwd_dkdv", (32, 272),
             table + [bf(1, 512, 64), bf(1, 256, 64), bf(1, 256, dv),
              bf(1, 512, dv), stat, stat,
              bf(1, 512, 64), bf(1, 8192, 64), bf(1, 8192, dv),
              vmem(64, 512), vmem(8192, 64), vmem(8192, dv)],
             [arr(32, 8192, 64), arr(8, 8192, 64), arr(8, 8192, dv)],
             32 * 2 ** 20),
        ]

    assert calls(kv) == listed(64)
    wide = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    assert calls(wide) == listed(128)


# (id, q shape [B,S,H,D], kv heads, value width, the backward's form, the
# MiB it states): the attention call of every flash cell.  The first five
# serve seven cells and read what PR 43's tree read, form and stated
# limit (32 MiB, the module's constant); the sixth left the two passes in
# PR 44 and states its own count.
_CELL_CALLS = [
    ("gpt2m_train_s1024_and_dp4", (8, 1024, 16, 64), 16, 64,
     "dkdv_resident", 32),
    ("granite4hm_train_s8192", (1, 8192, 32, 64), 8, 64, "dkdv_resident",
     32),
    ("glm47f_train_s8192", (1, 8192, 20, 256), 20, 256, "dq_resident", 32),
    ("trinitym_train_s8192", (1, 8192, 32, 128), 4, 128, "dkdv_resident",
     32),
    ("phi4mf_train_s8192", (1, 8192, 40, 64), 20, 128, "dkdv_resident", 32),
    ("smallthinker_train_s16384", (1, 16384, 28, 128), 4, 128,
     "dkdv_resident", 37),
]


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window_512"])
@pytest.mark.parametrize("shape,kv_heads,dv,form,mib",
                         [c[1:] for c in _CELL_CALLS],
                         ids=[c[0] for c in _CELL_CALLS])
def test_every_cells_call_keeps_its_form_and_the_vmem_it_states(
        shape, kv_heads, dv, form, mib, window):
    """The one backward ``pallas_call`` of each cell's attention shape,
    full and banded, read from the differentiated jaxpr: its grid says
    the form (Q tile outermost ``(z, live)``, a head's live tiles; K
    tile outermost ``(z_kv, live * group)``) and its params the
    ``vmem_limit_bytes``.  A call that fit 32 MiB before PR 44 states
    those 32 MiB still: the table changes the grid and adds the
    prefetched columns, never the VMEM a call states."""
    from horovod_tpu.ops import flash_attention as fa

    b, s, h, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, kv_heads, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, kv_heads, dv), jnp.bfloat16)
    group, live = h // kv_heads, len(live_pairs(s, 512, 256, window))
    plan = fa.flash_plan(q, k, v, causal=True, window=window)
    assert (plan.bwd_form, plan.bwd_vmem_bytes) == (form, mib * 2 ** 20)
    assert (plan.tiles_grid, plan.tiles_mask) == (
        b * h * live, b * h * (s // 512) * (s // 256))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: flash_attention(
            *a, causal=True, window=window, interpret=True
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    calls = list(_pallas_calls(jaxpr.jaxpr, lambda p: (
        p["name"], tuple(p["grid_mapping"].grid), _stated_vmem(p))))
    grid = ((b * h, live) if form == "dkdv_resident"
            else (b * kv_heads, live * group))
    assert calls == [("flash_fwd", (b * h, live),
                      plan.fwd_vmem_bytes or None),
                     ("flash_bwd_dkdv", grid, mib * 2 ** 20)]


def _forward_call(eqn_params):
    """What a ``flash_fwd`` ``pallas_call`` holds of K and V: the rows of
    their blocks (the whole kv row where it is resident, ``block_k``
    where tiles stream) and the VMEM the call states."""
    k_block, v_block = eqn_params["grid_mapping"].block_mappings[1:3]
    rows = {int(m.block_shape[1].block_size) for m in (k_block, v_block)}
    assert len(rows) == 1 and k_block.pipeline_mode is None
    return rows.pop(), _stated_vmem(eqn_params)


# (id, causal, window, h, hkv, s, d, dv, bq, bk)
_FWD_FORM_CASES = [
    ("causal", True, None, 2, 2, 64, 16, 16, 32, 16),
    ("noncausal", False, None, 2, 2, 64, 16, 16, 32, 16),
    ("window_20", True, 20, 2, 2, 64, 16, 16, 32, 8),
    ("window_of_one_tile", True, 8, 2, 2, 64, 16, 16, 16, 16),
    ("mqa_4_on_1", True, None, 4, 1, 64, 16, 16, 32, 16),
    ("gqa_7_to_a_kv_head", True, None, 14, 2, 64, 16, 16, 32, 16),
    ("gqa_7_window", True, 24, 7, 1, 96, 16, 16, 32, 16),
    ("values_128_on_keys_64", True, None, 4, 2, 64, 64, 128, 32, 16),
    ("values_128_on_keys_64_window", True, 20, 4, 2, 64, 64, 128, 32, 16),
    ("values_narrower", False, None, 2, 1, 64, 16, 8, 32, 16),
    ("one_tile", True, None, 2, 1, 32, 16, 16, 32, 32),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "causal,window,h,hkv,s,d,dv,bq,bk", [c[1:] for c in _FWD_FORM_CASES],
    ids=[c[0] for c in _FWD_FORM_CASES])
def test_resident_forward_equals_streamed_to_the_bit(
        monkeypatch, causal, window, h, hkv, s, d, dv, bq, bk, dtype):
    """The forward with a kv row's K and V resident in VMEM (whole-row
    blocks whose index moves once a kv row, the body slicing its tile)
    against the streamed tiles it replaced wherever a row fits (forced
    here by a limit no row fits, as ``_vmem_limits`` forces the two
    backward passes): the same tiles, the same float32 sums in the same
    order, so ``o`` and ``lse`` are equal to the bit; and against the
    plain attention."""
    from horovod_tpu.ops import flash_attention as fa

    b = 2
    rng = np.random.RandomState(5)
    mk = lambda heads, width: jnp.asarray(
        rng.randn(b * heads, s, width) * 0.7, dtype)
    q, k, v = mk(h, d), mk(hkv, d), mk(hkv, dv)

    def forward(resident):
        plan = folded_plan(q, k, v, causal, bq, bk, h, hkv, window)
        assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes) == (resident, 0)
        run = lambda: fa._flash_fwd_kernel(q, k, v, plan, d ** -0.5, True)
        call, = _pallas_calls(jax.make_jaxpr(run)().jaxpr, _forward_call)
        return call, run()

    call, resident = forward(True)
    assert call == (s, None)
    _vmem_limits(monkeypatch, 0)
    call, streamed = forward(False)
    assert call == (bk, None)
    for part, a, t in zip(("o", "lse"), resident, streamed):
        assert a.dtype == t.dtype and a.shape == t.shape
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(t, np.float32),
            err_msg=f"{part}: resident against streamed")
    unfold = lambda x: x.reshape(b, -1, s, x.shape[-1]).transpose(0, 2, 1, 3)
    kx, vx = (jnp.repeat(unfold(x).astype(jnp.float32), h // hkv, axis=2)
              for x in (k, v))
    want = local_attention(unfold(q).astype(jnp.float32), kx, vx,
                           causal=causal, window=window)
    err = np.abs(np.asarray(unfold(resident[0]), np.float32)
                 - np.asarray(want)).max()
    assert err <= (2e-5 if dtype == jnp.float32 else 3e-2), err


# (id, keys, head size, value width, itemsize, resident, the MiB the call
# states, the bytes counted) at 512 x 256 tiles: every flash cell's
# forward holds its kv row resident; the two whose rows are 16 MiB in
# their two buffers (GLM's 8192 keys of 256, SmallThinker's 16384 of 128)
# pass the compiler's default scoped limit and state their count, the
# others state nothing.  The TPU compiler asks 18.12 and 17.31 MiB for
# those two (sandbox compiles for a described v5e, PR 46).
_FWD_COUNT_CASES = [
    ("gpt2m_1024x64", 1024, 64, 64, 2, True, 0, 3342336),
    ("granite4hm_8192x64", 8192, 64, 64, 2, True, 0, 10682368),
    ("glm47f_8192x256", 8192, 256, 256, 2, True, 20, 20512768),
    ("trinitym_8192x128", 8192, 128, 128, 2, True, 0, 10813440),
    ("phi4mf_8192x64_values_128", 8192, 64, 128, 2, True, 0, 10813440),
    ("smallthinker_16384x128", 16384, 128, 128, 2, True, 19, 19202048),
    # the first row that states a limit, the longest that stays resident
    # and the first whose tiles stream, at head sizes 128 and 256 and in
    # float32
    ("last_that_states_nothing_13824x128", 13824, 128, 128, 2, True, 0,
     16580608),
    ("first_that_states_its_count_14336x128", 14336, 128, 128, 2, True, 17,
     17104896),
    ("longest_resident_row_30208x128", 30208, 128, 128, 2, True, 32,
     33357824),
    ("first_streamed_row_30720x128", 30720, 128, 128, 2, False, 0,
     33882112),
    ("longest_resident_row_14336x256", 14336, 256, 256, 2, True, 32,
     33095680),
    ("first_streamed_row_14848x256", 14848, 256, 256, 2, False, 0,
     34144256),
    ("float32_longest_resident_row_14848x128", 14848, 128, 128, 4, True, 32,
     33357824),
    ("float32_first_streamed_row_15360x128", 15360, 128, 128, 4, False, 0,
     34406400),
]


@pytest.mark.parametrize("seq,d,dv,itemsize,resident,mib,count",
                         [c[1:] for c in _FWD_COUNT_CASES],
                         ids=[c[0] for c in _FWD_COUNT_CASES])
def test_forward_plan_at_the_cells_shapes(seq, d, dv, itemsize, resident,
                                          mib, count):
    from horovod_tpu.ops import flash_attention as fa

    assert fa._fwd_resident_vmem_bytes(seq, d, dv, 512, 256,
                                       itemsize) == count
    plan = plan_of(seq, d, 1, itemsize, value_dim=dv)
    assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes) == (
        resident, mib * 2 ** 20)
    assert plan == plan_of(seq, d, 1, itemsize, 512, 256, dv)
    # the rule: resident wherever the count fits the limit the backward's
    # forms share, a stated MiB only past the default scoped limit
    assert resident == (count <= fa._FUSED_BWD_VMEM_LIMIT)
    assert (mib > 0) == (fa._DEFAULT_SCOPED_VMEM < count
                         <= fa._FUSED_BWD_VMEM_LIMIT)
    if mib:
        assert 0 <= mib * 2 ** 20 - count < 2 ** 20


# (id, the limit and the default scoped limit the gate reads, the
# forward's plan at the shape, the same of the traced calls' plan as
# resident 1 / 0 and whole MiB, the rows of the K and V blocks) at 64
# keys of 16 channels in float32 and
# 32 x 16 tiles, where the resident forward counts 264 KiB: the limits as
# they stand; a default the count passes (the count stated, a whole MiB);
# no room
_FWD_GAUGE_CASES = [
    ("resident", None, None, (True, 0), 1, 0, 64),
    ("resident_stating_its_count", None, 0, (True, 2 ** 20), 1, 1, 64),
    ("streamed", 0, None, (False, 0), 0, 0, 16),
]


@pytest.mark.parametrize("limit,default,plan,resident,vmem_mib,rows",
                         [c[1:] for c in _FWD_GAUGE_CASES],
                         ids=[c[0] for c in _FWD_GAUGE_CASES])
def test_the_gauges_say_which_forward_the_step_holds(
        monkeypatch, limit, default, plan, resident, vmem_mib, rows):
    """The plan of the calls a two-layer model traces (whether the
    forward holds a kv row resident, the VMEM it states) against the K
    and V blocks and the stated VMEM of the ``flash_fwd`` calls in the
    model's jaxpr, on each side of the forward's gates: whoever asks and
    the kernel read one record, the call's ``FlashPlan``."""
    from horovod_tpu.ops import flash_attention as fa

    if default is not None:
        monkeypatch.setattr(fa, "_DEFAULT_SCOPED_VMEM", default)
    if limit is not None:
        _vmem_limits(monkeypatch, limit)
    at_the_shape = plan_of(64, 16, 1, 4, 32, 16)
    assert (at_the_shape.fwd_kv_resident, at_the_shape.fwd_vmem_bytes) == plan
    model = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                vocab_size=512, max_len=64, dtype=jnp.float32,
                flash_block_q=32, flash_block_k=16)
    toks = jnp.asarray(
        np.random.RandomState(3).randint(0, 512, (2, 64)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)
    asked = traced_calls(monkeypatch)
    jaxpr = jax.make_jaxpr(lambda p: model.apply(p, toks))(params)
    calls = list(_pallas_calls(jaxpr.jaxpr, lambda p: (
        p["name"], tuple(p["grid_mapping"].grid)) + _forward_call(p)))
    # the grid walks the 6 live tiles of a head's 2 x 4 in either form
    assert calls == [("flash_fwd", (8, 6), rows, plan[1] or None)] * 2
    (_, traced), = set(asked)
    assert len(asked) == 4
    assert [int(traced.fwd_kv_resident),
            traced.fwd_vmem_bytes // 2 ** 20] == [resident, vmem_mib]


def test_local_attention_refuses_a_window_it_cannot_mean():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="causal"):
        local_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window >= 1"):
        local_attention(q, k, v, causal=True, window=0)


# --------------------------- the grids walk the live tiles alone (PR 49)
# A head's live (Q tile, K tile) pairs come from a table the kernels
# prefetch to SMEM, not from two grid axes and a predicate.  Tiles 32 x 16
# over 96 keys (3 x 6 a head): no mask, the causal half, and windows under
# both tiles, of a K tile exactly, and a multiple of neither.
_WALK_SEQ, _WALK_BQ, _WALK_BK, _WALK_D = 96, 32, 16, 16
_WALK_MASKS = [
    ("noncausal", False, None),
    ("causal", True, None),
    ("window_8_under_the_tiles", True, 8),
    ("window_16_a_k_tile", True, 16),
    ("window_20_no_multiple", True, 20),
]
# (id, query heads, key/value heads, value width)
_WALK_HEADS = [
    ("h_is_hkv", 2, 2, 16),
    ("grouped_3_values_32", 6, 2, 32),
]
_WALK_FORMS = ["dkdv_resident", "dq_resident", "two_passes"]
# sha256[:16] over o, lse, dq, dk, dv of the PARENT's kernels (commit
# 9ced719, the whole nq x nk rectangle under a ``needed`` predicate) on
# ``_walk_inputs`` in float32, by (mask, heads) and, in ``_WALK_FORMS``'
# order, backward form: made by running ``_walk_results`` with the
# parent's package on the path.  The
# bfloat16 cases pin none (a bfloat16 result's last bit is the host CPU's:
# PR 48); they are held, as every case is, to this tree's own rectangle
# (``live_tiles=None``), which is the parent's walk, in this process.
_WALK_PARENT_DIGESTS = {
    ("noncausal", "h_is_hkv"): (
        "76aa08ded2c9e1a3", "76aa08ded2c9e1a3", "76aa08ded2c9e1a3"),
    ("noncausal", "grouped_3_values_32"): (
        "bea937c82125c187", "bea937c82125c187", "bea937c82125c187"),
    ("causal", "h_is_hkv"): (
        "c5e141324daaddf7", "c5e141324daaddf7", "c5e141324daaddf7"),
    ("causal", "grouped_3_values_32"): (
        "d7227a652c7734c1", "d7227a652c7734c1", "d7227a652c7734c1"),
    ("window_8_under_the_tiles", "h_is_hkv"): (
        "6dbf65d7680af580", "6dbf65d7680af580", "6dbf65d7680af580"),
    ("window_8_under_the_tiles", "grouped_3_values_32"): (
        "87d28254e926a078", "87d28254e926a078", "87d28254e926a078"),
    ("window_16_a_k_tile", "h_is_hkv"): (
        "343f8130bd91f4da", "343f8130bd91f4da", "343f8130bd91f4da"),
    ("window_16_a_k_tile", "grouped_3_values_32"): (
        "44afd7410a17617b", "44afd7410a17617b", "44afd7410a17617b"),
    ("window_20_no_multiple", "h_is_hkv"): (
        "cc45cc91adb5148d", "cc45cc91adb5148d", "cc45cc91adb5148d"),
    ("window_20_no_multiple", "grouped_3_values_32"): (
        "5975c32a82d5fa3d", "5975c32a82d5fa3d", "5975c32a82d5fa3d"),
}


def _walk_inputs(h, hkv, dv, dtype):
    rng = np.random.RandomState(49)
    mk = lambda heads, width: jnp.asarray(
        rng.randn(2 * heads, _WALK_SEQ, width) * 0.7, dtype)
    return mk(h, _WALK_D), mk(hkv, _WALK_D), mk(hkv, dv), mk(h, dv)


def _walk_results(plan, q, k, v, do):
    """o, lse, dq, dk, dv of the kernels under ``plan``, folded."""
    from horovod_tpu.ops import flash_attention as fa

    scale = _WALK_D ** -0.5

    @jax.jit
    def run(q, k, v, do):
        o, lse = fa._flash_fwd_kernel(q, k, v, plan, scale, True)
        return (o, lse) + tuple(fa._flash_bwd_pallas(
            q, k, v, o, lse, do, plan, scale, True))

    return run(q, k, v, do)


def _walk_digest(results):
    import hashlib

    sha = hashlib.sha256()
    for a in results:
        sha.update(np.asarray(a).tobytes())
    return sha.hexdigest()[:16]


def _walk_plan(causal, window, h, hkv, dv, dtype, form):
    from dataclasses import replace

    q, k, v, _ = (jax.ShapeDtypeStruct(x.shape, x.dtype)
                  for x in _walk_inputs(h, hkv, dv, dtype))
    plan = folded_plan(q, k, v, causal, _WALK_BQ, _WALK_BK, h, hkv, window)
    assert (plan.bwd_form, plan.fwd_kv_resident) == ("dkdv_resident", True)
    return replace(plan, bwd_form=form,
                   bwd_vmem_bytes=0 if form == "two_passes"
                   else plan.bwd_vmem_bytes)


@pytest.mark.parametrize("form", _WALK_FORMS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("h,hkv,dv", [c[1:] for c in _WALK_HEADS],
                         ids=[c[0] for c in _WALK_HEADS])
@pytest.mark.parametrize("causal,window", [c[1:] for c in _WALK_MASKS],
                         ids=[c[0] for c in _WALK_MASKS])
def test_the_table_walk_is_the_rectangle_to_the_bit(
        request, causal, window, h, hkv, dv, dtype, form):
    """Forward and every backward form over the table of live tiles
    against the same kernels over the whole rectangle under the predicate
    (``live_tiles=None``: what a table past the SMEM limit falls back to,
    and what the parent ran): a dead step added nothing, so ``o``,
    ``lse``, ``dq``, ``dk``, ``dv`` are equal to the bit, streamed
    forward and resident alike; in float32 equal to the digest pinned
    from the parent's kernels; and within the standing tolerances of the
    plain attention and the blockwise scan."""
    from dataclasses import replace

    plan = _walk_plan(causal, window, h, hkv, dv, dtype, form)
    q, k, v, do = _walk_inputs(h, hkv, dv, dtype)
    assert plan.live_tiles is not None
    assert plan.tiles_grid == plan.tiles_live == \
        2 * h * len(plan.live_tiles)
    table = _walk_results(plan, q, k, v, do)
    others = {"the rectangle": replace(plan, live_tiles=None)}
    if form == _WALK_FORMS[0]:   # the forward knows no backward form
        others["the streamed forward's table"] = replace(
            plan, fwd_kv_resident=False)
    names = ("o", "lse", "dq", "dk", "dv")
    for which, other in others.items():
        for name, a, r in zip(names, table,
                              _walk_results(other, q, k, v, do)):
            assert a.dtype == r.dtype and a.shape == r.shape, name
            assert np.asarray(a).tobytes() == np.asarray(r).tobytes(), (
                f"{name}: the table against {which}")
    mask, heads = request.node.callspec.id.split("-")[:2]
    if dtype == jnp.float32:
        assert _walk_digest(table) == _WALK_PARENT_DIGESTS[mask, heads][
            _WALK_FORMS.index(form)], "the parent's kernels, to the bit"
    scale = _WALK_D ** -0.5
    unfold = lambda x, heads: x.reshape(2, heads, _WALK_SEQ, -1).transpose(
        0, 2, 1, 3).astype(jnp.float32)
    rep = lambda x: jnp.repeat(unfold(x, hkv), h // hkv, axis=2)
    want = local_attention(unfold(q, h), rep(k), rep(v), causal=causal,
                           window=window)
    err = np.abs(np.asarray(unfold(table[0], h)) - np.asarray(want)).max()
    assert err <= (2e-5 if dtype == jnp.float32 else 3e-2), err
    ref = _grouped_blockwise(q, k, v, table[0], table[1], do, causal, scale,
                             _WALK_BK, window, h, hkv)
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    for name, a, r in zip(names[2:], table[2:], ref):
        got, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.abs(got - r).max() <= tol * np.abs(r).max(), name


def _dense_live_pairs(seq, bq, bk, causal, window):
    """A mask's live tile pairs from the positions themselves."""
    qp, kp = np.arange(seq)[:, None], np.arange(seq)[None, :]
    sees = np.ones((seq, seq), bool)
    if causal:
        sees = kp <= qp
        if window is not None:
            sees &= kp >= qp - (window - 1)
    tiles = sees.reshape(seq // bq, bq, seq // bk, bk).any((1, 3))
    return [(int(i), int(j)) for i, j in zip(*np.nonzero(tiles))]


# (id, S, block_q, block_k, causal, window, query heads a key/value head)
_TABLE_CASES = [
    (mask + "_group_%d" % group, _WALK_SEQ, _WALK_BQ, _WALK_BK, causal,
     window, group)
    for mask, causal, window in _WALK_MASKS for group in (1, 3)
] + [
    ("window_72_of_256_tiles_64x32_group_8", 256, 64, 32, True, 72, 8),
    ("s_is_window_plus_1_group_8", 128, 64, 32, True, 127, 8),
    ("tiles_16x16_window_of_one_tile", 64, 16, 16, True, 16, 2),
    ("k_tiles_wider_than_q_tiles", 64, 8, 32, True, 20, 2),
    # the cells' calls at 512 x 256: Trinity's band, Phi's, SmallThinker's
    # band and triangle, LFM2's triangle
    ("trinitym_8192_window_2048_group_8", 8192, 512, 256, True, 2048, 8),
    ("phi4mf_8192_window_512_group_2", 8192, 512, 256, True, 512, 2),
    ("smallthinker_16384_window_4096_group_7", 16384, 512, 256, True, 4096,
     7),
    ("smallthinker_16384_full_group_7", 16384, 512, 256, True, None, 7),
    ("lfm2_32768_full_group_4", 32768, 512, 256, True, None, 4),
]


@pytest.mark.parametrize("seq,bq,bk,causal,window,group",
                         [c[1:] for c in _TABLE_CASES],
                         ids=[c[0] for c in _TABLE_CASES])
def test_the_table_holds_the_live_tiles_once_in_walk_order(
        seq, bq, bk, causal, window, group):
    """The plan's table is exactly the pairs the mask keeps (from the
    positions themselves at small sizes, from the tiles' distances at the
    cells'), each once, Q tile major with K tiles ascending: the order
    the rectangle walked them; ``len(table) * batch * heads`` is
    ``tiles_live`` and ``tiles_grid``, the rectangle ``tiles_mask``; and
    the Q-major columns mark each Q row's first and last live tile."""
    from horovod_tpu.ops import flash_attention as fa

    plan = plan_of(seq, 64, group, 2, bq, bk, causal=causal, window=window,
                   rows=2)
    pairs = live_pairs(seq, bq, bk, window, causal)
    if seq <= 256:
        assert pairs == _dense_live_pairs(seq, bq, bk, causal, window)
    assert list(plan.live_tiles) == pairs == sorted(set(pairs))
    assert all(fa._tile_live(i, j, bq, bk, causal, plan.window)
               for i, j in pairs)
    heads = 2 * group
    assert plan.tiles_live == plan.tiles_grid == heads * len(pairs)
    assert plan.tiles_mask == heads * (seq // bq) * (seq // bk)
    qi, kj, edges = fa._q_major_table(plan.live_tiles)
    assert list(zip(qi.tolist(), kj.tolist())) == pairs
    assert qi.dtype == kj.dtype == edges.dtype == np.int32
    for t, (i, j) in enumerate(pairs):
        row = [jj for ii, jj in pairs if ii == i] if seq <= 256 else None
        first = t == 0 or pairs[t - 1][0] != i
        last = t == len(pairs) - 1 or pairs[t + 1][0] != i
        assert edges[t] == first + 2 * last, (t, i, j)
        if row:
            assert (first, last) == (j == row[0], j == row[-1])


@pytest.mark.parametrize("seq,bq,bk,causal,window,group",
                         [c[1:] for c in _TABLE_CASES],
                         ids=[c[0] for c in _TABLE_CASES])
def test_the_k_major_table_writes_each_dq_block_at_its_last_live_k_tile(
        seq, bq, bk, causal, window, group):
    """The K-outermost kernel's table: for each K tile in turn, for each
    query head of the group, the Q tiles that see it, Q tiles ascending
    (the order the rectangle walked them, so dk and dv sum in the
    parent's order).  dk and dv's accumulators open on a K tile's first
    step and close on its last; a ``(g, i)`` pair's dq opens on its first
    live K tile and is written on its LAST (the rectangle wrote at ``j ==
    nk - 1``, which under a mask most pairs never reach live); and dq's
    block index, the pair written next, holds still up to each write and
    moves right after it, so a block is one run of steps and goes to HBM
    once."""
    from horovod_tpu.ops import flash_attention as fa

    nq = seq // bq
    plan = plan_of(seq, 64, group, 2, bq, bk, causal=causal, window=window)
    pairs = set(plan.live_tiles)
    kj, qg, qi, edges, fg, fi = fa._k_major_table(plan.live_tiles, nq, group)
    steps = list(zip(kj.tolist(), qg.tolist(), qi.tolist()))
    assert steps == [(j, g, i) for j in range(seq // bk)
                     for g in range(group)
                     for i in range(nq) if (i, j) in pairs]
    assert len(steps) == group * len(pairs) == len(set(steps))
    first_j, last_j = {}, {}
    for i, j in sorted(pairs):
        first_j.setdefault(i, j)
        last_j[i] = j
    total = len(steps)
    for t, (j, g, i) in enumerate(steps):
        assert edges[t] == (
            (t == 0 or steps[t - 1][0] != j)
            + 2 * (t == total - 1 or steps[t + 1][0] != j)
            + 4 * (j == first_j[i]) + 8 * (j == last_j[i])), (t, j, g, i)
    closing = [t for t in range(total) if edges[t] & 8]
    assert sorted((steps[t][1], steps[t][2]) for t in closing) == [
        (g, i) for g in range(group) for i in range(nq)]
    assert closing[-1] == total - 1
    blocks = list(zip(fg.tolist(), fi.tolist()))
    start = 0
    for t in closing:   # each write ends the run of its own block index
        assert set(blocks[start:t + 1]) == {steps[t][1:]}, t
        start = t + 1


def test_a_table_past_the_smem_limit_keeps_the_rectangle(monkeypatch):
    """The plan says from the shape whether the call's largest table (the
    forward's three columns, the K-outermost backward's six a query head
    of the group) fits ``_TILE_TABLE_SMEM_LIMIT``: every cell's call does,
    LFM2's 16 640 steps the largest at 390 KiB; 131 072 keys in two
    passes do not, and keep the rectangle, as any call does with the
    limit at nothing."""
    from horovod_tpu.ops import flash_attention as fa

    assert fa._TILE_TABLE_SMEM_LIMIT == 512 * 2 ** 10
    lfm2 = plan_of(32768, 64, 4, rows=8)
    assert lfm2.bwd_form == "dq_resident"
    assert 4 * fa._k_major_table(lfm2.live_tiles, 64, 4).size == 399_360
    for seq, d, group, window in [(1024, 64, 1, None), (8192, 64, 4, None),
                                  (8192, 256, 1, None), (8192, 128, 8, 2048),
                                  (8192, 64, 2, 512), (16384, 128, 7, 4096),
                                  (16384, 128, 7, None)]:
        plan = plan_of(seq, d, group, window=window)
        assert plan.live_tiles and plan.tiles_grid == plan.tiles_live
    long = plan_of(131072, 128, 2)
    assert (long.bwd_form, long.live_tiles) == ("two_passes", None)
    assert long.tiles_grid == long.tiles_mask == 2 * 256 * 512
    assert long.tiles_live == 2 * 65792
    monkeypatch.setattr(fa, "_TILE_TABLE_SMEM_LIMIT", 0)
    small = plan_of(64, 16, 1, 4, 32, 16)
    assert (small.live_tiles, small.tiles_live, small.tiles_grid,
            small.tiles_mask) == (None, 6, 8, 8)
    q, k, v = _qkv(b=1, s=64, h=2, d=16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
        *a, causal=True, block_q=32, block_k=16).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    assert list(_pallas_calls(jaxpr.jaxpr, lambda p: (
        p["name"], tuple(p["grid_mapping"].grid),
        p["grid_mapping"].num_index_operands))) == [
            ("flash_fwd", (2, 8), 0), ("flash_bwd_dkdv", (2, 8), 0)]
