"""Trinity-Mini's mechanisms on the training path (``model_type: afmoe``):
sliding-window and full attention layers mixed, rotary positions in the
window layers only, a norm over each head of q and k, a sigmoid output
gate, four norms a block, heads wider than ``emb_dim // num_heads``, and
8-of-128 routed experts as a chip's share.  The program
(``models/transformer.py``) against the benchmark's own plain reference
(``benchmark/configs/trinity-mini.reference.py``) on seeded weights; the
shares of the experts adding up to the uncut layer; the published values
of the named size and the count of its cut; the scopes and gauges the new
layers bring; the trees of the other named sizes unchanged.  (The paths
that refuse the new settings and what ``__post_init__`` refuses are cases
of the parametrised tests in ``tests/test_glm_moe_mla.py``.)
All on the CPU at small sizes: hidden 64, 8 query heads over 2 key/value
heads of 16 (q is 128 wide), a window of 8 in 32 tokens, two window
layers and a full one, one dense layer before two expert layers of 16
experts of width 32 of which 4 are held from expert 4 on, 3 a token.
"""

import functools
import hashlib
import importlib.util
import os
from dataclasses import fields, replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (GPT_CONFIGS, Block,
                                            TransformerConfig, gpt)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs",
                        "trinity-mini.reference.py")
    spec = importlib.util.spec_from_file_location("trinity_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

KINDS = ("sliding_attention", "sliding_attention", "full_attention")
SMALL = dict(
    num_layers=3, layer_types=KINDS, vocab_size=256, emb_dim=64,
    num_heads=8, num_kv_heads=2, head_size=16, attention_window=8,
    mlp_ratio=3, dense_layers_first=1, routed_experts=16, routed_held=4,
    routed_first_held=4, routed_top_k=3, routed_width=32, max_len=64,
    embedding_multiplier=8.0, attention_impl="reference",
    # several tiles a row, and a window that is a multiple of neither
    flash_block_q=16, flash_block_k=4, dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, sliding_window=8, layer_types=list(KINDS),
    rope_theta=10000.0, rms_norm_eps=1e-5, num_experts=4,
    first_held_expert=4, num_experts_per_tok=3, route_scale=2.826)
SEQ = 32
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, 256)


def small_model(**overrides):
    return gpt("trinity-mini", **{**SMALL, **overrides})


def init(model, key=1):
    """Seeded variables; the router ten times its initial size so that
    the scores spread over (0, 1) at this width, and the norms' weights
    away from 1."""
    variables = jax.jit(model.init)(jax.random.PRNGKey(key),
                                    TOKENS[:, :SEQ])

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router" in name:
            return leaf * 10.0
        if "scale" in name:
            return leaf + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), leaf.shape)
        return leaf

    return {**variables, "params": jax.tree_util.tree_map_with_path(
        moved, variables["params"])}


def program_logprob(model, variables, tokens):
    logits = model.apply(
        {k: variables[k] for k in ("params", "moe_state")}, tokens[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def program_loss(model, variables, tokens):
    return -program_logprob(model, variables, tokens).mean()


def _outcome(logprob, loss, variables):
    """The labels' log-probabilities, the loss and the gradient of the
    loss in ``params``, from one trace."""
    def run(v):
        grads = jax.grad(lambda p: loss({**v, "params": p}))(v["params"])
        return logprob(v), loss(v), grads

    with jax.default_matmul_precision("highest"):
        return jax.jit(run)(variables)


@functools.cache
def sound():
    """The seeded variables and what the plain reference gives for them,
    computed once a module (the attention's form changes no variable)."""
    variables = init(small_model())
    batch = {"tokens": TOKENS}
    return variables, _outcome(
        lambda v: ref.logprob(CONFIG, v, batch),
        lambda v: ref.loss(CONFIG, v, batch), variables)


@functools.cache
def program(attention):
    model = small_model(attention_impl=attention)
    return _outcome(lambda v: program_logprob(model, v, TOKENS),
                    lambda v: program_loss(model, v, TOKENS), sound()[0])


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_model_matches_plain_reference(attention):
    """The loss, every label's log-probability and every leaf of the
    gradient, with the reference attention (``local_attention`` with its
    window) and through the flash kernels (the Pallas interpreter)."""
    _, (want_logp, want_loss, want_grads) = sound()
    got_logp, got_loss, got_grads = program(attention)
    np.testing.assert_allclose(got_logp, want_logp, atol=2e-4)
    np.testing.assert_allclose(got_loss, want_loss, atol=1e-5)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert flat_got.keys() == flat_want.keys()
    for path, want_leaf in flat_want.items():
        scale = float(jnp.abs(want_leaf).max())
        assert scale > 0, f"{path}: the reference's gradient is zero"
        np.testing.assert_allclose(
            flat_got[path], want_leaf, atol=2e-4 * scale + 1e-7,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("depart", [
    "window_ignored", "window_off_by_one", "gate_dropped",
    "rope_in_full_layer", "post_norm_dropped", "multiplier_dropped"])
def test_comparison_fails_on_a_seeded_departure(depart):
    variables, (_, sound_loss, _) = sound()
    got = program("reference")[1]
    with jax.default_matmul_precision("highest"):
        departed = jax.jit(lambda v: ref.loss(
            CONFIG, v, {"tokens": TOKENS}, depart))(variables)
    assert abs(got - sound_loss) < 1e-5
    assert abs(got - departed) > 1e-4


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips hold sixteen experts each of 128, eight a token.
    Every share computes the same attention and the same shared expert,
    and its own experts' part of the routed sum: the routed parts of all
    eight, with the rest counted ONCE, are the whole layer as the uncut
    reference gives it.  A norm on the branch's output is not additive,
    so the shares are summed where the deployment sums them, before it:
    the blocks here are built without the post-norms, and the reference's
    layer is put together from its own attention and its own uncut expert
    layer the same way."""
    from horovod_tpu.ops.rope import rope_tables

    cfg = small_model(routed_experts=128, routed_held=16,
                      routed_first_held=0, routed_top_k=8,
                      post_norms=False).cfg
    kind = "sliding_attention"
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    positions = jnp.arange(SEQ)
    tabs = rope_tables(positions, cfg.rope_dim, cfg.rope_theta)

    def block(first, held):
        return Block(replace(cfg, routed_first_held=first,
                             routed_held=held), kind, "routed")

    variables = jax.jit(block(0, 128).init)(jax.random.PRNGKey(4), x,
                                            positions, tabs)
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0
    bias = variables["moe_state"]["bias"]

    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 16],
                "experts_fc2": p["experts_fc2"][first:first + 16]
                * fc2_scale}
        return block(first, 16).apply(
            {"params": mine, "moe_state": {"bias": bias}}, x, positions,
            tabs)

    config = {**CONFIG, "num_experts": 128, "first_held_expert": 0,
              "num_experts_per_tok": 8}
    with jax.default_matmul_precision("highest"):
        alike = share(0, fc2_scale=0.0)   # the stream, attention, shared
        total = alike + sum(share(first) - alike
                            for first in range(0, 128, 16))
        after = x + ref._attention(
            config, p, ref._rms_norm(x, p["ln1"]["scale"], 1e-5), kind,
            None)
        uncut = after + ref._experts(
            config, p, bias, ref._rms_norm(after, p["ln2"]["scale"], 1e-5))
        one = share(16)
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    # and one share alone is NOT the layer: it leaves out 112 experts
    assert float(jnp.abs(one - uncut).max()) > 1e-2


@pytest.mark.parametrize("skew,overflowed", [(0.0, False), (10.0, True)])
def test_eight_of_128_on_the_row_bound_is_the_whole_buffers_layer(
        skew, overflowed, monkeypatch):
    """Trinity-Mini's routing at a small size: 8 a token of 128 experts,
    16 held from expert 16 on, 256 tokens in bfloat16.  Two even shares
    of the 2048 slots are 512 rows, one row tile of the grouped matmul, a
    quarter of the buffer as in the cell.  Under the bound, and with a
    selection bias that sends every token to three held experts over it,
    ``y`` and the gradients of the tokens, the router and both expert
    matrices are those of the computation on all 2048 rows: every bit
    (over the bound ``y`` and the tokens' and the router's gradients to
    the last bit: ``tests/test_glm_moe_mla.py:_assert_last_bit`` has
    why)."""
    from test_glm_moe_mla import _assert_bitwise, _assert_last_bit

    from horovod_tpu.parallel import moe

    k = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(k[0], (256, 32)).astype(jnp.bfloat16)
    router = jax.random.normal(k[1], (32, 128)) * 0.3
    bias = jax.random.uniform(k[2], (128,), minval=-0.05, maxval=0.05)
    bias = bias.at[20:23].add(skew)
    fc1 = jax.random.normal(k[3], (16, 32, 48)) * 0.2
    fc2 = jax.random.normal(k[4], (16, 24, 32)) * 0.2
    probe = jax.random.normal(k[5], x.shape)
    assert moe.row_bound(256, 8, 16, 128) == 512

    def outcome():
        def run(x, router, fc1, fc2):
            y, routing = moe.routed_experts(
                x, router, bias, fc1, fc2, top_k=8, scaling=2.826,
                first_held=16)
            return (y.astype(jnp.float32) * probe).sum(), (y, routing)

        (_, (y, routing)), grads = jax.value_and_grad(
            run, argnums=(0, 1, 2, 3), has_aux=True)(x, router, fc1, fc2)
        return y, routing, grads

    y, routing, grads = outcome()
    assert bool(routing.overflowed) is overflowed
    assert (int(routing.group_sizes[:16].sum()) > 512) is overflowed
    assert int(routing.dropped) == 0
    monkeypatch.setattr(moe, "ROW_BOUND_SHARES", 10 ** 6)   # no bound
    whole_y, _, whole_grads = outcome()

    _assert_bitwise(grads[2:], whole_grads[2:])
    (_assert_last_bit if overflowed else _assert_bitwise)(
        (y, grads[:2]), (whole_y, whole_grads[:2]))
    assert float(jnp.abs(grads[1]).max()) > 0


PUBLISHED = dict(
    vocab_size=200192, num_layers=32, emb_dim=2048, num_heads=32,
    kv_heads=4, head_dim=128, attention_window=2048, rope_theta=10000.0,
    norm_eps=1e-5, routed_experts=128, held_experts=128, routed_top_k=8,
    routed_width=1024, routed_scaling=2.826, shared_experts=1,
    dense_layers_first=2, mtp_modules=0, max_len=131072,
    tie_embeddings=False, use_bias=False, norm="rmsnorm", mlp="silu_gated",
    pos_embedding="rope", rope_layer_types=("sliding_attention",),
    qk_norm=True, attention_gate=True, post_norms=True,
    embedding_multiplier=2048 ** 0.5, remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS["trinity-mini"]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    assert cfg.mlp_ratio * cfg.emb_dim == 6144
    assert cfg.layer_types == tuple(
        "full_attention" if i % 4 == 3 else "sliding_attention"
        for i in range(32))
    assert [cfg.ffn_type(i) for i in (0, 1, 2, 31)] == [
        "dense", "dense", "routed", "routed"]
    assert cfg.window_of("sliding_attention") == 2048
    assert cfg.window_of("full_attention") is None
    assert cfg.rotates("sliding_attention")
    assert not cfg.rotates("full_attention")


def test_the_cut_counts_705473792_parameters():
    """The benchmark's cut from the named size: depth 32 -> 5 (one dense
    layer and one whole period of expert layers), 16 of 128 experts held,
    an eighth of the vocabulary; every width as published (ISSUE 34 has
    the sum)."""
    model = gpt("trinity-mini", num_layers=5, dense_layers_first=1,
                layer_types=("sliding_attention",) * 4 + ("full_attention",),
                routed_held=16, vocab_size=25024)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    p = shapes["params"]
    attention = sum(count(p["block0"][k]) for k in (
        "qkv", "proj", "gate", "q_norm", "k_norm"))
    assert attention == 27_263_232
    assert count(p["block0"]) == 65_020_160
    for i in (1, 2, 3, 4):
        assert count(p[f"block{i}"]) == 134_488_320
    assert count(p["wte"]) == count(p["head"]) == 51_249_152
    assert count(p) == 705_473_792
    # the selection bias and the counters are state: no gradient, no moment
    assert count(shapes["moe_state"]) == 4 * 128
    # per expert layer: rows of 16 held experts, rows dropped, the load
    # of all 128, and the steps in which the layer passed its row bound
    assert count(shapes["moe_stats"]) == 4 * (16 + 1 + 128 + 1)


def test_the_defaults_are_gpt2s():
    """Every new setting defaults to GPT-2's block: no head size of its
    own, one mask and one kind of position for the whole model, no head
    norms, no gate, two norms a block."""
    cfg = TransformerConfig()
    assert (cfg.head_size, cfg.rope_layer_types, cfg.qk_norm,
            cfg.attention_gate, cfg.post_norms) == (
                None, None, False, False, False)
    assert cfg.head_dim == cfg.emb_dim // cfg.num_heads == 64
    assert cfg.window_of("attention") is None
    assert replace(cfg, attention_window=8).window_of(None) == 8
    assert not cfg.rotates("attention")
    assert replace(cfg, pos_embedding="rope").rotates("attention")
    names = {f.name for f in fields(cfg)}
    assert {"head_size", "rope_layer_types", "qk_norm", "attention_gate",
            "post_norms"} <= names


# leaves and a digest of the sorted ``path:shape:dtype`` lines of the
# whole variable tree, taken on the commit before this file existed
TREES = {"small": (149, "6cdd1d23a276d671"),
         "granite-4.0-h-micro": (458, "3fbd116a94e7256c"),
         # 47 expert layers' ``overflow_steps`` (collection "moe_stats")
         # beside the 864 leaves it had; ``params`` as they were
         "glm-4.7-flash": (911, "02dcf7089015504d")}


@pytest.mark.parametrize("size", sorted(TREES))
def test_the_other_named_sizes_build_the_trees_they_built(size):
    model = gpt(size, attention_impl="reference")
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32)))
    lines = sorted(
        f"{jax.tree_util.keystr(path)}:{tuple(leaf.shape)}:{leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes))
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]
    assert (len(lines), digest) == TREES[size]


def test_a_block_makes_the_new_modules_only_where_asked():
    plain = gpt("nano", attention_impl="reference")
    tree = jax.eval_shape(lambda: plain.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert set(tree["block0"]) == {"ln1", "qkv", "proj", "ln2", "fc1", "fc2"}
    tree = jax.eval_shape(lambda: small_model().init(
        jax.random.PRNGKey(0), TOKENS[:, :SEQ]))["params"]
    assert set(tree["block0"]) == {
        "ln1", "qkv", "q_norm", "k_norm", "gate", "proj", "post_attn_norm",
        "ln2", "fc1", "fc2", "post_mlp_norm"}
    assert tree["block0"]["qkv"]["kernel"].shape == (64, 128 + 2 * 32)
    assert tree["block0"]["gate"]["kernel"].shape == (64, 128)
    assert tree["block0"]["q_norm"]["scale"].shape == (16,)
    assert tree["block0"]["proj"]["kernel"].shape == (128, 64)


def test_the_window_layers_carry_their_scopes_and_tile_counts():
    """A step traced through the flash kernels names the window layers'
    attention call ``attn_window`` and the gate ``attn_gate`` (the full
    layer's call carries no window scope), and leaves, by layer type,
    the tiles its grid walks and those that do work."""
    from horovod_tpu.obs.registry import get_registry
    from flash_oracle import plan_of

    model = small_model(attention_impl="flash")
    variables = init(model)
    text = jax.jit(lambda v, t: program_loss(model, v, t)).lower(
        variables, TOKENS).as_text(debug_info=True)
    assert "block0/attn/attn_window/" in text
    assert "block2/attn/attn_gate/" in text
    assert "block2/attn/attn_window" not in text
    registry = get_registry()
    rows = 2 * 8
    mask = rows * (SEQ // 16) * (SEQ // 4)
    live = {kind: registry.gauge("flash.tiles_live", layer_type=kind).value
            for kind in set(KINDS)}
    for kind in set(KINDS):
        assert registry.gauge("flash.tiles_mask",
                              layer_type=kind).value == mask
        # the grid walks the live tiles alone (PR 49)
        assert registry.gauge("flash.tiles_grid",
                              layer_type=kind).value == live[kind]
    # q tile 0 sees k tiles 0-3; q tile 1 (rows 16-31) k tiles 4-7 and,
    # in a window layer, of the earlier ones only those that hold keys
    # 9.. (tiles 2 and 3): 4 + 6 against 4 + 8
    assert live["full_attention"] == rows * 12
    assert live["sliding_attention"] == rows * 10
    tiles = lambda plan: (plan.tiles_live, plan.tiles_grid,
                          plan.tiles_mask)
    assert tiles(plan_of(SEQ, 16, 1, 4, 16, 4, window=8, rows=rows)) == (
        rows * 10, rows * 10, mask)
    assert tiles(plan_of(8192, 128)) == (272, 272, 512)
    assert tiles(plan_of(8192, 128, window=2048)) == (140, 140, 512)
