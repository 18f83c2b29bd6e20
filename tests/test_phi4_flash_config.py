"""Phi-4-mini-flash-reasoning's configuration (``models/transformer.py``):
the published values of the named size and the counts of its cuts, the
defaults, the modules a block makes, the scopes and gauges the new layers
bring, the paths and settings that refuse, the trees of the other named
sizes unchanged.  (``tests/test_phi4_flash.py`` holds the numbers against
the plain reference.)
"""

import hashlib
from dataclasses import fields, replace

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models.transformer import (GPT_CONFIGS, LAYER_TYPES,
                                            TransformerConfig, gpt)
from horovod_tpu.ops.selective_scan import kept_mib
from test_phi4_flash import (CUT, KINDS, NAME, SEQ, TOKENS, init,
                             program_loss, small_model)


# --------------------------------------------- the named size and its cut


PUBLISHED = dict(
    vocab_size=200064, num_layers=32, emb_dim=2560, max_len=262144,
    num_heads=40, kv_heads=20, head_dim=64, attention_window=512,
    norm="layernorm", norm_eps=1e-5, use_bias=True, mlp_bias=False,
    ffn_bias=False, mlp="silu_gated", tie_embeddings=True,
    pos_embedding="none", differential_attention=True, shared_kv_layer=17,
    memory_layer=16, first_layer_index=0, ssm_width=5120, ssm_state=16,
    ssm_conv=4, ssm_dt_rank=160, remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS[NAME]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    assert cfg.mlp_ratio * cfg.emb_dim == 10240
    kinds = cfg.layer_types
    assert kinds[0:16:2] == ("selective_scan",) * 8
    assert kinds[1:16:2] == ("sliding_attention",) * 8
    assert kinds[16:18] == ("selective_scan", "full_attention")
    assert kinds[18::2] == ("gmu",) * 7
    assert kinds[19::2] == ("cross_attention",) * 7
    assert [cfg.hands_on(i) for i in (15, 16, 17, 18)] == [
        None, "memory", "kv", None]
    assert kinds[14:22] == KINDS


def _count(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


# The benchmark's cut (published layers 14-19: ISSUE 39's rule took the
# fallback, PERF.md section 4 says why) and the issue's first choice
# (layers 14-21, two readers of each shared value).
@pytest.mark.parametrize("layers,total", [(6, 697_094_272),
                                          (8, 893_728_256)])
def test_the_cut_counts_its_parameters(layers, total):
    """From the named size: the layers kept with their published indices
    and an eighth of the vocabulary; every width as published (ISSUE 39
    has the sums)."""
    cut = {**CUT, "num_layers": layers, "layer_types": KINDS[:layers]}
    model = gpt(NAME, attention_impl="reference", vocab_size=25008, **cut)
    p = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    mixer = lambda blk: _count(blk) - sum(
        _count(blk[k]) for k in ("ln1", "ln2", "fc1", "fc2"))
    assert _count(p["block0"]["fc1"]) + _count(p["block0"]["fc2"]) \
        == 78_643_200
    assert [mixer(p[f"block{i}"]) for i in range(6)] == [
        41_241_600, 19_668_864, 41_241_600, 19_668_864, 26_214_400,
        13_112_704]
    assert [_count(p[f"block{i}"]) for i in range(layers)] == [
        119_895_040, 98_322_304, 119_895_040, 98_322_304,
        104_867_840, 91_766_144, 104_867_840, 91_766_144][:layers]
    assert _count(p["wte"]) == 64_020_480
    assert "head" not in p and "wpe" not in p
    assert _count(p) == total


def test_the_named_size_counts_3_85_billion_parameters():
    model = gpt(NAME, attention_impl="reference")
    p = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    assert _count(p) == (9 * 119_895_040 + 9 * 98_322_304
                         + 7 * 104_867_840 + 7 * 91_766_144
                         + 200_064 * 2560 + 5_120) == 3_852_562_944


def test_the_defaults_are_gpt2s():
    cfg = TransformerConfig()
    assert (cfg.mlp_bias, cfg.differential_attention, cfg.first_layer_index,
            cfg.shared_kv_layer, cfg.memory_layer, cfg.ssm_width,
            cfg.ssm_dt_rank) == (None, False, 0, None, None, 0, 0)
    assert cfg.ffn_bias is True and not replace(cfg, use_bias=False).ffn_bias
    assert cfg.hands_on(0) is None
    assert {"selective_scan", "gmu", "cross_attention"} <= set(LAYER_TYPES)
    assert {"mlp_bias", "differential_attention", "first_layer_index",
            "shared_kv_layer", "memory_layer", "ssm_width",
            "ssm_dt_rank"} <= {f.name for f in fields(cfg)}


# leaves and a digest of the sorted ``path:shape:dtype`` lines of the
# whole variable tree, taken on the commit before this file existed
TREES = {"small": (149, "6cdd1d23a276d671"),
         "granite-4.0-h-micro": (458, "3fbd116a94e7256c"),
         "glm-4.7-flash": (911, "02dcf7089015504d"),
         "trinity-mini": (595, "881c866566da5b95")}


def _tree_digest(size):
    model = gpt(size, attention_impl="reference")
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32)))
    lines = sorted(
        f"{jax.tree_util.keystr(path)}:{tuple(leaf.shape)}:{leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes))
    return (len(lines),
            hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16])


@pytest.mark.parametrize("size", sorted(TREES))
def test_the_other_named_sizes_build_the_trees_they_built(size):
    assert _tree_digest(size) == TREES[size]


def test_a_block_makes_the_new_modules_only_where_asked():
    tree = jax.eval_shape(lambda: small_model().init(
        jax.random.PRNGKey(0), TOKENS[:, :SEQ]))["params"]
    common = {"ln1", "ln2", "fc1", "fc2"}
    differential = {"lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
                    "subln", "proj"}
    assert set(tree["block0"]) == common | {
        "in_proj", "conv_kernel", "conv_bias", "x_proj", "dt_proj", "A_log",
        "D", "out_proj"}
    assert set(tree["block1"]) == set(tree["block3"]) == (
        common | differential | {"qkv"})
    assert set(tree["block4"]) == common | {"in_proj", "out_proj"}
    assert set(tree["block5"]) == common | differential | {"q"}
    assert tree["block0"]["A_log"].shape == (128, 16)
    assert tree["block0"]["x_proj"]["kernel"].shape == (128, 4 + 32)
    assert set(tree["block0"]["dt_proj"]) == {"kernel", "bias"}
    assert tree["block1"]["qkv"]["kernel"].shape == (64, 64 + 2 * 32)
    assert set(tree["block1"]["qkv"]) == {"kernel", "bias"}
    assert set(tree["block1"]["fc1"]) == {"kernel"}        # mlp_bias false
    assert tree["block1"]["subln"]["scale"].shape == (16,)
    assert tree["block5"]["q"]["kernel"].shape == (64, 64)
    assert tree["block4"]["in_proj"]["kernel"].shape == (64, 128)


def test_the_new_layers_carry_their_scopes_and_gauges(monkeypatch):
    """A step traced through the kernels names the scan
    ``ssm/selective_scan``, a memory unit ``gmu``, a cross layer's
    attention call ``attn/attn_cross`` and differential attention's own
    arithmetic ``attn/attn_diff`` (the window layer's call keeps
    ``attn_window``), and leaves the readers of each handed-on value and
    what a scan keeps."""
    from flash_oracle import traced_calls
    from horovod_tpu.obs.registry import get_registry

    model = small_model(attention_impl="flash")
    variables = init(model)
    calls = traced_calls(monkeypatch)
    text = jax.jit(lambda v, t: program_loss(model, v, t)).lower(
        variables, TOKENS).as_text(debug_info=True)
    for scope in ("block0/ssm/selective_scan/", "block1/attn/attn_window/",
                  "block1/attn/attn_diff/", "block3/attn/attn_diff/",
                  "block4/gmu/", "block5/attn/attn_cross/",
                  "block7/attn/attn_diff/"):
        assert scope in text, scope
    for scope in ("block3/attn/attn_window", "block3/attn/attn_cross",
                  "block4/ssm"):
        assert scope not in text, scope
    registry = get_registry()
    assert registry.gauge("shared.kv_readers").value == 2
    assert registry.gauge("shared.memory_readers").value == 2
    assert registry.gauge("sscan.kept_mib").value == kept_mib(2, SEQ, 128, 16)
    # one call a layer over the pairs' own rows (2 sequences x 8
    # sub-heads, not four times the pairs), values as wide as a pair
    rows = 2 * 8
    for kind in ("sliding_attention", "full_attention", "cross_attention"):
        assert registry.gauge("flash.tiles_mask", layer_type=kind).value \
            == rows * (SEQ // 16) * (SEQ // 4)
        # the grid walks the live tiles alone (PR 49)
        assert registry.gauge("flash.tiles_grid", layer_type=kind).value \
            == registry.gauge("flash.tiles_live", layer_type=kind).value
    # every differential call's values, 2 x 8 wide on keys of 8
    assert {(q[3], k[3], v[3]) for (q, k, v), _ in calls} == {(8, 8, 16)}
    assert registry.gauge("flash.tiles_live",
                          layer_type="cross_attention").value == rows * 12
    assert registry.gauge("flash.tiles_live",
                          layer_type="sliding_attention").value == rows * 10


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention",
                                  "cross_attention"])
def test_a_differential_layer_is_one_flash_call_at_the_pairs_shape(
        monkeypatch, kind):
    """At the published widths and the benchmark cell's 8192 tokens (only
    traced): 40 sub-heads of 64 over 20, so ONE ``flash_fwd`` over 40
    rows, its keys 64 wide and its values and output 128, and nothing
    stacks four groups of heads before it (the parent's call ran each
    score map twice, over 80 rows on 40)."""
    from flash_oracle import traced_calls
    from horovod_tpu.models.transformer import _attend_differential

    cfg = GPT_CONFIGS[NAME]
    asked = traced_calls(monkeypatch)
    assert cfg.attention_impl == "flash"
    shaped = lambda heads: jax.ShapeDtypeStruct((1, 8192, heads, 64),
                                                jnp.bfloat16)
    lambdas = [jnp.full((64,), 0.1)] * 4
    jaxpr = jax.make_jaxpr(lambda q, k, v: _attend_differential(
        cfg, q, k, v, jnp.arange(8192), kind, lambdas=lambdas,
        subln=lambda t: t, lambda_init=0.5))(
            shaped(40), shaped(20), shaped(20))
    assert jaxpr.out_avals[0].shape == (1, 8192, 20, 128)
    equations = list(_equations(jaxpr.jaxpr))
    calls = [e.params for e in equations
             if e.primitive.name == "pallas_call"]
    assert [c["name"] for c in calls] == ["flash_fwd"]
    # after the table's three columns (PR 49) q, k, v, o: since PR 46 a
    # kv row's K and V whole, fetched once a row
    blocks = [v.aval.shape for v in calls[0]["jaxpr"].invars[3:7]]
    assert blocks == [(1, 512, 64), (1, 8192, 64), (1, 8192, 128),
                      (1, 512, 128)]
    # 40 rows by a head's live tiles of 16 x 32 (PR 49: the rectangle
    # before it)
    assert tuple(calls[0]["grid_mapping"].grid) == (
        40, 62 if kind == "sliding_attention" else 272)
    assert [a.shape for a in calls[0]["out_avals"]][0] == (40, 8192, 128)
    joins = [len(e.invars) for e in equations
             if e.primitive.name == "concatenate"]
    assert joins and max(joins) == 2, joins
    # what the benchmark's builder leaves in ran["flash_tiles"]: half the
    # stacked call's 40 960 walked and 21 760 / 4 960 live tiles
    from horovod_tpu.obs.registry import get_registry

    gauge = lambda name: get_registry().gauge(name, layer_type=kind).value
    assert gauge("flash.tiles_mask") == 20480
    assert gauge("flash.tiles_live") == gauge("flash.tiles_grid") == (
        2480 if kind == "sliding_attention" else 10880)
    # and the plan of that call: values of 128 on keys of 64, the kv row
    # resident, nothing stated
    ((q, k, v), plan), = set(asked)
    assert (q[3], k[3], v[3]) == (64, 64, 128)
    assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes) == (True, 0)
    assert (plan.tiles_live, plan.tiles_grid, plan.tiles_mask) == (
        gauge("flash.tiles_live"), gauge("flash.tiles_grid"),
        gauge("flash.tiles_mask"))


# ------------------------------------------------------------- refusals


def _refusals():
    from horovod_tpu.models import decode
    from horovod_tpu.models.transformer import raw_block_forward
    from horovod_tpu.parallel import pipeline, tensor_parallel
    from horovod_tpu.serve.engine import SlotEngine

    x = jnp.zeros((1, 8, 64))
    return {
        "generate": lambda c, t: decode.generate(c, {}, t, 4),
        "prefill": lambda c, t: decode.prefill(c, {}, t),
        "init_cache": lambda c, t: decode.init_cache(c, 1),
        "init_paged_pool": lambda c, t: decode.init_paged_pool(c, 4, 8, 2),
        "slot_engine": lambda c, t: SlotEngine(c, {}, 2),
        "stack_tp_params": lambda c, t: tensor_parallel.stack_tp_params(
            {}, c, 2),
        "tp_gpt_apply": lambda c, t: tensor_parallel.tp_gpt_apply(
            {}, {}, c, t, "tp"),
        "stack_pp_params": lambda c, t: pipeline.stack_pp_params({}, c, 2),
        "pp_gpt_apply": lambda c, t: pipeline.pp_gpt_apply(
            {}, {}, c, t, "pp", microbatches=1),
        "raw_block_forward": lambda c, t: raw_block_forward(
            c, {}, x, jnp.arange(8), None),
    }


# Decode, serve, tensor and pipeline parallelism build GPT-2's block from
# raw weights and carry one stream between layers: they refuse each new
# layer type (by ``layer_types``) and each new setting by name, before
# anything is traced.
@pytest.mark.parametrize("setting", [
    "selective_scan", "gmu", "cross_attention", "differential_attention",
    "mlp_bias", "first_layer_index"])
@pytest.mark.parametrize("path", sorted(_refusals()))
def test_paths_refuse_what_they_cannot_run(path, setting):
    gpt2 = gpt("nano").cfg
    scans = dict(ssm_width=128, ssm_dt_rank=4, ssm_state=16)
    cfg = {"selective_scan": replace(
               gpt2, layer_types=("selective_scan", "attention",
                                  "attention"), **scans),
           "gmu": replace(
               gpt2, layer_types=("selective_scan", "gmu", "attention"),
               memory_layer=0, **scans),
           "cross_attention": replace(
               gpt2, layer_types=("attention", "cross_attention",
                                  "attention"), shared_kv_layer=0),
           "differential_attention": replace(
               gpt2, differential_attention=True),
           "mlp_bias": replace(gpt2, mlp_bias=False),
           "first_layer_index": replace(gpt2, first_layer_index=14),
           }[setting]
    with pytest.raises(ValueError, match=setting):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("override,message", [
    ({"ssm_dt_rank": 0}, "needs positive ssm_width"),
    ({"shared_kv_layer": None}, "'cross_attention' layer reads"),
    ({"shared_kv_layer": 2}, "must name a layer of type attention or"),
    ({"shared_kv_layer": 5}, "before the first of them"),
    ({"memory_layer": None}, "'gmu' layer reads"),
    ({"memory_layer": 1}, "must name a layer of type selective_scan"),
    ({"memory_layer": 4}, "before the first of them"),
    ({"layer_types": KINDS[:4], "num_layers": 4},
     "be None where there is none"),
    ({"num_heads": 7, "num_kv_heads": 7}, "pairs its heads"),
    ({"layer_types": KINDS[:7] + ("memory_unit",)},
     "layer_types must name"),
])
def test_configuration_refuses_what_it_cannot_mean(override, message):
    with pytest.raises(ValueError, match=message):
        small_model(**override)


def test_handed_on_values_need_layer_types():
    with pytest.raises(ValueError, match="layer_types must be set"):
        TransformerConfig(shared_kv_layer=0)
