"""Phi-4-mini-flash-reasoning's mechanisms on the training path
(``model_type: phi4flash``, the SambaY decoder-hybrid-decoder): Mamba-1
selective-scan layers, sliding-window and full differential attention,
and a cross-decoder whose gated memory units read one layer's scan
output and whose cross-attention layers read one layer's keys and
values.  The program (``models/transformer.py``, ``ops/
selective_scan.py``) against the benchmark's own plain reference
(``benchmark/configs/phi-4-mini-flash-reasoning.reference.py``) on seeded
weights; the scan kernel through the interpreter against the
token-by-token recurrence; differential attention against the dense
formula (``tests/test_selective_scan.py`` has those two); the handed-on
values' gradients as sums over their readers; the window's reach.  (The
published values of the named size, the counts of its cuts, the paths and
settings that refuse, the scopes and gauges and the other named sizes'
trees are in ``tests/test_phi4_flash_config.py``.)  Every comparison is
one jitted function a side, made once a process.
All on the CPU at small sizes: published layers 14-21 (the benchmark's cut,
layers 14-19, and one more period, so that each shared value has two
readers), hidden 64, 8 sub-heads of 8 over 4, inner
128, state 16, ``dt_rank`` 4, a window of 8 in 32 tokens.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import gpt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "phi-4-mini-flash-reasoning"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("phi4flash_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

KINDS = ("selective_scan", "sliding_attention", "selective_scan",
         "full_attention", "gmu", "cross_attention", "gmu",
         "cross_attention")
CUT = dict(num_layers=8, layer_types=KINDS, first_layer_index=14,
           shared_kv_layer=3, memory_layer=2)
SMALL = dict(
    **CUT, vocab_size=96, emb_dim=64, num_heads=8, num_kv_heads=4,
    ssm_width=128, ssm_dt_rank=4, attention_window=8, max_len=64,
    attention_impl="reference",
    # several tiles a row, and a window that is a multiple of neither
    flash_block_q=16, flash_block_k=4, dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
    layer_norm_eps=1e-5, sliding_window=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_dt_rank=4, layer_types=list(KINDS), first_layer_index=14,
    shared_kv_layer=3, memory_layer=2)
SEQ = 32
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, 96)


def small_model(**overrides):
    return gpt(NAME, **{**SMALL, **overrides})


def init(model, key=1):
    """Seeded variables, every leaf moved off its initial value (biases
    and norm weights start at 0 and 1, and the four lambda vectors at a
    tenth, where a lost term would not show)."""
    variables = jax.jit(model.init)(jax.random.PRNGKey(key),
                                    TOKENS[:, :SEQ])

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        size = 0.3 if "lambda" in name else 0.05
        return leaf + size * jax.random.normal(
            jax.random.PRNGKey(len(name)), leaf.shape)

    return {"params": jax.tree_util.tree_map_with_path(
        moved, variables["params"])}


def program_logprob(model, variables, tokens):
    logp = jax.nn.log_softmax(model.apply(variables, tokens[:, :-1]), axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def program_loss(model, variables, tokens):
    return -program_logprob(model, variables, tokens).mean()


def _leaves(tree):
    return dict(jax.tree_util.tree_leaves_with_path(tree))




@functools.lru_cache(maxsize=None)
def program(attention="reference", remat=False):
    """``(loss and gradient, log-probabilities)`` of the small model, each
    jitted once a process: functions of the variables alone."""
    model = small_model(attention_impl=attention, remat=remat)

    def loss(v):
        with jax.default_matmul_precision("highest"):
            return program_loss(model, v, TOKENS)

    def logprob(v):
        with jax.default_matmul_precision("highest"):
            return program_logprob(model, v, TOKENS)

    return jax.jit(jax.value_and_grad(loss)), jax.jit(logprob)


@functools.lru_cache(maxsize=None)
def reference(depart=None):
    """The same pair from the plain reference, with a departure seeded
    where a test asks for one."""
    batch = {"tokens": TOKENS}
    return (jax.jit(jax.value_and_grad(
        lambda v: ref.loss(CONFIG, v, batch, depart=depart))),
        jax.jit(lambda v: ref.logprob(CONFIG, v, batch, depart=depart)))


@functools.lru_cache(maxsize=None)
def variables():
    return init(small_model())

# the plain path whole, and the kernels with every block rematerialised
@pytest.mark.parametrize("attention,remat", [("reference", False),
                                             ("flash", True)])
def test_model_matches_plain_reference(attention, remat):
    """The loss, every label's log-probability and every leaf of the
    gradient, with the reference attention and through the flash kernels
    (the Pallas interpreter; the scan kernel runs through it in both),
    the blocks whole or rematerialised (the handed-on values then cross
    ``jax.checkpoint``)."""
    (got_loss, got), got_logp = (f(variables())
                                 for f in program(attention, remat))
    (want_loss, want), want_logp = (f(variables()) for f in reference())
    np.testing.assert_allclose(got_logp, want_logp, atol=2e-4)
    np.testing.assert_allclose(got_loss, want_loss, atol=1e-5)
    got, want = _leaves(got), _leaves(want)
    assert got.keys() == want.keys()
    for path, want_leaf in want.items():
        np.testing.assert_allclose(
            got[path], want_leaf, atol=2e-5, rtol=2e-3,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("depart", ref.DEPARTURES)
def test_comparison_fails_on_a_seeded_departure(depart):
    """Each fault seeded into the reference moves the loss away from the
    program's by far more than the comparison above allows."""
    sound, _ = program()[0](variables())
    departed = -reference(depart)[1](variables()).mean()
    assert abs(float(departed) - float(sound)) > 1e-3, (
        depart, departed, sound)


# ----------------------------------------- values that cross the layers


def _with_zero(variables, *paths):
    params = jax.tree.map(lambda a: a, variables["params"])
    for block, name in paths:
        params[block][name] = jax.tree.map(jnp.zeros_like,
                                           params[block][name])
    return {"params": params}


SILENCED = {
    "no_reader_silent": (),
    "first_cross_silent": (("block5", "proj"),),
    "second_cross_silent": (("block7", "proj"),),
    "both_cross_silent": (("block5", "proj"), ("block7", "proj")),
    "first_gmu_silent": (("block4", "out_proj"),),
    "second_gmu_silent": (("block6", "out_proj"),),
    "both_gmu_silent": (("block4", "out_proj"), ("block6", "out_proj")),
}


def _shared_grads(loss_and_grad, silent):
    """The gradient into what only the handed-on values carry back:
    the key and value columns of layer 17's ``qkv`` (block3; the last
    2 x 4 x 8 columns) and layer 16's ``A_log`` and ``x_proj`` (block2)."""
    g = loss_and_grad(_with_zero(variables(), *silent))[1]["params"]
    return {"kv": np.asarray(g["block3"]["qkv"]["kernel"][:, 64:]),
            "A_log": np.asarray(g["block2"]["A_log"]),
            "x_proj": np.asarray(g["block2"]["x_proj"]["kernel"])}


@pytest.mark.parametrize("silent", sorted(SILENCED))
def test_shared_values_gradients_are_sums_over_their_readers(silent):
    """Layer 17's keys and values are read by the layer itself and by the
    two cross layers (blocks 5 and 7), layer 16's scan output by its own
    gate and by the two memory units (blocks 4 and 6).  A reader whose
    output projection is zero sends nothing back through the value it
    read.  With none, one or both readers of a value silenced, the
    program's gradient into the value's maker equals the reference's,
    which shares no code with it and sums its readers through plain
    autodiff of plain arrays; and each reader's share is there: silencing
    one moves the gradient, silencing the second moves it again.  The
    blocks are rematerialised, so the values and their gradients cross
    ``jax.checkpoint``."""
    rematerialised = program(remat=True)[0]
    got = _shared_grads(rematerialised, SILENCED[silent])
    want = _shared_grads(reference()[0], SILENCED[silent])
    whole = _shared_grads(rematerialised, ())
    for name in got:
        np.testing.assert_allclose(got[name], want[name], atol=2e-5,
                                   rtol=2e-3, err_msg=name)
    moved = "kv" if "cross" in silent else "A_log"
    if silent != "no_reader_silent":
        assert np.abs(got[moved] - whole[moved]).max() > 1e-6
    if silent.startswith("both"):
        # the second reader's share, beside the first's
        one = _shared_grads(rematerialised, SILENCED[silent][:1])
        assert np.abs(got[moved] - one[moved]).max() > 1e-6


def test_the_window_reaches_layer_15_and_not_17():
    """Token 31's logits depend on token 0 through the full layer and the
    cross layers; with those made blind to it (their attention silenced)
    the window layer alone cannot carry it 31 tokens... but the scans
    can, so silence them too: the sliding layer (block1, published 15)
    sees 8 keys, the full layer (block3, published 17) all of them."""
    cfg = small_model().cfg
    assert cfg.window_of(cfg.layer_type(1)) == 8
    assert cfg.window_of(cfg.layer_type(3)) is None
    assert cfg.window_of("cross_attention") is None
    model = small_model()
    scans_off = [(b, "out_proj") for b in ("block0", "block2", "block4",
                                           "block6")]
    changed = TOKENS.at[:, 0].set((TOKENS[:, 0] + 1) % 96)

    def last_logits(v, tokens):
        return model.apply(v, tokens[:, :SEQ])[:, -1]

    # only the window layer attends: token 0 is out of token 31's reach
    window_only = _with_zero(variables(), *scans_off, ("block3", "proj"),
                             ("block5", "proj"), ("block7", "proj"))
    np.testing.assert_array_equal(last_logits(window_only, TOKENS),
                                  last_logits(window_only, changed))
    # only the full layer attends: it reaches token 0
    full_only = _with_zero(variables(), *scans_off, ("block1", "proj"),
                           ("block5", "proj"), ("block7", "proj"))
    assert np.abs(last_logits(full_only, TOKENS)
                  - last_logits(full_only, changed)).max() > 1e-6
