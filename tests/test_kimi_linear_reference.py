"""Kimi-Linear-48B-A3B-Instruct on the training path against the
benchmark's own plain reference
(``benchmark/configs/kimi-linear-48b-a3b-instruct.reference.py``) in
float32 on seeded weights: the loss, every label's log-probability and
every leaf of the gradient under each attention form and remat setting,
and each of the reference's seeded departures told by the same
comparison.  The model, the seeded variables and the helpers are
``tests/test_kimi_linear.py``'s, from which these cases moved whole: a
file of their own so that ``--dist loadfile`` gives the family's four
minutes to two workers.  Alone: 91 s (the other file 103 s).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kimi_linear import (BATCH, CONFIG, program_sides, ref,
                              small_model, sound)


@pytest.mark.parametrize("attention,remat", [
    ("reference", False), ("reference", True), ("flash", True)],
    ids=["reference-kept", "reference-remat", "flash-remat"])
def test_model_matches_plain_reference(attention, remat):
    """The loss, every label's log-probability and every leaf of the
    gradient, with the reference attention and through the flash kernels
    (the Pallas interpreter, keys of 24 over values of 16), every block
    kept and every block recomputed from its input (the rule's ``o`` and
    states kept by name)."""
    model = small_model(attention_impl=attention, remat=remat)
    variables, want_logp, want_grads = sound()
    with jax.default_matmul_precision("highest"):
        got_logp, got_grads = program_sides(model, variables)
    np.testing.assert_allclose(got_logp, want_logp, atol=2e-4)
    np.testing.assert_allclose(got_logp.mean(), want_logp.mean(), atol=1e-5)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert flat_got.keys() == flat_want.keys()
    for path, want_leaf in flat_want.items():
        scale = float(jnp.abs(want_leaf).max())
        assert scale > 0, f"{path}: the reference's gradient is zero"
        np.testing.assert_allclose(
            flat_got[path], want_leaf, atol=2e-4 * scale + 1e-7,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("depart", ref.DEPARTURES)
def test_comparison_fails_on_a_seeded_departure(depart, monkeypatch):
    # 64 tokens: the state dropped every 16th token, the tiny chunk
    monkeypatch.setattr(ref, "STATE_DROP", 16)
    variables, want_logp, _ = sound()
    with jax.default_matmul_precision("highest"):
        departed = jax.jit(lambda v: ref.loss(CONFIG, v, BATCH, depart))(
            variables)
    # (test_model_matches_plain_reference holds the program to the sound
    # reference's loss within 1e-5)
    assert abs(-want_logp.mean() - departed) > 1e-4
