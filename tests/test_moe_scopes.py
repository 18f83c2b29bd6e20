"""The names inside the dropless expert layer's three scopes
(``horovod_tpu/scopes.py``: ``moe_logits``, ``moe_topk``, ``moe_sort``,
``moe_unsort`` inside ``moe_route``; ``moe_rows_in``, ``moe_rows_out``
inside ``moe_dispatch``; ``moe_cast``, ``moe_gate`` inside
``moe_experts``) and the two gauges beside ``moe.gmm_tile_fill``.

Lowering only, on a tiny shape (64 tokens of 32, 8 experts of which 2
are held, 2 a token, bfloat16 on float32 masters, the grouped matmul's
stand-in): the names are read from the locations of the lowered
gradient's text, nothing is compiled or run.
"""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu import scopes
from horovod_tpu.obs import profile
from horovod_tpu.parallel import moe

N, D, FF, EXPERTS, HELD, TOP_K = 64, 32, 16, 8, 2, 2

# name: (the scope it lies in, an operation of its forward pass, what
# tells an operation of its backward pass or None where it has none: the
# sort and its inverse are integers).  ``route`` and the cast are
# differentiated by jax, so their backward carries ``transpose(``;
# ``_backward`` is a rule of its own and enters the scopes by the same
# ``with``: the product with the weights and the sums over a token's
# choices are its alone, and the gate's ``jax.vjp`` sits inside its scope.
# ``moe_rows_out`` holds, off the chip as here, the form by the slots
# (``ops/moe_combine.py:combine_slots``: the einsum over a token's
# gathered rows forward, their sum backward); on the chip the kernel
# ``moe_combine`` takes their place under the same scope
# (``test_tpu_compile.py`` reads its name there).
PARTS = {
    scopes.MOE_LOGITS: (scopes.MOE_ROUTE, "dot_general", "transpose("),
    scopes.MOE_TOPK: (scopes.MOE_ROUTE, "top_k", "transpose("),
    scopes.MOE_SORT: (scopes.MOE_ROUTE, "jit(argsort)", None),
    scopes.MOE_UNSORT: (scopes.MOE_ROUTE, "jit(argsort)", None),
    scopes.MOE_ROWS_IN: (scopes.MOE_DISPATCH, "jit(_take)", "/mul"),
    scopes.MOE_ROWS_OUT: (scopes.MOE_DISPATCH, "dot_general", "/reduce_sum"),
    scopes.MOE_CAST: (scopes.MOE_EXPERTS, "convert_element_type",
                      "transpose("),
    scopes.MOE_GATE: (scopes.MOE_EXPERTS, "mul", "transpose("),
}
BACKWARD = sorted(n for n, (_, _, mark) in PARTS.items() if mark)


@functools.lru_cache(maxsize=None)
def locations(score_rule="sigmoid"):
    """The name stacks in the lowered gradient of the layer's two halves
    on one tensor, under an outer ``mlp`` as a block puts them."""

    def loss(x2, router, gate_up, down):
        with jax.named_scope(scopes.MLP):
            bias = jnp.zeros((EXPERTS,)) if score_rule == "sigmoid" else None
            routing = moe.routing_decision(
                x2, router, bias, top_k=TOP_K, scaling=1.5, first_held=2,
                held=HELD, score_rule=score_rule, balance=True)
            y, routing = moe.apply_routing(routing, x2, gate_up, down,
                                           interpret=True)
        return (y.astype(jnp.float32) ** 2).sum() + routing.balance

    shape = jax.ShapeDtypeStruct
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        shape((N, D), jnp.bfloat16), shape((D, EXPERTS), jnp.float32),
        shape((HELD, D, 2 * FF), jnp.float32),
        shape((HELD, FF, D), jnp.float32)).as_text(debug_info=True)
    return frozenset(re.findall(r'loc\("([^"]+)"', text))


def under(name, score_rule="sigmoid"):
    """The name stacks that hold ``name`` directly inside its parent."""
    nested = f"{PARTS[name][0]}/{name}/"
    return [loc for loc in locations(score_rule) if nested in loc]


@pytest.mark.parametrize("name", sorted(PARTS))
def test_the_forward_pass_carries_the_name_inside_its_parent(name):
    operation = PARTS[name][1]
    assert any(loc.endswith("/" + operation) and "transpose(" not in loc
               for loc in under(name)), sorted(under(name))
    # nowhere outside its parent
    assert [loc for loc in locations() if f"/{name}/" in loc
            or loc.startswith(name + "/")] == under(name)


@pytest.mark.parametrize("name", BACKWARD)
def test_the_backward_pass_carries_the_name_inside_its_parent(name):
    mark = PARTS[name][2]
    assert any(mark in loc for loc in under(name)), sorted(under(name))


@pytest.mark.parametrize("name", sorted(PARTS))
def test_the_programs_own_reduction_files_time_under_the_name(name):
    """``scopes.SCOPES`` holds it, so ``obs/profile.py:scope_of`` (the
    innermost program scope of an operation's name stack) files an
    operation under the inner name and no longer under its parent."""
    parent = PARTS[name][0]
    assert name in scopes.SCOPES and scopes.SCOPES.count(name) == 1
    forward = f"jit(step)/jvp(GPT)/block1/mlp/{parent}/{name}/mul:"
    backward = (f"jit(step)/transpose(jvp(GPT))/block1/mlp/jit(_backward)/"
                f"{parent}/{name}/mul:")
    assert profile.scope_of(forward) == name
    assert profile.scope_of(backward) == f"transpose({name})"
    assert profile.scope_of(
        f"jit(step)/jvp(GPT)/block1/mlp/{parent}/mul:") == parent


@pytest.mark.parametrize("score_rule", moe.SCORE_RULES)
def test_both_score_rules_name_the_logits_and_the_choice(score_rule):
    for name, operation in ((scopes.MOE_LOGITS, "dot_general"),
                            (scopes.MOE_TOPK, "top_k")):
        found = under(name, score_rule)
        assert any(loc.endswith("/" + operation) for loc in found), found
        assert any("transpose(" in loc for loc in found), found
    # the sigmoid is the sigmoid rule's; softmax_chosen keeps the logits
    assert any(loc.endswith("/logistic")
               for loc in under(scopes.MOE_LOGITS, score_rule)) == (
                   score_rule == "sigmoid")


@pytest.mark.parametrize("score_rule", moe.SCORE_RULES)
def test_nothing_under_the_route_scatters_or_gathers(score_rule):
    """The counts are comparisons summed over the slots, the sigmoid
    rule's chosen scores a select over the experts (its transpose a dense
    masked sum), the inverse a second sort: forward, and in the backward
    pass that ``jax`` derives, no operation under ``moe_route`` follows
    the slots one after another.  ``softmax_chosen`` reads ``top_k``'s
    own values, and the transpose of that primitive's rule is the one
    scatter-add that is lowered (the chip's compiler fuses it away:
    ``test_tpu_compile.py`` reads the compiled program, PERF.md section 6
    the trace)."""
    found = [loc for loc in locations(score_rule)
             if f"/{scopes.MOE_ROUTE}/" in loc]
    assert any("transpose(" in loc for loc in found)
    assert [loc for loc in found if loc.endswith(
        ("/scatter", "/scatter-add", "/scatter_add", "/gather"))] == (
            [] if score_rule == "sigmoid" else [
                f"jit(loss)/transpose(jvp({scopes.MLP}))/{scopes.MOE_ROUTE}/"
                f"{scopes.MOE_TOPK}/scatter-add"])


def test_the_weights_gradient_goes_to_its_slots_by_the_rows():
    """``d_weights`` is a sum a ROW, put at the slot the row came from
    (a scatter of ``rows`` scalars under ``moe_rows_out``), not gathered
    by the slots."""
    found = under(scopes.MOE_ROWS_OUT)
    assert any(loc.endswith("/scatter") for loc in found), sorted(found)


def test_the_row_gauges_are_set_while_the_step_is_traced():
    """``moe.row_bound{layer}`` and ``moe.slots{layer}`` beside
    ``moe.gmm_tile_fill{layer}``, under the layers' names that
    ``publish_stats`` uses: the rows every ``[row_bound, .]`` buffer
    carries and the slots a step over the bound runs on."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_glm_moe_mla import SEQ, SMALL, TOKENS, small_model

    from horovod_tpu.obs.registry import get_registry, reset_registry

    model = small_model()
    # shapes alone: the variables are not made, nothing is compiled
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(1),
                               TOKENS[:, :SEQ])
    reset_registry()
    jax.eval_shape(lambda v: model.apply(
        v, TOKENS[:, :-2], next_tokens=TOKENS[:, 1:-1],
        mutable=["moe_stats"]), variables)
    tokens = TOKENS[:, :-2].size
    # off the chip no layer's way back takes the kernel
    assert get_registry().gauge("moe.combine_kernel_layers").value == 0
    gauges = {(m["name"], m["tags"]["layer"]): m["value"]
              for m in get_registry().snapshot()
              if m["name"] in ("moe.row_bound", "moe.slots")}
    layers = ("block1", "block2", "mtp/block")
    assert gauges == {
        **{("moe.row_bound", layer): moe.row_bound(
            tokens, SMALL["routed_top_k"], SMALL["routed_held"],
            SMALL["routed_experts"]) for layer in layers},
        **{("moe.slots", layer): tokens * SMALL["routed_top_k"]
           for layer in layers}}
