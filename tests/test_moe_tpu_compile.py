"""The expert layers compiled for a described v5e, without the chip: the
grouped matmuls at the cells' widths, the layer under its row bound, the
router without a pass by the slots, and the weighted sum back.  The
fixtures are ``tests/test_tpu_compile.py``'s, from which these cases
moved whole; they have a file of their own, as
``tests/test_kimi_linear_tpu_compile.py`` has, so that ``--dist loadfile``
starts these minutes of TPU compiles beside that file's and not after
them."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from test_tpu_compile import no_compile_cache, one_chip, topo  # noqa: F401


# (id, rows, hidden, held experts, expert width): the whole slot buffer of
# the GLM, Trinity and SmallThinker cells, the LFM2 cell's row bound
_EXPERT_SHAPES = [
    ("glm47f_train_s8192", 32768, 2048, 8, 1536),
    ("trinitym_train_s8192", 65536, 2048, 16, 1024),
    ("smallthinker_train_s16384", 98304, 2560, 16, 768),
    ("lfm2_train_s32768", 32768, 2048, 8, 1536),
]


@pytest.mark.parametrize("rows,hidden,held,width",
                         [case[1:] for case in _EXPERT_SHAPES],
                         ids=[case[0] for case in _EXPERT_SHAPES])
def test_grouped_expert_matmuls_compile_for_v5e(one_chip, rows, hidden, held,
                                                width):
    """The dropless expert layer's grouped feed-forward at each expert
    cell's shape (glm47f_train_s8192: 8192 tokens x 4 choices = 32768
    rows of 2048, 8 held experts of 1536, a ninth group for the rows whose
    expert lives elsewhere), forward and backward: jax's Pallas grouped
    matmul, whose grid follows the group sizes (five calls: the first
    matmul forward, and each matmul's two gradients, ``gmm`` for the rows
    and ``tgmm`` for the weights), and no ``ragged-dot`` beside it.  The
    chip's compiler accepts the tiles ``gmm_tiles`` gives each call: their
    blocks fit the VMEM a kernel that states no limit may use."""
    from horovod_tpu.parallel.moe import grouped_ffn

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((rows, hidden), jnp.bfloat16),
            shape((held, hidden, 2 * width), jnp.float32),
            shape((held, width, hidden), jnp.float32),
            shape((held + 1,), jnp.int32))

    def backward(xs, fc1, fc2, sizes):
        return jax.grad(
            lambda *a: grouped_ffn(*a, sizes, interpret=False).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(xs, fc1, fc2)

    compiled = jax.jit(backward).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5
    assert "ragged-dot" not in text and "ragged_dot" not in text
    # the widest temporaries are the [rows, 2 width] buffers, 192 MiB
    # each at GLM's shape, where the limit is 1 GiB: 16 / 3 of them
    widest = rows * 2 * width * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * widest // 3


def _wide_rows(text, rows):
    """Where a compiled program holds arrays of ``rows`` rows and more
    than one column: ``(outside, sides)``, the shapes outside every
    ``conditional`` and, for each conditional, the shapes inside each of
    its branches (with what the branch calls), as ``{shape: count}``."""
    import re

    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)
    called = {
        name: set(re.findall(
            r"(?:to_apply|calls|body|condition|true_computation"
            r"|false_computation)=%?([\w.\-]+)", "\n".join(lines)))
        | {c.strip().lstrip("%") for group in re.findall(
            r"branch_computations=\{([^}]*)\}", "\n".join(lines))
           for c in group.split(",")}
        for name, lines in bodies.items()}

    def reach(name, seen):
        if name in bodies and name not in seen:
            seen.add(name)
            for other in called[name]:
                reach(other, seen)
        return seen

    wide = re.compile(r"= \(?((?:bf16|f32|s32|pred)\[%d,\d+\])" % rows)

    def shapes(names):
        found = {}
        for name in names:
            for line in bodies[name]:
                for shape in wide.findall(line):
                    if not shape.endswith(",1]"):      # a gather's indices
                        found[shape] = found.get(shape, 0) + 1
        return found

    sides, inside = [], set()
    for lines in bodies.values():
        for line in lines:
            branches = re.search(r" conditional\(.*branch_computations="
                                 r"\{([^}]*)\}", line)
            if branches:
                reached = [reach(b.strip().lstrip("%"), set())
                           for b in branches.group(1).split(",")]
                sides.append([shapes(r) for r in reached])
                inside |= set().union(*reached)
    return shapes(set(bodies) - inside), sides


@pytest.mark.parametrize("experts,held,top_k,ff,bound", [
    (64, 8, 4, 1536, 8192),      # glm47f_train_s8192: 32768 slots
    (128, 16, 8, 1024, 16384),   # trinitym_train_s8192: 65536 slots
])
def test_bounded_expert_layer_compiles_for_v5e(one_chip, experts, held,
                                               top_k, ff, bound):
    """The dropless expert layer at both cells' shapes, rematerialised,
    forward and backward, with the compiled kernels.  Outside the branch
    nothing has ``n * top_k`` rows, and on the side that stays under the
    row bound nothing has either: the way back to the tokens and the
    tokens' gradient follow the routed rows (``ops/moe_combine.py``), so
    no gather brings rows back to slot order, and every gate, cast,
    select and grouped matmul there is on ``[bound, .]`` buffers.  The
    one exception is no array: the counts compare every slot with every
    group (``moe._counts``) inside a fusion that writes ``held + 1``
    integers, and the ``[n * top_k, held + 1]`` matches are values of its
    loop.  The other side is the whole-buffer computation with the same
    kernels.
    The bounded side calls the Pallas grouped matmul as the layer without
    a bound does: twice forward, twice in the recomputed forward, four
    times backward (``gmm`` by the rows, ``tgmm`` by the matrices; the
    forward calls that ``jax.vjp`` traces there are dropped), and
    ``moe_combine`` for the outputs, for them again and for the tokens'
    gradient, each reading ``[bound, d]`` rows there and ``[n * top_k,
    d]`` on the other side; no computation holds kernels of both
    sides."""
    import re

    from horovod_tpu.parallel import moe

    n, d = 8192, 2048
    assert moe.row_bound(n, top_k, held, experts) == bound

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((n, d), jnp.bfloat16), shape((d, experts), jnp.float32),
            shape((held, d, 2 * ff), jnp.float32),
            shape((held, ff, d), jnp.float32), shape((experts,), jnp.float32))

    def step(x2, router, fc1, fc2, bias):
        def loss(x2, router, fc1, fc2):
            y, _ = moe.routed_experts(x2, router, bias, fc1, fc2,
                                      top_k=top_k, scaling=1.8,
                                      interpret=False)
            return y.astype(jnp.float32).sum()

        return jax.value_and_grad(
            jax.checkpoint(
                loss, policy=jax.checkpoint_policies.nothing_saveable),
            argnums=(0, 1, 2, 3))(x2, router, fc1, fc2)

    text = jax.jit(step).lower(*args).compile().as_text()
    assert "ragged-dot" not in text and "ragged_dot" not in text
    outside, sides = _wide_rows(text, n * top_k)
    matches = {f"{kind}[{n * top_k},{held + 1}]" for kind in ("pred", "s32")}
    assert set(outside) == matches
    # made and used up inside fusions: none is a fusion's operand or result
    assert set(re.findall(
        r"= (?:pred|s32)\[%d,%d\]\S* ([\w\-]+)\(" % (n * top_k, held + 1),
        text)) <= {"compare", "broadcast", "convert", "iota"}
    for under, over in sides:
        assert under == {}, under
    assert any(f"bf16[{n * top_k},{2 * ff}]" in over for _, over in sides)
    # the Pallas calls: which computation holds each, and its rows (a
    # ``tgmm`` gives matrices, [held, ., .]: 0 here; a ``moe_combine``
    # gives tokens: the rows it reads, its last operand's)
    where, combines, name = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
        elif "tpu_custom_call" in line and "/moe_combine/" in line:
            assert "/moe_dispatch/moe_rows_out/" in line   # its scope
            read = re.search(r"bf16\[(\d+),%d\]\{1,0\}\}, frontend" % d, line)
            combines.setdefault(name, []).append(int(read.group(1)))
        elif "tpu_custom_call" in line and "pallas_call" in line:
            rows = re.search(r"= bf16\[(\d+),\d+\]", line)
            where.setdefault(name, []).append(
                int(rows.group(1)) if rows else 0)
    assert "ENTRY" not in where and all(
        bound not in rows or n * top_k not in rows for rows in where.values())
    calls = sorted(rows for side in where.values() for rows in side)
    assert calls.count(bound) == 6 and calls.count(0) == 4
    # the other side: forward (the compiler may merge its two passes:
    # nothing lies between them here), forward again and by the rows
    assert calls.count(n * top_k) in (6, 8) and len(calls) in (16, 18)
    # the way back: forward (merged or twice) and backward a side, each
    # computation's calls on its own side's rows
    assert "ENTRY" not in combines and all(
        len(set(rows)) == 1 for rows in combines.values())
    read = sorted(rows for side in combines.values() for rows in side)
    assert read.count(bound) in (2, 3) and read.count(n * top_k) in (2, 3)
    assert len(read) == read.count(bound) + read.count(n * top_k)


@pytest.mark.parametrize("n,top_k,experts,held,score_rule", [
    (16384, 8, 256, 8, "sigmoid"),           # kimilin_train_s16384
    (16384, 8, 128, 16, "softmax_chosen"),   # sdar_train_s8192_bd4
])
def test_the_router_compiles_without_a_pass_by_the_slots(
        one_chip, n, top_k, experts, held, score_rule):
    """The decision and its gradient at the widest sigmoid cell and the
    widest ``softmax_chosen`` one, 131 072 slots a layer both: the
    compiled program scatters and gathers nothing but, under
    ``softmax_chosen``, the derivative of ``top_k``'s own values (one
    scatter to indices that are unique, which the chip does not take one
    after another: 0.03 ms a layer in the cell's trace), and what the
    dense forms compare (``[slots, bins]`` for the counts, ``[n, k, E]``
    for the sigmoid rule's chosen scores and their gradient) is made and
    used up inside fusions, never an array in HBM (Kimi-Linear's one-hot
    would be 128 MiB a pass in int32)."""
    import re

    from horovod_tpu.parallel import moe

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def step(x2, router, bias):
        def loss(x2, router):
            routing = moe.routing_decision(
                x2, router, bias if score_rule == "sigmoid" else None,
                top_k=top_k, scaling=1.5, first_held=0, held=held,
                score_rule=score_rule, balance=True)
            return routing.weights.sum() + routing.balance, routing

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            x2, router)

    text = jax.jit(step).lower(
        shape((n, 2048), jnp.bfloat16), shape((2048, experts), jnp.float32),
        shape((experts,), jnp.float32)).compile().as_text()
    assert re.findall(r" (scatter|gather)\(", text) == (
        [] if score_rule == "sigmoid" else ["scatter"])
    slots = n * top_k
    dense = "|".join((f"{slots},{held + 1}", f"{slots},{experts}",
                      f"{n},{top_k},{experts}"))
    ops = set(re.findall(
        r"= (?:pred|s32|f32)\[(?:%s)\]\S* ([\w\-]+)\(" % dense, text))
    assert ops >= ({"compare", "select"} if score_rule == "sigmoid"
                   else {"compare"})
    assert ops <= {"compare", "select", "broadcast", "convert", "iota",
                   "bitcast", "reshape"}, ops


# tokens, choices, hidden, held, experts, rows: the widest layers of the
# way back, SDAR's under its row bound and SmallThinker's whole buffer
_COMBINE_SHAPES = {
    "sdar_train_s8192_bd4": (16384, 8, 2048, 16, 128, 32768),
    "smallthinker_whole_buffer": (16384, 6, 2560, 16, 64, 98304),
}


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain_sum"])
@pytest.mark.parametrize("cell", sorted(_COMBINE_SHAPES))
def test_moe_combine_compiles_for_v5e(one_chip, cell, weighted):
    """``moe_combine`` under the plan's tiles at the cell's shape: the
    weighted sum into float32 (the forward pass's) and the plain sum into
    bfloat16 (the tokens' gradient), inside the VMEM the call states."""
    from horovod_tpu.ops import moe_combine

    n, k, d, held, experts, rows = _COMBINE_SHAPES[cell]
    tiles = moe_combine.plan(n, d, held, rows, 2)
    assert tiles is not None

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def back(ys, weights, inverse, held_sizes):
        return moe_combine.combine_rows(
            ys, weights if weighted else None, inverse, held_sizes, k=k,
            tiles=tiles, dtype=jnp.float32 if weighted else jnp.bfloat16)

    text = jax.jit(back).lower(
        shape((rows, d), jnp.bfloat16), shape((n, k), jnp.float32),
        shape((n * k,), jnp.int32), shape((held,), jnp.int32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "moe_combine" in text
    # nothing by the slots: no gather, no scatter, no [n k, d] array
    assert " gather(" not in text and " scatter(" not in text
    assert f"[{n * k},{d}]" not in text or rows == n * k
