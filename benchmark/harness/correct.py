"""The comparisons that decide ``correct``.  Each check is a named entry
``{"ok": bool, ...what was compared}`` so a failing run says which."""

from __future__ import annotations

import math
from typing import Dict, Sequence


def first_device(tree):
    """A replicated tree's copy on its first device, without moving
    anything."""
    import jax

    return jax.tree.map(lambda a: a.addressable_shards[0].data, tree)


def replicas_equal(tree) -> bool:
    """Every device's copy of every leaf equals the first device's, bit
    for bit.  Copies go to the first device and are compared there."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            width = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
            return jax.lax.bitcast_convert_type(x, width)
        return x

    same = jax.jit(lambda a, b: jnp.stack(
        [jnp.array_equal(bits(x), bits(y)) for x, y in zip(a, b)]).all())
    leaves = jax.tree.leaves(tree)
    base = [leaf.addressable_shards[0].data for leaf in leaves]
    home = leaves[0].addressable_shards[0].device
    copies = len(leaves[0].addressable_shards)
    for k in range(1, copies):
        other = [jax.device_put(leaf.addressable_shards[k].data, home)
                 for leaf in leaves]
        if not bool(same(base, other)):
            return False
    return True


def through_fp8(variables):
    """The control of a bfloat16 configuration (the nearest precision
    below it): every floating-point leaf rounded to the nearest
    ``float8_e4m3fn`` value.  Handed to ``compare_sides`` as the
    program's variables it must come out as not correct.

    Spelt out in arithmetic, not as a cast there and back: the TPU
    compiler removes such a pair of converts, and the control came out
    equal to the sound program to the last digit (my chip run, PR 27).
    Normal numbers keep three mantissa bits (``reduce_precision``, to
    nearest even), those under 2**-6 fall on the subnormal grid of
    2**-9, and 448 is the largest; a test holds it to the cast."""
    import jax
    import jax.numpy as jnp

    def rounded(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        x = leaf.astype(jnp.float32)
        normal = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
        subnormal = jnp.round(x * 512.0) / 512.0
        x = jnp.where(jnp.abs(x) < 2.0 ** -6, subnormal, normal)
        return jnp.clip(x, -448.0, 448.0).astype(leaf.dtype)

    return jax.jit(lambda tree: jax.tree.map(rounded, tree))(variables)


def zeroed(variables, paths):
    """``variables`` with the leaves under each of ``paths`` (a tuple of
    keys each) set to zero: how a family's ``fault_probes`` turn a layer
    into an identity without touching the program."""
    import jax

    def walk(tree, path):
        if not path:
            return jax.tree.map(lambda a: a * 0, tree)
        return {k: walk(v, path[1:]) if k == path[0] else v
                for k, v in tree.items()}

    for path in paths:
        variables = walk(variables, tuple(path))
    return variables


def reference_sides(program_loss, reference, config, with_logprob=True):
    """The two things compared, each one jitted function of
    ``(variables, sample)`` -> ``(loss, log-probabilities or None,
    gradient)``: the program's own loss on its own path, and the plain
    reference's in float32 with full-precision matmuls, backward pass
    included.  ``program_loss`` returns the loss, or the loss and the
    log-probability of each label from the same forward pass."""
    import jax

    def with_aux(variables, sample):
        out = program_loss(variables, sample)
        return out if isinstance(out, tuple) else (out, None)

    def program(variables, sample):
        (value, picked), grads = jax.value_and_grad(
            with_aux, has_aux=True)(variables, sample)
        return value, picked, grads

    def plain(variables, sample):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(
                lambda v: reference.loss(config, v, sample))(variables)
            picked = (reference.logprob(config, variables, sample)
                      if with_logprob else None)
        return value, picked, grads

    return jax.jit(program), jax.jit(plain)


def compare_sides(sides, variables, sample, program_variables=None) -> dict:
    """The numbers the three reference checks compare.  The reference
    reads ``variables``; the program reads ``program_variables`` where
    given (a fault probe hands it a damaged copy), else the same tree."""
    import jax
    import jax.numpy as jnp

    def norm(tree):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree.leaves(tree)))

    program, reference = sides
    got_loss, got_logp, got_grads = program(
        variables if program_variables is None else program_variables,
        sample)
    want_loss, want_logp, want_grads = reference(variables, sample)
    got_norm, want_norm, apart = (float(x) for x in jax.jit(
        lambda got, want: (norm(got), norm(want), norm(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b, got, want))))(
                got_grads, want_grads))
    out = {"loss": (float(got_loss), float(want_loss)),
           "grad_norm": (got_norm, want_norm),
           # the norm of the gradients' difference, and the difference
           # of their norms, each over the reference's norm
           "grad_rel": apart / want_norm,
           "grad_norm_rel": abs(got_norm - want_norm) / want_norm}
    if got_logp is not None and want_logp is not None:
        diff = jnp.abs(got_logp.astype(jnp.float32) - want_logp)
        out["logprob_abs"] = float(diff.max())
        out["logprob_abs_rms"] = float(jnp.sqrt(jnp.mean(diff ** 2)))
        out["labels"] = int(diff.size)
    return out


def reference_checks(numbers: dict, tolerance: dict) -> Dict[str, dict]:
    """``compare_sides``' numbers against the configuration's
    ``reference_tolerance``, each number compared beside its limit:
    ``loss_abs``; ``logprob_abs`` where stated; and of ``grad_rel`` and
    ``grad_norm_rel`` whichever the configuration states, at least one."""
    got, want = numbers["loss"]
    checks = {"matches_reference": {
        "ok": abs(got - want) <= tolerance["loss_abs"], "program": got,
        "reference": want, "abs_diff": abs(got - want),
        "tolerance": tolerance["loss_abs"]}}
    if "logprob_abs" in tolerance:
        if "logprob_abs" not in numbers:
            raise SystemExit(
                "benchmark: the configuration states a logprob_abs "
                "tolerance and its builder's program_loss returns no "
                "log-probabilities")
        checks["logprob_matches_reference"] = {
            "ok": numbers["logprob_abs"] <= tolerance["logprob_abs"],
            "abs_diff_max": numbers["logprob_abs"],
            "abs_diff_rms": numbers["logprob_abs_rms"],
            "labels": numbers["labels"],
            "tolerance": tolerance["logprob_abs"]}
    stated = [key for key in ("grad_rel", "grad_norm_rel")
              if key in tolerance]
    if not stated:
        raise SystemExit("benchmark: the configuration's reference_tolerance "
                         "states neither grad_rel nor grad_norm_rel")
    got, want = numbers["grad_norm"]
    checks["gradient_matches_reference"] = {
        "ok": all(numbers[key] <= tolerance[key] for key in stated),
        "program_norm": got, "reference_norm": want,
        "diff_norm_over_reference_norm": numbers["grad_rel"],
        "norms_apart_over_reference_norm": numbers["grad_norm_rel"],
        "tolerance": {key: tolerance[key] for key in stated}}
    return checks


def training(*, losses: Sequence[float], builds_in_window: int, variables,
             sample, program_loss, reference, config: dict, chips: int,
             tolerance: dict) -> Dict[str, dict]:
    checks: Dict[str, dict] = {}
    checks["losses_finite"] = {
        "ok": all(math.isfinite(v) for v in losses), "steps": len(losses)}
    checks["loss_falls"] = {
        "ok": losses[-1] < losses[0], "first": losses[0],
        "last": losses[-1]}
    checks["nothing_built_in_window"] = {
        "ok": builds_in_window == 0, "builds": builds_in_window}
    if chips > 1:
        checks["replicas_bitwise_equal"] = {
            "ok": replicas_equal(variables), "copies": chips}
    sides = reference_sides(program_loss, reference, config,
                            with_logprob="logprob_abs" in tolerance)
    checks.update(reference_checks(
        compare_sides(sides, first_device(variables), sample), tolerance))
    return checks
