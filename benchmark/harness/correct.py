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


def training(*, losses: Sequence[float], builds_in_window: int, variables,
             sample, program_loss, reference, config: dict, chips: int,
             tolerance: float) -> Dict[str, dict]:
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
    local = first_device(variables)
    got = float(program_loss(local, sample))
    want = float(reference.loss(config, local, sample))
    checks["matches_reference"] = {
        "ok": abs(got - want) <= tolerance, "program": got,
        "reference": want, "abs_diff": abs(got - want),
        "tolerance": tolerance}
    return checks
