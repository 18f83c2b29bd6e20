"""What every model builder under ``benchmark/models/`` returns, and the
few helpers they share."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable


# Folded into the seed's key for the reference check's batch.  Not a
# small number: ``fold_in(key, 1)`` is ``split(key)[1]``, the very key
# the training batch is drawn from.
FRESH = 0x5EED


@dataclass
class Built:
    step: Any                 # the jitted step: step(*carry, *const)
    state: tuple              # carry first, then the constant batch
    carry_len: int
    items_per_step: int       # global, over all chips
    chips: int
    mesh: Any
    program_loss: Callable    # (variables, sample) -> loss, the program's
    sample: Callable          # n -> a small fresh batch on one device
    variables: Callable       # state -> the tree the reference reads
    ran: dict                 # the sizes the program was built with


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62: JAX's ``PRNGKey``
    takes 32 bits, the driver's seeds are wider."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must not be negative: {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def sharded(mesh, axis):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(axis))


def make_on_device(make_state, seed: int, described_mesh, shardings):
    """The whole state in one jitted call from the seed, each part born
    where the step wants it.  For a described mesh (a compile without
    the chip) only the shapes, with those shardings."""
    import jax

    if described_mesh is None:
        return jax.jit(make_state, out_shardings=shardings)(seed_key(seed))
    shapes = jax.eval_shape(make_state, seed_key(seed))
    return tuple(
        jax.tree.map(lambda a, s=s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), part)
        for part, s in zip(shapes, shardings))
