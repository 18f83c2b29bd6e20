"""What every model builder under ``benchmark/models/`` returns, and the
few helpers they share.

A family is one file ``benchmark/models/<family>.py`` with two functions
(``registry.load_model_builder`` refuses a file that lacks either, before
anything is built):

* ``build(config, params, seed, described_mesh=None) -> Built``;
* ``train_flops_per_item(config, ran) -> float``: the model FLOPs one
  item (token, image) of a training step requires, forward and backward,
  recomputed operations not counted, from the configuration file's
  values and the ``ran`` that ``build`` returned.  The arithmetic lives
  in ``benchmark/harness/flops.py``; a new family adds its function
  there only if it shares one, else keeps it in its own file.

A family may also state ``fault_probes(config, ran) -> {name: damage}``, each
``damage(variables)`` returning a copy of the trained variables with
which the program must FAIL the reference checks (a layer turned into an
identity, say).  ``benchmark/tools/probe_correct.py`` and the tests run
them beside the control every configuration has, its weights through the
next lower precision (``harness/correct.py:through_fp8``); no measured
run does.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable


# Folded into the seed's key for the reference check's batch.  Not a
# small number: ``fold_in(key, 1)`` is ``split(key)[1]``, the very key
# the training batch is drawn from.
FRESH = 0x5EED

# The scope ``hvd.DistributedOptimizer`` traces the wrapped optimizer's
# update under (horovod_tpu/scopes.py), spelt out here because the
# reader ``benchmark/metrics/optimizer_ms.py`` spells it out too: the
# builders put the step's ``optax.apply_updates`` under the same name.
OPTIMIZER_SCOPE = "optimizer_update"


@dataclass
class Built:
    """What ``build`` returns.

    ``ran`` holds the sizes the program was built with.  The runner lays
    it over the configuration file's values for the reference and for
    ``train_flops_per_item``, and metric readers read it as
    ``run["ran"]``.  Keys that readers which are there depend on:

    * ``flash_roofline`` reads ``global_batch``, ``n_head``, ``n_embd``,
      ``seq_len`` and ``n_layer`` and counts full causal multi-head
      attention in every layer (head size ``n_embd // n_head``, as many
      key heads as query heads, no window).  A family that lists a
      ``flash_*`` metric for its cell asserts exactly that of its
      attention; one with grouped heads, a window or another head size
      brings its own roofline reader.
    * Every other reader that is there reads the trace, the stamps or the
      compile log, and nothing of ``ran``.
    """

    step: Any                 # the jitted step: step(*carry, *const)
    state: tuple              # carry first, then the constant batch
    carry_len: int
    items_per_step: int       # global, over all chips
    chips: int
    mesh: Any
    # (variables, sample) -> the program's loss on its own path, or the
    # pair (loss, log-probability of each label: per token, per image)
    # from the one forward pass: the reference checks take the loss's
    # gradient, and compare the pair's second with the reference's
    # ``logprob`` wherever the configuration states ``logprob_abs``.
    program_loss: Callable
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
