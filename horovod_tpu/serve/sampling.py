"""Replicated per-request PRNG sampling (ROADMAP item 1c): temperature
/ top-k token picks that are a PURE function of ``(request id,
emission index, serve seed)`` — so every rank of the serving world
derives the identical token, and an elastic replay that re-prefills
``prompt + resume`` continues the stream bit-exactly where the dead
world stopped.  This ends the slot engine's greedy-only loop.

Key discipline (the HVD001 invariant applied to randomness):

* ``request_key(seed, rid)`` folds a stable CRC-32 of the request id
  into ``PRNGKey(seed)`` — NOT Python's ``hash`` (PYTHONHASHSEED-
  dependent, the exact poison hvdtpu-lint HVD012 rejects) — giving
  each request its own stream root, identical on every rank.
* token ``i`` of a request is sampled with ``fold_in(root, i)`` where
  ``i`` is the request's EMISSION index (tokens emitted so far), not
  the serving step: two fleets that admit the same request at
  different steps — or a replay that resumes mid-stream — still draw
  the same keys.
* :func:`sample_token` is the ONE sampling math, used inside the slot
  engine's jitted step AND by the single-stream oracle tests, so
  "bitwise-equal to the oracle" is a property of shared code, not of
  two implementations agreeing.

``temperature == 0`` is greedy argmax (the key is ignored), so the
default path is byte-identical to the pre-sampling engine.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from .. import scopes

__all__ = ["request_key", "token_key", "sample_token", "KEY_SHAPE"]

# Raw key width: old-style jax PRNG keys are uint32[2]; the engine
# carries them as plain arrays so they cross the host/jit boundary as
# data, not as typed-key objects (version-tolerant).
KEY_SHAPE = (2,)


def request_key(seed: int, rid: str):
    """The request's PRNG stream root: ``fold_in(PRNGKey(seed),
    crc32(rid))``.  crc32 is stable across processes, platforms and
    PYTHONHASHSEED — the determinism contract's replacement for
    ``hash``."""
    rid_tag = zlib.crc32(rid.encode("utf-8")) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(int(seed)), rid_tag)


@jax.named_scope(scopes.SAMPLE)
def token_key(base, emission_index):
    """Key for the request's ``emission_index``-th generated token."""
    return jax.random.fold_in(base, emission_index)


@jax.named_scope(scopes.SAMPLE)  # the token pick, by name in a device trace
def sample_token(logits, temperature, top_k, key):
    """One token from one row of logits — greedy when ``temperature <=
    0``, else top-k-truncated temperature sampling via the Gumbel-max
    trick (an argmax, like the greedy path, so the whole pick stays
    inside the compiled step).

    ``logits [vocab]`` fp32; ``temperature`` scalar f32; ``top_k``
    scalar i32 (0 = no truncation); ``key`` uint32[2].  Jit/vmap-safe:
    both branches are computed and selected with ``where`` (per-slot
    mixed greedy/sampled pools share one compiled step).
    """
    greedy = jnp.argmax(logits).astype(jnp.int32)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    lt = logits.astype(jnp.float32) / safe_t
    # top-k truncation without dynamic shapes: positions below the
    # k-th largest logit are -inf.  top_k == 0 (or >= vocab) keeps all.
    vocab = logits.shape[-1]
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, vocab), 1, vocab)
    sorted_lt = jnp.sort(lt)[::-1]
    kth = sorted_lt[jnp.minimum(k_eff - 1, vocab - 1)]
    lt = jnp.where(lt < kth, -jnp.inf, lt)
    g = jax.random.gumbel(key, (vocab,), dtype=jnp.float32)
    sampled = jnp.argmax(lt + g).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)
