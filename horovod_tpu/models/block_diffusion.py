"""Block-diffusion training's step-level objective (BD3-LMs,
arXiv:2503.09573; SDAR, arXiv:2510.06303): what a step does around a
model whose ``TransformerConfig.block_diffusion`` is set.

A sequence of ``L`` tokens ``x_0`` is cut into blocks of ``B``.  Each
block draws a noise level ``t`` and each of its tokens becomes the mask
token with probability ``t`` (the linear schedule).  The model runs the
noised sequence ``x_t`` and the clean one side by side as ONE sequence
of ``2L`` rows, ``[x_t ; x_0]``, at positions ``[0..L-1, 0..L-1]``,
under the block-diffusion mask (``ops/flash_attention.py``): a noised
block sees itself and the clean blocks before it.  The logits are the
noised rows', the label of a masked position is its own token (no
shift), and the loss is the masked positions' cross-entropy weighted by
``1 / t``, over ``L``::

    loss = (1 / L) sum over i with x_t,i = MASK of
           (1 / t_i) * (-log softmax(logits_i)[x_0,i])

The noise is drawn on the device inside the step from a key the step
carries (:func:`draw`, scope ``diffusion_noise``), so a job never uses
its noise twice; a check hands in a fixed draw instead.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import scopes

T_MIN = 1e-3    # the levels are uniform in [T_MIN, 1): 1 / t stays finite


class Noise(NamedTuple):
    """One draw for ``x_0`` ``[batch, L]``: which positions are masked,
    and each position's level (its block's)."""

    masked: jax.Array   # bool [batch, L]
    t: jax.Array        # float32 [batch, L]


def draw(key, batch: int, length: int, block: int,
         t_min: float = T_MIN) -> Noise:
    """A level ``t`` a block, uniform in ``[t_min, 1)``, and each token
    masked with its block's probability ``t``."""
    if length % block:
        raise ValueError(
            f"the block length {block} does not divide {length} tokens")
    k_level, k_mask = jax.random.split(key)
    t = jnp.repeat(jax.random.uniform(
        k_level, (batch, length // block), jnp.float32, t_min, 1.0),
        block, axis=1)
    return Noise(jax.random.uniform(k_mask, (batch, length)) < t, t)


def paired(tokens, noise: Noise, mask_token: int):
    """``[x_t ; x_0]`` int ``[batch, 2L]``, the model's input, and its
    positions ``[0..L-1, 0..L-1]``."""
    noised = jnp.where(noise.masked, mask_token, tokens)
    at = jnp.arange(tokens.shape[1])
    return (jnp.concatenate([noised, tokens], axis=1),
            jnp.concatenate([at, at]))


def noised_inputs(key, tokens, block: int, mask_token: int,
                  t_min: float = T_MIN):
    """The step's noising in one scope: a fresh draw for ``tokens`` and
    the pair made from it.  Returns ``(noise, pair, positions)``."""
    with jax.named_scope(scopes.DIFFUSION_NOISE):
        noise = draw(key, *tokens.shape, block, t_min)
        return (noise, *paired(tokens, noise, mask_token))


def label_logprobs(logits, tokens):
    """Each position's log-probability of its own token under the noised
    row's logits: float32 ``[batch, L]``."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]


def loss(logprobs, noise: Noise):
    """The masked positions' negative log-probabilities weighted by
    ``1 / t``, over ``L``, the mean over the batch."""
    weights = noise.masked / noise.t
    return -(weights * logprobs).sum(-1).mean() / logprobs.shape[-1]


def visible_pairs(length: int, block: int) -> int:
    """The (query, key) pairs the mask shows over ``2 * length`` rows:
    a noised row its block's ``block`` noised keys and the clean keys of
    the blocks before it, a clean row the clean keys up to its block's
    end: ``L B + L^2``."""
    return length * block + length * length


def publish_masked(masked_tokens) -> float:
    """The last step's count of masked tokens, from the step's carry to
    the gauge ``bd.masked_tokens``; returns it."""
    from ..obs.registry import get_registry  # noqa: PLC0415

    count = float(masked_tokens)
    get_registry().gauge("bd.masked_tokens").set(count)
    return count
