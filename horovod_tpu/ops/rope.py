"""Rotary position embedding (RoPE), TPU-shaped.

Positions are an explicit int vector (one global position per local row),
NOT an offset + arange — that is what makes RoPE compose with arbitrary
sequence layouts: contiguous shards pass ``offset + arange``, zigzag
shards pass :func:`horovod_tpu.parallel.zigzag_positions`, and the
rotation is correct either way because it only ever looks at the
per-token position value.

Angles are computed in fp32 regardless of activation dtype (bf16 angles
destroy long-range phase accuracy), rotation output casts back.

The frequencies are ``theta ** (-2i / head_dim)``, or what a
``rope_scaling`` record makes of them: a configuration's scaling is data
handed to :func:`rope_tables` (:func:`scaled_frequencies`), not a table
function of its own.  YaRN (arXiv:2309.00071, as DeepSeek-V3's modeling
code reads the record) is the one scheme there: channels that turn fast
keep their frequency, those that turn slowly have it divided by
``factor``, a linear ramp between the two, and the softmax takes
:func:`yarn_mscale` squared (the model's ``attention_scale``).
"""

from __future__ import annotations

import math
from typing import Mapping, Optional

import jax
import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction ``0.1 mscale ln(factor) + 1`` (1 where
    nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp(head_dim: int, theta: float, scaling: Mapping) -> tuple:
    """``(low, high)``: the channels (of ``head_dim // 2``) between which
    YaRN blends, from the rotations a channel makes over the original
    context (``beta_fast`` of them at ``low``, ``beta_slow`` at
    ``high``)."""
    original = scaling["original_max_position_embeddings"]

    def channel(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(channel(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(channel(scaling.get("beta_slow", 1))), head_dim - 1)
    return low, high


def scaled_frequencies(head_dim: int, theta: float,
                       scaling: Optional[Mapping] = None):
    """The ``head_dim // 2`` rotary frequencies, float32, and the factor
    cos and sin carry: ``theta ** (-2i / head_dim)`` and 1 without a
    ``scaling`` record; with one of type ``yarn``, ``(1 - r_i) f_i + r_i
    f_i / factor`` with ``r_i = clip((i - low) / (high - low), 0, 1)``
    and ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    half = head_dim // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    )
    if scaling is None:
        return freqs, 1.0
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(
            f"rope_scaling of type {kind!r} is not implemented: 'yarn' is")
    factor = scaling["factor"]
    low, high = yarn_ramp(head_dim, theta, scaling)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    magnitude = (yarn_mscale(factor, scaling.get("mscale", 1.0))
                 / yarn_mscale(factor, scaling.get("mscale_all_dim", 0.0)))
    return (1.0 - ramp) * freqs + ramp * freqs / factor, magnitude


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float = 10000.0,
                scaling: Optional[Mapping] = None):
    """Precompute ``(cos, sin)`` ``[seq, head_dim//2]`` for
    :func:`apply_rope_tables`.  Angles depend only on positions, theta
    and the ``rope_scaling`` record where the configuration has one
    (:func:`scaled_frequencies`), so a model computes them ONCE and
    threads them to every block — under remat the per-block recompute
    would otherwise re-run the transcendentals in the backward pass
    too."""
    if head_dim % 2:
        raise ValueError(f"RoPE requires an even head_dim, got {head_dim}")
    freqs, magnitude = scaled_frequencies(head_dim, theta, scaling)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    if magnitude == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * magnitude, jnp.sin(ang) * magnitude


def apply_rope_tables(x: jax.Array, cos: jax.Array,
                      sin: jax.Array) -> jax.Array:
    """Rotate ``x`` ``[batch, seq, heads, head_dim]`` by precomputed
    tables from :func:`rope_tables`.  Tables made for fewer channels
    than a head has (a configuration's ``partial_rotary_factor``) turn
    the head's FIRST ``2 * cos.shape[-1]`` channels, split halves among
    themselves, and leave the others as they are."""
    rotary = 2 * cos.shape[-1]
    if rotary < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope_tables(x[..., :rotary], cos, sin), x[..., rotary:]],
            axis=-1)
    half = x.shape[-1] // 2
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    )
    return out.astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """One-shot spelling: rotate ``x`` by per-token angles from
    ``positions`` (int ``[seq]`` global token positions)."""
    cos, sin = rope_tables(positions, x.shape[-1], theta)
    return apply_rope_tables(x, cos, sin)
