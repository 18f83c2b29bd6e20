"""Megatron-style tensor parallelism for the transformer family.

Beyond reference parity (Horovod 0.19.1 is data-parallel only,
SURVEY.md §2.9 — TP listed as optional stretch): the GPT block's weights
shard across a mesh axis the Megatron way —

* qkv projection **column-parallel** (whole attention heads per rank:
  attention is embarrassingly parallel over heads, zero comms),
* output projection **row-parallel** (one ``psum`` rejoins the residual),
* MLP fc1 column-parallel, fc2 row-parallel (one ``psum``),

so a block costs exactly TWO psums over the tp axis, and every matmul
stays MXU-large.  LayerNorms, embeddings, and the LM head stay
replicated (their cost is marginal at these widths).

The implementation operates on the EXISTING `GPT` parameter pytree:
:func:`stack_tp_params` reshapes a trained/initialized checkpoint into
per-rank shards with a leading ``tp`` dim (shard it over the axis with
``in_specs=P("tp")``), and :func:`tp_gpt_apply` reproduces
``GPT.apply`` bit-for-bit (up to fp associativity) inside ``shard_map``.
Equivalence is pinned by tests/test_tensor_parallel.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import scopes

__all__ = ["stack_tp_params", "unstack_tp_params", "tp_gpt_apply"]


def _split_qkv_columns(kernel, bias, cfg, tp: int):
    """Split the fused qkv projection so rank r holds whole head groups:
    q columns [r*h/tp head blocks], k and v columns likewise at kv_heads.
    Returns per-rank (kernel, bias) lists."""
    emb = cfg.emb_dim
    hd = cfg.head_dim
    kv_dim = cfg.kv_heads * hd
    q_w, k_w, v_w = (
        kernel[:, :emb], kernel[:, emb:emb + kv_dim],
        kernel[:, emb + kv_dim:],
    )
    q_b, k_b, v_b = bias[:emb], bias[emb:emb + kv_dim], bias[emb + kv_dim:]
    qs = np.split(np.asarray(q_w), tp, axis=1)
    ks = np.split(np.asarray(k_w), tp, axis=1)
    vs = np.split(np.asarray(v_w), tp, axis=1)
    qbs = np.split(np.asarray(q_b), tp)
    kbs = np.split(np.asarray(k_b), tp)
    vbs = np.split(np.asarray(v_b), tp)
    kernels = [
        np.concatenate([qs[r], ks[r], vs[r]], axis=1) for r in range(tp)
    ]
    biases = [
        np.concatenate([qbs[r], kbs[r], vbs[r]]) for r in range(tp)
    ]
    return kernels, biases


def stack_tp_params(params, cfg, tp: int):
    """Split a GPT parameter pytree into ``(sharded, replicated)`` trees.

    ``sharded`` carries the block matmul weights with a leading ``tp``
    dimension (rank r's shard at index r) — pass it through ``shard_map``
    with ``in_specs=P(tp_axis)``.  ``replicated`` carries embeddings,
    layer norms, post-psum biases, and the LM head — pass it with
    ``in_specs=P()``.  The separation is LOAD-BEARING for training, not
    just memory hygiene: stacking replicated weights per rank and
    sharding them makes every downstream value device-varying, and the
    psum transpose then sums the per-rank cotangents — sharded-weight
    gradients come out scaled by tp (pinned by
    tests/test_tensor_parallel.py).

    Requires ``num_heads % tp == 0`` and ``kv_heads % tp == 0`` (whole
    heads per rank) and ``mlp_ratio * emb_dim % tp == 0``.
    """
    from ..models.transformer import require_gpt2_block  # noqa: PLC0415

    require_gpt2_block(cfg, "parallel.tensor_parallel.stack_tp_params")
    if cfg.num_heads % tp or cfg.kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_heads={cfg.num_heads} and "
            f"kv_heads={cfg.kv_heads}"
        )
    if (cfg.mlp_ratio * cfg.emb_dim) % tp:
        raise ValueError(f"tp={tp} must divide the MLP width")
    if set(params.keys()) == {"params"}:  # accept the flax variables dict
        params = params["params"]
    p = jax.tree_util.tree_map(np.asarray, params)
    sharded, replicated = {}, {}
    for name, sub in p.items():
        if not name.startswith("block"):
            replicated[name] = sub  # embeddings / final LN / head
            continue
        blk = dict(sub)
        if "fc1" not in blk:
            raise ValueError(
                "stack_tp_params supports dense blocks only; MoE blocks "
                "(cfg.moe_experts > 0) shard over the ep axis instead "
                "(parallel/moe.py moe_mlp_ep)"
            )
        qk, qb = _split_qkv_columns(
            blk["qkv"]["kernel"], blk["qkv"]["bias"], cfg, tp
        )
        sharded[name] = {
            "qkv": {"kernel": np.stack(qk), "bias": np.stack(qb)},
            # proj/fc2 row-parallel; their biases apply once after the
            # psum, so they live on the replicated tree
            "proj": {
                "kernel": np.stack(
                    np.split(blk["proj"]["kernel"], tp, axis=0)
                ),
            },
            "fc1": {
                "kernel": np.stack(np.split(blk["fc1"]["kernel"], tp,
                                            axis=1)),
                "bias": np.stack(np.split(blk["fc1"]["bias"], tp)),
            },
            "fc2": {
                "kernel": np.stack(np.split(blk["fc2"]["kernel"], tp,
                                            axis=0)),
            },
        }
        replicated[name] = {
            "ln1": blk["ln1"],
            "ln2": blk["ln2"],
            "proj_bias": blk["proj"]["bias"],
            "fc2_bias": blk["fc2"]["bias"],
        }
    to_jnp = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
    return to_jnp(sharded), to_jnp(replicated)




def unstack_tp_params(sharded, replicated, cfg, tp: int):
    """Inverse of :func:`stack_tp_params`: reassemble the canonical GPT
    parameter pytree from the per-rank shards — the code behind
    docs/inference.md's "invert the column/row splits" instruction, so a
    TP-trained state round-trips to the single-device checkpoint format
    (pinned by tests/test_tensor_parallel.py)."""
    emb = cfg.emb_dim
    kv_dim = cfg.kv_heads * cfg.head_dim
    qw, kw = emb // tp, kv_dim // tp
    out = {k: v for k, v in replicated.items()
           if not k.startswith("block")}
    for name, blk in sharded.items():
        lead = np.asarray(blk["qkv"]["kernel"]).shape[0]
        if lead != tp:
            # numpy slicing never goes out of bounds, so a wrong tp
            # would reassemble a CORRECT-SHAPED but scrambled qkv
            # kernel — fail loudly instead
            raise ValueError(
                f"{name} shards carry leading dim {lead}, expected "
                f"tp={tp} — unstacking with a different tp than the "
                "tree was stacked with"
            )
        rep_blk = replicated[name]
        kern = np.asarray(blk["qkv"]["kernel"])  # [tp, emb, qw+2kw]
        bias = np.asarray(blk["qkv"]["bias"])    # [tp, qw+2kw]
        qkv_kernel = np.concatenate(
            [np.concatenate(list(part), axis=1)
             for part in (kern[:, :, :qw], kern[:, :, qw:qw + kw],
                          kern[:, :, qw + kw:])],
            axis=1,
        )
        qkv_bias = np.concatenate(
            [np.concatenate(list(part))
             for part in (bias[:, :qw], bias[:, qw:qw + kw],
                          bias[:, qw + kw:])]
        )
        out[name] = {
            "ln1": rep_blk["ln1"],
            "ln2": rep_blk["ln2"],
            "qkv": {"kernel": jnp.asarray(qkv_kernel),
                    "bias": jnp.asarray(qkv_bias)},
            "proj": {
                # row-parallel: shards concatenate back on the input dim
                "kernel": jnp.concatenate(
                    list(blk["proj"]["kernel"]), axis=0
                ),
                "bias": rep_blk["proj_bias"],
            },
            "fc1": {
                "kernel": jnp.concatenate(
                    list(blk["fc1"]["kernel"]), axis=1
                ),
                "bias": jnp.concatenate(list(blk["fc1"]["bias"])),
            },
            "fc2": {
                "kernel": jnp.concatenate(
                    list(blk["fc2"]["kernel"]), axis=0
                ),
                "bias": rep_blk["fc2_bias"],
            },
        }
    return out


def _gpt_embed(rep, cfg, tokens, pos_offset, positions):
    """Shared replicated preamble of the TP/PP reimplementations of
    GPT.apply — ONE copy of its trace-time guards and embedding contract
    (max_len check, zigzag-positions requirement, learned-table gather
    with loud NaN fill, rope tables).  Returns (x, positions, rope_tabs).
    """
    s = tokens.shape[1]
    if s > cfg.max_len:
        raise ValueError(f"sequence length {s} exceeds max_len={cfg.max_len}")
    if positions is None:
        if cfg.attention_impl == "zigzag":
            raise ValueError(
                "attention_impl='zigzag' requires explicit positions "
                "(zigzag_positions(axis_index, P, s_local))"
            )
        positions = pos_offset + jnp.arange(s)
    with jax.named_scope(scopes.EMBED):
        x = jnp.take(rep["wte"]["embedding"], tokens,
                     axis=0).astype(cfg.dtype)
        if cfg.pos_embedding == "learned":
            pos = jnp.take(rep["wpe"], positions, axis=0,
                           mode="fill", fill_value=jnp.nan)
            x = x + pos.astype(cfg.dtype)[None]
    rope_tabs = None
    if cfg.pos_embedding == "rope":
        from ..ops.rope import rope_tables  # noqa: PLC0415

        rope_tabs = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    return x, positions, rope_tabs


@jax.named_scope(scopes.HEAD)
def _gpt_head(rep, cfg, x):
    """Shared replicated epilogue: final LN + LM head, fp32 logits."""
    from ..models.transformer import raw_layer_norm  # noqa: PLC0415

    x = raw_layer_norm(x, rep["lnf"]["scale"], rep["lnf"]["bias"])
    logits = x.astype(cfg.dtype) @ rep["head"]["kernel"].astype(cfg.dtype)
    return logits.astype(jnp.float32)


def _tp_block(cfg, p, rep, x, positions, rope_tabs, tp_axis, tp,
              attend=None):
    """One transformer block on this rank's head/width shard: the shared
    ``block_math`` wiring with column-parallel qkv/fc1 and row-parallel
    proj/fc2 closures — each row-parallel matmul rejoined by one psum,
    its bias applied once after (the bias lives on the replicated
    tree).  ``attend`` overrides the attention schedule exactly as in
    ``attention_mixer`` — the width-sharded paged decode path
    (models/decode.py) supplies one that appends to its per-shard KV
    pages and attends its own heads."""
    from ..models.transformer import (  # noqa: PLC0415
        attention_mixer, block_math, raw_dense, raw_layer_norm,
        require_gpt2_block,
    )

    require_gpt2_block(cfg, "parallel.tensor_parallel")
    dt = cfg.dtype

    def row(kernel, bias):  # row-parallel: psum rejoin, then the bias
        return lambda h: lax.psum(
            h.astype(dt) @ kernel.astype(dt), tp_axis
        ) + bias.astype(dt)

    def mlp(h):
        from ..models.transformer import act_store  # noqa: PLC0415

        return row(p["fc2"]["kernel"], rep["fc2_bias"])(
            act_store(jax.nn.gelu(raw_dense(p["fc1"], dt)(h)), cfg)
        )

    return block_math(
        cfg, x,
        ln1=lambda h: raw_layer_norm(h, rep["ln1"]["scale"],
                                     rep["ln1"]["bias"]),
        mixer=lambda h: attention_mixer(
            cfg, h, positions, rope_tabs, qkv=raw_dense(p["qkv"], dt),
            proj=row(p["proj"]["kernel"], rep["proj_bias"]),
            num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.kv_heads // tp, attend=attend),
        ln2=lambda h: raw_layer_norm(h, rep["ln2"]["scale"],
                                     rep["ln2"]["bias"]),
        mlp=mlp,
    )


def tp_gpt_apply(sharded_params, replicated_params, cfg, tokens,
                 tp_axis: str, pos_offset=0, positions=None):
    """``GPT.apply`` with block weights tensor-sharded over ``tp_axis``.

    Call inside ``shard_map`` with the two trees from
    :func:`stack_tp_params`: ``sharded_params`` with ``in_specs=
    P(tp_axis)``, ``replicated_params`` with ``in_specs=P()``, tokens
    replicated.  Returns fp32 logits, identical (up to fp associativity)
    to the unsharded model's.  Use ``check_vma=True`` (replication
    tracking) when differentiating — see ``stack_tp_params``.
    """
    from ..models.transformer import require_gpt2_block  # noqa: PLC0415
    from ..ops.collectives import axis_size  # noqa: PLC0415

    require_gpt2_block(cfg, "parallel.tensor_parallel.tp_gpt_apply")
    tp = axis_size(tp_axis)
    p = jax.tree_util.tree_map(lambda a: a[0], sharded_params)
    rep = replicated_params
    x, positions, rope_tabs = _gpt_embed(rep, cfg, tokens, pos_offset,
                                         positions)
    for i in range(cfg.num_layers):
        x = _tp_block(cfg, p[f"block{i}"], rep[f"block{i}"], x, positions,
                      rope_tabs, tp_axis, tp)
    return _gpt_head(rep, cfg, x)
