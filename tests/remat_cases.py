"""One small model per mixer, for the tests of what a rematerialised
block keeps (tests/test_models_gpt.py, tests/test_remat_kernels.py): GPT
nano with the reference attention and with the flash kernels, latent
attention with routed experts and a prediction module (the block it
builds is rematerialised too), and Mamba-2 layers.  Two blocks each, the
Pallas kernels through the interpreter."""

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models.transformer import gpt

POLICIES = ("dots_with_no_batch_dims_saveable", "nothing_saveable")
# mixer -> (named size, overrides, the forward kernel a block runs)
MIXERS = {
    "reference": ("nano", dict(attention_impl="reference"), None),
    "flash": ("nano", dict(attention_impl="flash", dtype=jnp.float32),
              "flash_fwd"),
    "mla": ("glm-4.7-flash", dict(
        layer_types=("mla",) * 2, vocab_size=256, emb_dim=64, num_heads=4,
        num_kv_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
        mlp_ratio=3, routed_experts=16, routed_held=4, routed_first_held=4,
        routed_top_k=3, routed_width=32, max_len=64,
        attention_impl="flash", dtype=jnp.float32), "flash_fwd"),
    "mamba": ("granite-4.0-h-micro", dict(
        layer_types=("mamba",) * 2, vocab_size=256, emb_dim=64,
        num_heads=4, num_kv_heads=2, ssm_heads=4, ssm_head_dim=16,
        ssm_state=16, ssm_chunk=8, max_len=64, dtype=jnp.float32),
              "ssd_fwd"),
}
SEQ = 32


def _model(mixer, **settings):
    size, overrides, _ = MIXERS[mixer]
    return gpt(size, **overrides, num_layers=2, **settings)


def blocks(mixer):
    """Blocks that run the mixer: the two layers, and the prediction
    module's where the model has one."""
    return 2 + _model(mixer).cfg.mtp_modules


def build(mixer, remat=False, policy=POLICIES[0]):
    """(loss of the variables' ``params``, those ``params``) of the
    mixer's two-block model; the variables are those of the model without
    ``remat`` (the trees are the same)."""
    plain = _model(mixer)
    model = _model(mixer, remat=remat, remat_policy=policy)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 2), 0,
                                plain.cfg.vocab_size)
    variables = jax.jit(plain.init)(jax.random.PRNGKey(1), tokens[:, :SEQ])
    rest = {k: v for k, v in variables.items() if k == "moe_state"}

    def cross_entropy(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    def loss(params):
        if not plain.cfg.mtp_modules:
            return cross_entropy(
                model.apply({"params": params}, tokens[:, :SEQ]),
                tokens[:, 1:-1])
        logits, mtp_logits = model.apply(
            {"params": params, **rest}, tokens[:, :SEQ],
            next_tokens=tokens[:, 1:-1])
        return (cross_entropy(logits, tokens[:, 1:-1])
                + 0.3 * cross_entropy(mtp_logits, tokens[:, 2:]))

    return loss, variables["params"]


def matmul_outputs(mixer):
    """Bytes of the matmul outputs (a ``dot_general`` with no batch
    dimensions) that the mixer's rematerialised blocks make, one entry a
    block in trace order, each the sizes in the block's own order: what
    ``block_remat_policy`` may keep while the chip has room.  Read off a
    block's forward, one ``remat2`` equation each."""
    loss, params = build(mixer, remat=True, policy="nothing_saveable")
    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "remat2":
                found.append([])
                walk(eqn.params["jaxpr"], True)
            elif (inside and eqn.primitive.name == "dot_general"
                  and not any(eqn.params["dimension_numbers"][1])):
                out = eqn.outvars[0].aval
                found[-1].append(out.size * out.dtype.itemsize)
            elif not eqn.primitive.name.startswith("custom_vjp"):
                # (a kernel's own products: the policy is shown its
                # forward rule, where they are inside the ``pallas_call``)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, inside)

    walk(jax.make_jaxpr(loss)(params).jaxpr, False)
    return found
