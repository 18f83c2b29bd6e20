"""Plain reference for ``resnet50-v1.5``: the training-mode forward pass
(batch statistics in BatchNorm) and the softmax cross-entropy in plain
``jax.numpy`` / ``lax.conv_general_dilated``, float32, full-precision
convolutions.  It reads the program's variable tree (``params`` with
``conv_init``, ``bn_init``, ``stage<i>_block<j>/{conv1..3, bn1..3,
proj_conv, proj_bn}``, ``head``) and nothing else of the program.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, p, stride, pad):
    return lax.conv_general_dilated(
        x, p["kernel"].astype(jnp.float32), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _same(x, p, stride):
    """flax's ``padding='SAME'``: output = ceil(input / stride)."""
    k = p["kernel"].shape[0]
    size = x.shape[1]
    total = max((-(-size // stride) - 1) * stride + k - size, 0)
    lo = total // 2
    return lax.conv_general_dilated(
        x, p["kernel"].astype(jnp.float32), (stride, stride),
        [(lo, total - lo), (lo, total - lo)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, eps):
    mu = x.mean((0, 1, 2))
    var = ((x - mu) ** 2).mean((0, 1, 2))
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def logits(config, params, images):
    p = params["params"]
    eps = config["batch_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = images.astype(jnp.float32)
        x = jax.nn.relu(_bn(_conv(x, p["conv_init"], 2, 3),
                            p["bn_init"], eps))
        x = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])
        for i, blocks in enumerate(config["stage_sizes"]):
            for j in range(blocks):
                blk = p[f"stage{i + 1}_block{j + 1}"]
                stride = 2 if i > 0 and j == 0 else 1
                y = jax.nn.relu(_bn(_same(x, blk["conv1"], 1),
                                    blk["bn1"], eps))
                y = jax.nn.relu(_bn(_same(y, blk["conv2"], stride),
                                    blk["bn2"], eps))
                y = _bn(_same(y, blk["conv3"], 1), blk["bn3"], eps)
                if "proj_conv" in blk:
                    x = _bn(_same(x, blk["proj_conv"], stride),
                            blk["proj_bn"], eps)
                x = jax.nn.relu(x + y)
        x = x.mean((1, 2))
        return x @ p["head"]["kernel"] + p["head"]["bias"]


def logprob(config, params, batch):
    """Log-probability of each image's label: float32 [n]."""
    lg = logits(config, params, batch["images"])
    logp = jax.nn.log_softmax(lg, axis=-1)
    return jnp.take_along_axis(
        logp, batch["labels"][:, None], axis=-1)[:, 0]


def loss(config, params, batch):
    return -logprob(config, params, batch).mean()
