"""Operations and bytes computed from shapes.

Model FLOPs are what the forward and backward passes *require* (a
multiply-add is two operations; backward is twice forward; recomputed
operations never count), so a utilisation derived from them cannot be
raised by recomputing.
The program's ``cost_analysis()`` is not used: a Pallas custom call is
opaque to it and recomputed operations count there.
Which count is a family's is stated by the family, not here
(``benchmark/models/<family>.py:train_flops_per_item``).
"""

from __future__ import annotations

# --------------------------------------------------------------- GPT-2


def gpt_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward matmul FLOPs of one token of a ``seq_len`` sequence.

    Per layer: qkv (d x 3d), proj (d x d), fc1 (d x 4d), fc2 (4d x d) =
    12 d^2 multiply-adds; causal attention sees (seq_len + 1) / 2 keys on
    average, in QK^T and in PV, each d multiply-adds a key.  Then the
    output head (d x vocab).  Embedding lookups, LayerNorm, gelu and the
    softmaxes are not matmuls and are left out, as is usual for MFU."""
    d = cfg["n_embd"]
    ratio = cfg.get("n_inner_ratio", 4)
    layer = 2 * (3 * d * d + d * d + 2 * ratio * d * d)
    attn = 2 * 2 * d * (seq_len + 1) / 2
    head = 2 * d * cfg["vocab_size"]
    return cfg["n_layer"] * (layer + attn) + head


def gpt_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (2x forward), no recompute."""
    return 3.0 * gpt_forward_flops_per_token(cfg, seq_len)


# ------------------------------------------------------------ ResNet-50


def _conv(h: int, w: int, k: int, cin: int, cout: int, stride: int):
    """(flops, h_out, w_out) of a 'same'-style conv as ResNet uses them
    (output = ceil(input / stride))."""
    ho, wo = -(-h // stride), -(-w // stride)
    return 2 * ho * wo * k * k * cin * cout, ho, wo


def resnet_forward_flops_per_image(cfg: dict) -> float:
    """Forward conv + dense FLOPs of one image through a bottleneck
    ResNet, v1.5 (the stride sits on the 3x3).  BatchNorm, ReLU and the
    pools are elementwise and left out."""
    size = cfg["image_size"]
    width = cfg["num_filters"]
    total, h, w = _conv(size, size, 7, 3, width, 2)
    h, w = -(-h // 2), -(-w // 2)  # 3x3 max pool, stride 2
    cin = width
    for stage, blocks in enumerate(cfg["stage_sizes"]):
        mid = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            f1, _, _ = _conv(h, w, 1, cin, mid, 1)
            f2, ho, wo = _conv(h, w, 3, mid, mid, stride)
            f3, _, _ = _conv(ho, wo, 1, mid, 4 * mid, 1)
            total += f1 + f2 + f3
            if cin != 4 * mid or stride != 1:
                fp, _, _ = _conv(h, w, 1, cin, 4 * mid, stride)
                total += fp
            h, w, cin = ho, wo, 4 * mid
    return total + 2 * cin * cfg["num_classes"]


def resnet_train_flops_per_image(cfg: dict) -> float:
    return 3.0 * resnet_forward_flops_per_image(cfg)


# ------------------------------------------------- flash attention kernel


def flash_train_flops_bytes(batch: int, heads: int, seq_len: int,
                            head_dim: int, layers: int,
                            dtype_bytes: int = 2):
    """(flops, bytes) one training step's flash-attention calls need,
    forward and backward, causal, over all layers, on one chip.

    Operations: the algorithm's seven S x S x head_dim matmuls per
    (sequence, head) — QK^T and PV forward; recomputed QK^T, dP = dO V^T,
    dV, dK and dQ backward (the flash backward has to recompute the
    scores: that one recompute is the algorithm, further ones are the
    implementation's).  A causal mask needs half of each.  Bytes: forward
    reads q, k, v and writes o; backward reads q, k, v, o, dO and writes
    dq, dk, dv — twelve S x head_dim arrays, each moved once (the row
    statistics are 1/head_dim of that and left out)."""
    per_matmul = 2 * seq_len * seq_len * head_dim / 2
    n = batch * heads * layers
    flops = 7 * per_matmul * n
    nbytes = 12 * seq_len * head_dim * dtype_bytes * n
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """(lower bound in seconds, which side bounds it)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return ((t_flops, "compute") if t_flops >= t_bytes
            else (t_bytes, "memory"))
