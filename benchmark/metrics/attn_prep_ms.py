"""Device time per step of what stands between an attention layer's
fused q/k/v matmul and its attention call
(``models/transformer.py:attention_mixer``: the split into heads, the
norm over each head of ``q`` and ``k``, the rotation by position and,
where the kernels run, the head-major layout the flash kernels read),
forward, backward and whatever of it is recomputed: the operations
traced under the scope ``attn_prep``, inside ``attn``.  The kernels
``attn_prep_fwd`` and ``attn_prep_bwd`` of
``horovod_tpu/ops/attn_prep.py`` where its ``plan`` takes the call, else
XLA's fusions; it reads the scope and no kernel name.  A program without
the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "attn_prep"


def read(run):
    return tr.scope_ms(run, SCOPE)
