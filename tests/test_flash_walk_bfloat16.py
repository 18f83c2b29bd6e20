"""The bfloat16 cases of ``tests/test_flash_walk.py``'s table walk against
the rectangle, in a file of their own (a file is one worker's load, and
the sixty cases together were the run's longest)."""

from __future__ import annotations

import jax.numpy as jnp

from test_flash_walk import walk_case, walk_cases


@walk_cases(jnp.bfloat16)
def test_the_table_walk_is_the_rectangle_to_the_bit(
        request, causal, window, h, hkv, dv, dtype, form):
    walk_case(request.node.callspec.id, causal, window, h, hkv, dv, dtype,
              form)
