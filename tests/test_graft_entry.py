"""``__graft_entry__``'s two entry points: the flagship forward on one
device and the dry run of the full training step over eight.  (Moved whole
from ``tests/test_models.py``.)"""

import jax
import pytest


def test_graft_entry_single_device():
    import __graft_entry__ as g

    fn, example = g.entry()
    out = jax.jit(fn)(*example)
    assert out.shape == (8, 1000)


@pytest.mark.multiprocess
def test_graft_entry_dryrun_multichip():
    import __graft_entry__ as g

    g.dryrun_multichip(8)
