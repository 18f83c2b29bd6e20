"""Device time per step of the expert layers' sort by expert
(``horovod_tpu/parallel/moe.py:route``: the keys of the ``n k`` slots,
the stable ``argsort``, the two scatter-adds that count the group sizes
and the load, the dropped count; integers, so there is no backward): the
operations traced under the scope ``moe_sort``, inside ``moe_route``.  A
program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_sort"


def read(run):
    return tr.scope_ms(run, SCOPE)
