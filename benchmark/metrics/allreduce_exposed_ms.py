"""The part of ``allreduce_ms`` during which no other operation runs on
that device: what the collective adds to the step."""

from benchmark.harness import registry


def read(run):
    return registry.sibling_metric(__file__, "allreduce_ms").per_device(
        run, 1)
