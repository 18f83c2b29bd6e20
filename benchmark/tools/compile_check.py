#!/usr/bin/env python3
"""Compile a training cell's step at its real size for a described
``v5e:2x2``, without the chip (on-chip-measurement guide, section 2):

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_check.py <cell> ...

Prints each cell's per-device memory as the TPU compiler counts it,
whether the Pallas kernel, an all-reduce and the optimizer's scope are in
the compiled text, and the family's model FLOPs per item.
Nothing runs, so this says nothing about results or times.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def check(cell_name: str) -> dict:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from benchmark.harness import registry
    from horovod_tpu.ops import flash_attention

    # The program picks interpret mode from the default backend, which is
    # the CPU here; the compile is for the chip.
    flash_attention._interpret_for_backend = lambda backend: False
    cell = registry.load_cell(cell_name)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:cell["chips"]], dtype=object),
                (hvd.DP_AXIS,))
    config = cell["config_values"]
    builder = registry.load_model_builder(config["family"])
    built = builder.build(config, cell["params"], 0, described_mesh=mesh)
    compiled = built.step.lower(*built.state).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2 ** 30
    return {
        "cell": cell_name, "chips": cell["chips"],
        "argument_gib": mem.argument_size_in_bytes / gib,
        "output_gib": mem.output_size_in_bytes / gib,
        "alias_gib": mem.alias_size_in_bytes / gib,
        "temp_gib": mem.temp_size_in_bytes / gib,
        "total_gib": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      - mem.alias_size_in_bytes
                      + mem.temp_size_in_bytes) / gib,
        "tpu_custom_call": "tpu_custom_call" in text,
        "all_reduce": "all-reduce" in text,
        "optimizer_scope": "optimizer_update/add" in text,
        "model_flops_per_item": builder.train_flops_per_item(
            config, built.ran),
    }


if __name__ == "__main__":
    import json

    for name in sys.argv[1:]:
        print(json.dumps(check(name)), flush=True)
