"""Of the matmul outputs that the rematerialised blocks of the step made
(a ``dot_general`` with no batch dimensions: every Dense projection of a
block), the share that the blocks kept for the backward pass because the
chip had room: ``remat.matmul_kept_mib{trace}`` over
``remat.eligible_mib{trace}``.  The program sets both while a model is
differentiated (``horovod_tpu/models/transformer.py:
block_remat_policy``), under the number of that trace in the process;
the step is the first thing the runner differentiates (the checks, with
the optimizer's state given back, trace the model again and find more
room), so the lowest number is read, from the program's own registry,
in this process, as ``moe_live_row_share`` reads its gauges.  0 where
the formula found no room; a program without the gauges (no ``remat``,
a backend that reports no memory, a tree of before the rule): None."""


def read(run):
    try:
        from horovod_tpu.obs.registry import get_registry
    except ImportError:
        return None
    traces = {}
    for m in get_registry().snapshot():
        if (m["name"] in ("remat.eligible_mib", "remat.matmul_kept_mib")
                and "trace" in m.get("tags", {})):
            traces.setdefault(int(m["tags"]["trace"]), {})[
                m["name"]] = m["value"]
    if not traces:
        return None
    step = traces[min(traces)]
    eligible = step.get("remat.eligible_mib")
    if not eligible:
        return None
    return step.get("remat.matmul_kept_mib", 0.0) / eligible
