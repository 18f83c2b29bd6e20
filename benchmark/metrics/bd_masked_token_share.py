"""Of the data tokens of the last step, the share the step's noise
masked: the count the step's carry holds (``bd.masked_tokens``,
published from the final carry by
``horovod_tpu/models/block_diffusion.py:publish_masked``), which the
family's builder leaves under ``ran["block_diffusion"]``, over the
step's items.  Levels uniform in ``[0.001, 1)`` a block mask half the
tokens on average; 0 or 1 says the noise is not running.  A program
without the counter: None."""


def read(run):
    counted = run["ran"].get("block_diffusion") or {}
    if counted.get("masked_tokens") is None or not run["items_per_step"]:
        return None
    return counted["masked_tokens"] / run["items_per_step"]
