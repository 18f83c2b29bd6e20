"""Device time per step of the step's own noising (a level a block, the
masked positions, the noised copy laid beside the clean one): the
operations traced under the scope ``diffusion_noise``
(``horovod_tpu/models/block_diffusion.py:noised_inputs``).  A program
without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "diffusion_noise"


def read(run):
    return tr.scope_ms(run, "diffusion_noise")
