"""Host clock around ``.lower().compile()`` of the cell's step program
(a load from the persistent cache after the first run in a checkout)."""


def read(run):
    return run.get("compile_s")
