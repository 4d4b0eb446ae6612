"""Set-up: from the process's first statement to the window's start
(JAX on the chip, weights, the scan pool, the engine, the warm-up
request). Host clock."""


def read(run):
    return run.setup_s
