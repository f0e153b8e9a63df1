"""From JAX holding the chip to the window's first due time: weights,
server, compiles (or compile-cache loads) and warm-up (host clock).
The interpreter, JAX's import and the TPU runtime's start-up come
before it and are left out: they vary by seconds with the host (8.3 to
14.2 s over six runs on one TPU v5e host) while what followed varied by a
third of a second, and no change to the program moves them."""


def read(run):
    return run.setup_s
