"""The diagonal tile's Cholesky factorization's share of its roofline:
n^3 / 3 FLOPs at the bf16 peak, or two f32 tiles at HBM bandwidth (memory,
at side 2048), over the device time of every op of the module
``jit_potrf``, for the ``potrf`` kernels the program counted."""

from yardstick.tile_roofline import read_module

KERNEL = "potrf"


def read(run):
    return read_module(run, KERNEL)
