"""The panel's triangular solve's share of its roofline: n^3 FLOPs at the
bf16 peak, or three f32 tiles at HBM bandwidth (memory, at side 2048),
over the device time of every op of the module ``jit_trsm``, for the
``trsm`` kernels the program counted."""

from yardstick.tile_roofline import read_module

KERNEL = "trsm"


def read(run):
    return read_module(run, KERNEL)
