"""The tile Cholesky's update kernel's share of its roofline: 2 n^3 FLOPs
at the bf16 peak, or four f32 tiles (C, A, B read, the result written) at
HBM bandwidth, whichever is longer (compute, narrowly), over the summed
device time of its Pallas ops (``gemm_nt.N``) in the trace."""

from yardstick.tile_roofline import read_pallas

KERNEL = "gemm_nt"


def read(run):
    return read_pallas(run, KERNEL)
