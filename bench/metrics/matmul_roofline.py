"""The matmul kernel's share of its roofline: the least time its calls
could take on this chip (2 n^3 FLOPs at the bf16 peak, or 3 n^2 f32 blocks
at HBM bandwidth, whichever is longer: compute) over the summed device
time of its events in the trace."""

from yardstick.kernel_roofline import read_kernel

KERNEL = "matmul"


def read(run):
    return read_kernel(run, KERNEL)
