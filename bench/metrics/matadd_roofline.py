"""The matadd kernel's share of its roofline: the least time its calls
could take on this chip (3 n^2 f32 blocks at HBM bandwidth: memory) over
the summed device time of its events in the trace."""

from yardstick.kernel_roofline import read_kernel

KERNEL = "matadd"


def read(run):
    return read_kernel(run, KERNEL)
