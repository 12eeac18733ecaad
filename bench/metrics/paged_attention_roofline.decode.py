"""Kernels (kernels/paged_attention.py): the paged attention kernel's
share of its roofline, from the trace and the peak table."""
from bench.readers import kernel_roofline

#: the kernel's op kind in the trace (the custom call takes the name of
#: the jitted wrapper in kernels/ops.py)
KERNEL = ("paged_ragged_attention",)


def read(run):
    return kernel_roofline(run, KERNEL)
