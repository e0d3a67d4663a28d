"""Share of its roofline reached by the conv kernels (%): the least
time of the configuration's conv layers, at each flush's bucket rows
(the larger of int8 peak and HBM bound), over the device time of its
conv kernels (the configuration's kernel_families)."""
import readers


def read(ctx):
    return readers.roofline(ctx, "conv")
