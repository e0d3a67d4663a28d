"""Share of its roofline reached by the dense kernels (%): the least
time of the configuration's dense layers, at each flush's bucket rows
(the larger of int8 peak and HBM bound), over the device time of its
dense kernels (the configuration's kernel_families)."""
import readers


def read(ctx):
    return readers.roofline(ctx, "dense")
