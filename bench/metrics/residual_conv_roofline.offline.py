"""Share of its roofline reached by the residual conv layers (%): the
least time of the configuration's 3x3 binary convs and stem, at each
flush's bucket rows (the larger of int8 peak and HBM bound), over the
device time of its residual conv kernels and the XLA operations that
go with them (the configuration's kernel_families)."""
import readers


def read(ctx):
    return readers.roofline(ctx, "residual_conv")
