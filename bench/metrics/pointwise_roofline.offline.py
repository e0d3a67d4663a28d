"""Share of its roofline reached by the residual 1x1 layers (%): the
least time of the configuration's 1x1 binary convs and classifier, at
each flush's bucket rows (the larger of int8 peak and HBM bound), over
the device time of its pointwise kernels and the XLA operations that go
with them (the configuration's kernel_families)."""
import readers


def read(ctx):
    return readers.roofline(ctx, "pointwise")
