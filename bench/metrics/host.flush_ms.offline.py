"""Host part of one flush: ``serve.flush`` less its ``serve.compute``
(pad, pack, dispatch, complete), mean over the window's flushes (ms)."""
import readers


def read(ctx):
    return readers.host_flush_ms(ctx)
