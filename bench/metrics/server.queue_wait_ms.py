"""Mean queue wait of a request, submit to flush start, from the
server's ``serve.queue_wait`` spans (ms).  Moves latency_p95_ms."""
import readers


def read(ctx):
    return readers.mean(readers.span_ms(ctx, "serve.queue_wait"))
