"""Mean wait of a request in the admission queue, from ``submit`` to the
dispatcher taking its batch (the ``serve.frontend.queue_wait_ms``
histogram, one value per request), in ms, over the window."""
from bench import readers


def read(ctx):
    return readers.histogram_mean(ctx, "serve.frontend.queue_wait_ms")
