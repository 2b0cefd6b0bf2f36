"""Mean real (unpadded) rows per frontend dispatch, from the
``serve.frontend.batch_occupancy`` histogram over the window."""
from bench import readers


def read(ctx):
    return readers.histogram_mean(ctx, "serve.frontend.batch_occupancy")
