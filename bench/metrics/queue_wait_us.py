"""Mean time a request waits in the pipeline's queue (``index/pipeline.py``
``AsyncIndexService``), in microseconds: its ``pipeline.wait`` span, from
the enqueue to the start of the flush that serves it."""

from bench import spans


def read(ctx):
    return spans.mean_us(ctx, "pipeline.wait")
