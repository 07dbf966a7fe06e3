"""Mean keys per fused flush of the pipeline (``index/pipeline.py``
``AsyncIndexService``), from its ``pipeline.flush`` channel rows
``(cause, fused_batch)`` recorded in the window."""


def read(ctx):
    rows = ctx.channels.get("pipeline.flush")
    if rows is None or rows.size == 0:
        return None
    return float(rows[:, 1].mean())
