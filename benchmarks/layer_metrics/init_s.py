"""world + mesh: seconds from ``hvd.init()`` to parameters made,
optimizer state made, parameters broadcast and the batch placed (the
reference check between them is not counted)."""


def read(run):
    return run.span_seconds("init")
