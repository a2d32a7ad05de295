"""compile: seconds tracing and lowering the train step
(``step.lower(...)``); no cache shortens it."""


def read(run):
    return run.span_seconds("lower")
