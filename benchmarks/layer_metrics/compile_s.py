"""compile: seconds in ``lowered.compile(...)`` of the train step; the
persistent cache shortens it after a checkout's first run."""


def read(run):
    return run.span_seconds("compile")
