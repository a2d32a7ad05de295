"""model: milliseconds a step spends in the backward pass: instructions
traced under ``transpose(``, the loss head's backward half and the
flash kernels' dq and dkv with them (``benchmarks/scopes.py``)."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel:
                       phase == "backward")
