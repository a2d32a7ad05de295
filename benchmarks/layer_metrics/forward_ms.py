"""model: milliseconds a step spends in the forward pass: instructions
traced under ``jvp(`` and no ``transpose(``, the loss head's forward
half with them (``benchmarks/scopes.py`` has the rules)."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel:
                       phase == "forward")
