"""model: milliseconds a step spends around the routed MLP's expert
products, both directions: the router's scores, the choice and its
renormalisation, the ordering of the (token, choice) pairs, the gather
of their rows and the weighted combine; instructions named by the scope
``moe_dispatch`` (``horovod_tpu/models/moe.py``). Nothing on a program
that has no such scope."""

from benchmarks import scopes


def read(run):
    scope = getattr(scopes.program, "MOE_DISPATCH", None)
    return scope and scopes.read(
        run, lambda phase, layer, kernel: layer == scope)
