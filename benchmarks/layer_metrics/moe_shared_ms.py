"""model: milliseconds a step spends in the shared expert every token
goes through beside its routed ones, both directions: instructions
named by the scope ``moe_shared``
(``horovod_tpu/utils/scopes.MOE_SHARED``, set in
``horovod_tpu/models/moe.py``): its three products and its activation.
Under ``remat`` a rematerialised block's second run counts as backward;
at one chip AdamW rides in the weight-gradient fusions. Nothing on a
program that has no such scope, or a model with no shared expert."""

from benchmarks import scopes


def read(run):
    scope = getattr(scopes.program, "MOE_SHARED", None)
    return (scope and scopes.read(
        run, lambda phase, layer, kernel: layer == scope)) or None
