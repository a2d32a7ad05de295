"""model: milliseconds a step spends in the norms on a block's two
branches before they join the residual (N2 and N4 of a block of four
norms), both directions: instructions named by the scope ``post_norm``
(``horovod_tpu/utils/scopes.POST_NORM``, set in
``horovod_tpu/models/transformer.Block``). ``norm_ms`` holds the other
norms (``ln_attn``, ``ln_mlp``, ``ln_final``). **It reads the unfused
remainder only**: an instruction counts under its root's ``op_name``,
and the chip's compiler fuses most of these norms into a neighbour
(the product before, the residual add after), whose name the fusion
then carries: at the published sizes 1.7 to 2.0 ms a step are read
here while fusions that hold a ``post_norm`` operation under another
name take 17.5 to 20.1 ms (PERF.md sections 5 and 6, PR 48). A fall
here can mean a faster norm or one more fusion. Nothing on a program
that has no such scope, or a model whose blocks have two norms."""

from benchmarks import scopes


def read(run):
    scope = getattr(scopes.program, "POST_NORM", None)
    return (scope and scopes.read(
        run, lambda phase, layer, kernel: layer == scope)) or None
