"""optimizer wrap and fusion: milliseconds a step spends on
instructions named by the scopes ``hvd_pack`` and ``hvd_unpack`` of
``optim/distributed._reduce_grad_tree``: gradients into fusion buckets
and back. Exactly 0 at one chip, where that path returns early. A
fusion counts under its own ``op_name``: an AdamW fusion that reads its
gradient straight out of a bucket is not counted here, so this is a
lower bound of what the buckets cost (``optimizer_ms`` has the rest)."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel: layer in (
        scopes.program.HVD_PACK, scopes.program.HVD_UNPACK))
