"""optimizer wrap and fusion: milliseconds a step spends outside the
differentiated loss and outside collectives: bucket pack and unpack
(``hvd_pack``, ``hvd_unpack``), the casts and scaling around the
all-reduces (``hvd_allreduce``), AdamW (``hvd_inner_update``) and
``apply_updates``. The all-reduces themselves are ``collective_ms``.
``gpt2m_dp4`` less ``gpt2m_dp1``, in lines of one chip call, is the
whole cost of the buckets whatever the fusions did."""

from benchmarks import scopes


def read(run):
    return scopes.read(run, lambda phase, layer, kernel:
                       phase == "optimizer")
