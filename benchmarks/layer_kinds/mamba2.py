"""The ``mamba2`` kind of layer: a state-space mixer (Mamba-2, "state
space duality"). One input projection makes the gate z, the inner
stream x, the groups' B and C and a step size a head; a short causal
depthwise convolution runs over x, B and C; a recurrence a head keeps
a state of ``d_head x d_state``, h_t = exp(dt_t·A)·h_{t-1} + dt_t·x_t
B_t^T and y_t = h_t C_t + D·x_t; a gated norm and an output projection
follow. The contract of a kind is at the top of ``benchmarks/flops.py``.

Counted as required work, whatever implements it (arithmetic from the
equations, no measurement):

* ``mixer_macs``: the input projection ``h·(2·d_inner + 2·groups·
  d_state + heads)``, the convolution ``d_conv·(d_inner + 2·groups·
  d_state)`` and the output projection ``d_inner·h``, with ``d_inner =
  expand·h = heads·d_head`` (a group where that does not hold is
  refused).
* ``mixing_flops``: the recurrence a token, the state's update x_t B_t^T
  and its readout h_t C_t, a multiply-add each for every element of
  the state: ``2 · 2·heads·d_head·d_state``. The products a chunked
  form makes inside a chunk are an implementation's choice and not
  required work, as the flash backward's recomputed scores are not;
  the decay's elementwise multiply, the exponentials and D·x are left
  out, as softmax's are.
* ``kernel_work``: three times that over the step's positions (forward,
  and backward into the inputs and into the state's path), and, in
  bf16 and each moved once: forward x, dt, B, C read and y written;
  backward x, dt, B, C and dy read and dx, ddt, dB, dC written. A and
  D, a number a head, and the state between chunks are not counted."""

from benchmarks import flops, published

KEYS = {
    "mamba_n_heads": "heads of the recurrence; required",
    "mamba_d_head": "a head's width; required",
    "mamba_d_state": "the state's width N a head and group; required",
    "mamba_expand": "d_inner over hidden_size; required",
    "mamba_d_conv": "taps of the depthwise convolution; required",
    "mamba_n_groups": "groups that share B and C; absent, 1",
}
BOOKED_UNDER = "mamba2"
SOURCE_NAMES = ("mamba", "mamba2")
ROWS = (
    published.Row(("mamba_d_state",), published.WIDTH,
                  lambda m: m.get("mamba_d_state")),
    published.Row(("mamba_d_head",), published.WIDTH,
                  lambda m: m.get("mamba_d_head")),
    published.Row(("mamba_expand",), published.WIDTH,
                  lambda m: m.get("mamba_expand")),
    published.Row(("mamba_d_conv",), published.WIDTH,
                  lambda m: m.get("mamba_d_conv")),
    published.Row(("mamba_n_groups",), published.WIDTH,
                  lambda m: m.get("mamba_n_groups", 1)),
    # the chunk is how the source's own code cuts the scan: no count
    # here reads it, and the model group is held to it all the same
    published.Row(("mamba_chunk_size",), published.WIDTH,
                  lambda m: m.get("mamba_chunk_size")),
    published.Row(("mamba_n_heads",), published.COUNT,
                  lambda m: m.get("mamba_n_heads")),
)


def sizes(model: dict) -> tuple:
    """``(d_inner, heads, d_head, groups · d_state)``."""
    h, heads = model["hidden_size"], model["mamba_n_heads"]
    d_head, expand = model["mamba_d_head"], model["mamba_expand"]
    if expand * h != heads * d_head:
        raise ValueError(
            f"mamba_expand {expand} x hidden_size {h} is not "
            f"mamba_n_heads {heads} x mamba_d_head {d_head}: the inner "
            f"stream has one width")
    return (heads * d_head, heads, d_head,
            model.get("mamba_n_groups", 1) * model["mamba_d_state"])


def products(model: dict) -> list:
    """``(rows in, columns out)`` of the input and output projections
    (the convolution is no dense product)."""
    d_inner, heads, _, bc = sizes(model)
    h = model["hidden_size"]
    return [(h, 2 * d_inner + 2 * bc + heads), (d_inner, h)]


def mixer_macs(model: dict) -> int:
    d_inner, _, _, bc = sizes(model)
    return (sum(k * n for k, n in products(model))
            + model["mamba_d_conv"] * (d_inner + 2 * bc))


def mixing_flops(model: dict, traffic: dict) -> float:
    """The recurrence a data token: update and readout of a state of
    heads x d_head x d_state, two operations a multiply-add, at every
    position the token runs."""
    _, heads, d_head, _ = sizes(model)
    return (flops.positions_per_token(traffic)
            * 2 * 2 * heads * d_head * model["mamba_d_state"])


def kernel_work(model: dict, traffic: dict) -> dict:
    d_inner, heads, _, bc = sizes(model)
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    positions = tokens * flops.positions_per_token(traffic)
    forward = (d_inner + heads + 2 * bc) + d_inner
    backward = 2 * (d_inner + heads + 2 * bc) + d_inner
    return {"flops": 3 * tokens * mixing_flops(model, traffic),
            "bytes": positions * (forward + backward) * flops.BF16_BYTES}
