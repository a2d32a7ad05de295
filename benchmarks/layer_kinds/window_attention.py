"""The ``window_attention`` kind of layer: ``attention``'s projections
and kernels, with a query that sees only the keys of a sliding window:
under a causal mask the ``sliding_window`` = w latest positions, itself
among them (key k of query q where 0 <= q - k < w); under a full mask
the keys nearer than w on either side (|q - k| < w). Arithmetic only:
no cell runs it, and the flash kernels have no window's tile range yet.
The contract of a kind is at the top of ``benchmarks/flops.py``."""

from benchmarks import flops, published

attention = flops.load_kind("attention")

KEYS = {
    **attention.KEYS,
    "sliding_window": "the window w in positions; required. A window "
                      "of the sequence's length or more reads exactly "
                      "as the attention kind",
}
BOOKED_UNDER = attention.BOOKED_UNDER
SOURCE_NAMES = ("sliding_attention",)
ROWS = (
    published.Row(("sliding_window",), published.WIDTH,
                  lambda m: m.get("sliding_window")),
)
mixer_macs = attention.mixer_macs
products = attention.products


def visible_pairs(model: dict, traffic: dict) -> float:
    """(query, key) pairs of one sequence of T positions that a window
    of w shows, in ``flops.visible_pairs``' convention: under a causal
    mask the diagonal's T/2 are left out (so that w >= T reads T^2/2,
    as causal does): T·w - w·(w - 1)/2 - T/2; under a full mask every
    pair: T·(2w - 1) - w·(w - 1)."""
    if model.get("diffusion_block"):
        raise ValueError(
            "window_attention under a block-diffusion mask "
            "(diffusion_block) is not counted: no source has it")
    t = traffic["seq_len"]
    w = min(model["sliding_window"], t)
    if model["causal"]:
        return t * w - w * (w - 1) / 2 - t / 2
    return float(t * (2 * w - 1) - w * (w - 1))


def mixing_flops(model: dict, traffic: dict) -> float:
    """``attention``'s, over the pairs the window shows."""
    return attention.mixing_over(model, traffic,
                                 visible_pairs(model, traffic))


def kernel_work(model: dict, traffic: dict) -> dict:
    """``attention``'s six products over the pairs the window shows and
    its twelve arrays, each still moved once."""
    return attention.work_over(model, traffic,
                               visible_pairs(model, traffic))
