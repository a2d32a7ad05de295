"""Operations and bytes a cell's work requires, from its shapes alone.

The benchmark's own arithmetic (the program's ``utils/mfu.py`` counts
6·N·D with every parameter and no attention term). "Required" means
what the forward and backward passes need if nothing is recomputed: a
matrix multiplication costs 2·m·n·k forward and twice that backward, so
training is three times the forward count.

Of the traffic it reads ``objective``, ``seq_len`` (the **data tokens**
of a sequence, which ``tokens_per_s_per_chip`` counts: what a user pays
for), ``batch_per_chip`` and what the objective needs
(``mask_fraction``; ``t_min``). Under ``block_diffusion`` a sequence of
T data tokens is noised block by block and run beside its clean copy:
every layer runs 2T positions, the mask shows T^2 + T*b of their pairs,
and the head is required at the masked positions of the noisy half.

A step is counted from the configuration's ``model`` group as it is
run, which may be one chip's share of a deployment, by kind of layer.

**A kind of layer is a file**, ``benchmarks/layer_kinds/<kind>.py``,
found by the name the ``model`` group gives under the run's root and
then among the harness's own (:func:`load_kind`), as
``harness.load_reference`` finds a family's reference: a new
architecture brings its mixer's count as a new file, and a name no
file has is refused by name, with the kinds there are. The ``model``
group may state ``layer_types``: ``num_layers`` names, one a layer, in
order (the leading dense layers included); absent, every layer is
``attention``. What a layer has beside its mixer, its MLP (plain or
routed, by ``dense_layers``), is counted here for every kind. A kind's
file has (``tests/benchmarks/test_bench_kinds.py`` holds every file in
the directory to this):

* ``KEYS``: ``{key of the model group it reads: what the key stands
  for and how the kind reads a group without it}``; beside them its
  functions ask the group for ``hidden_size`` alone.
* ``mixer_macs(model)``: multiply-adds a position of the mixer's dense
  products and convolutions, what ``blocks`` counts beside the layer's
  MLP.
* ``mixing_flops(model, traffic)``: forward operations a data token of
  what is not a dense product (scores and weighted values, a
  recurrence), and ``BOOKED_UNDER``: the part of
  ``forward_flops_per_token`` they are booked under, ``attention`` for
  a kind that runs the attention kernels, the kind's own name
  otherwise.
* ``kernel_work(model, traffic)``: ``{"flops", "bytes"}``, the required
  operations and least HBM bytes of ONE layer's kernel in one training
  step on one chip, the same work whatever implements it; or None.
* ``products(model)``: ``(rows in, columns out)`` of each of the mixer's
  weights, for a projections' roofline; None where the kind has shapes
  the file does not state.
* ``SOURCE_NAMES``: how a source spells the kind in its
  ``layer_types``; ``ROWS``: the ``published.Row``s that hold the
  kind's own sizes to the source (``benchmarks/published.py`` holds
  them for a file whose pattern names the kind).

The keys this file itself reads, and what a key's absence means (a test
holds this list, with the lists of the kinds, to the keys the functions
touch):

* ``hidden_size``, ``num_layers``, ``mlp_ratio``, ``vocab_size``:
  required. ``num_layers`` counts the leading dense layers and the
  layers after them, not the MTP layers; ``vocab_size`` is the rows of
  the head that are held here.
* ``layer_types``: absent, every layer is ``attention``.
* ``activation``: ``swiglu`` has three matrices a MLP or an expert,
  anything else, or absent, two.
* ``num_experts``: the router's width, as published; 0 or absent, every
  layer has a plain MLP of ``hidden_size * mlp_ratio``.
* ``experts_per_token``: the experts the router sends a token to, read
  where there are experts.
* ``experts_held``: the routed experts of a layer that this chip
  holds; absent, all ``num_experts``.
* ``expert_mlp_dim``: one expert's width, routed or shared; absent,
  ``hidden_size * mlp_ratio``.
* ``shared_experts``: experts every token goes through; absent, 0.
* ``dense_layers``: leading layers with a plain MLP of
  ``hidden_size * mlp_ratio`` in a model that has experts; absent, 0.
* ``mtp_layers``: further prediction heads (multi-token prediction),
  each a block whose mixer is ``attention``; absent, 0.
"""

from __future__ import annotations

import collections
import importlib.util
import os

BF16_BYTES = 2
HERE = os.path.dirname(os.path.abspath(__file__))
KINDS_DIR = "layer_kinds"
DEFAULT_KIND = "attention"
# the root a run's files are found under, where it is not the
# checkout (the tests' fixtures): ``harness.load_cell`` sets it, one
# cell a process. A kind's file is looked for there first
_kinds_root = None
_loaded: dict = {}  # path -> module


def kinds_root(root: str | None) -> None:
    """Kinds are looked for under ``<root>/benchmarks/layer_kinds``
    before the harness's own; None, the harness's own alone."""
    global _kinds_root
    _kinds_root = root


def _kind_dirs() -> list:
    dirs = [os.path.join(HERE, KINDS_DIR)]
    if _kinds_root:
        under = os.path.join(os.path.abspath(_kinds_root), "benchmarks",
                             KINDS_DIR)
        if under != dirs[0]:
            dirs.insert(0, under)
    return dirs


def kinds_there() -> list:
    """Names of the kinds that have a file, the run's root's and the
    harness's own."""
    return sorted({f[:-3] for d in _kind_dirs() if os.path.isdir(d)
                   for f in os.listdir(d)
                   if f.endswith(".py") and not f.startswith("_")})


def load_kind(name: str):
    """The module of one kind of layer (its contract is at the top of
    this file), loaded from ``layer_kinds/<name>.py`` under the run's
    root or else the harness's own; a name no file has is refused."""
    # a kind's name is a file's: nothing that leads out of the directory
    dirs = _kind_dirs() if str(name).isidentifier() else []
    for path in (os.path.join(d, f"{name}.py") for d in dirs):
        if path not in _loaded and os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(
                f"benchmarks_layer_kind_{name}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            _loaded[path] = module
        if path in _loaded:
            return _loaded[path]
    raise ValueError(
        f"layer kind {name!r}: no file benchmarks/{KINDS_DIR}/{name}.py "
        f"under the run's root or the harness's own; the kinds there "
        f"are {kinds_there()}. A new kind of layer comes as that file "
        f"(its contract is at the top of benchmarks/flops.py)")


def layer_kinds(model: dict) -> tuple:
    """The kind of each of the ``num_layers`` layers, in order: the
    ``model`` group's ``layer_types``, or ``attention`` for every
    layer where it states none."""
    layers = model["num_layers"]
    pattern = model.get("layer_types")
    if pattern is None:
        return (DEFAULT_KIND,) * layers
    if len(pattern) != layers or not all(
            isinstance(k, str) for k in pattern):
        raise ValueError(
            f"layer_types names {len(pattern)} layers and num_layers is "
            f"{layers}: it is one kind's name (a string) a layer, in "
            f"order, the leading dense layers included")
    return tuple(pattern)


def layers_by_kind(model: dict) -> dict:
    """``{kind's name: how many layers are of it}``, in the order the
    pattern first names each."""
    return dict(collections.Counter(layer_kinds(model)))


def layers_of(model: dict, part: str = DEFAULT_KIND) -> dict:
    """``{kind's module: how many layers}`` of the kinds whose mixing is
    booked under ``part`` of :func:`forward_flops_per_token`:
    ``attention`` is every kind that runs the attention kernels."""
    found = {}
    for name, count in layers_by_kind(model).items():
        kind = load_kind(name)
        if kind.BOOKED_UNDER == part:
            found[kind] = count
    return found


def head_positions_per_token(traffic: dict, ahead: int = 1) -> float:
    """Share of a sequence's data tokens at which a vocabulary head is
    required: the first head predicts the next token (``ahead`` 1), a
    further prediction head the one after it (``ahead`` 2)."""
    if traffic["objective"] == "causal_lm":
        t = traffic["seq_len"]
        return (t - ahead) / t  # the last positions predict nothing
    if traffic["objective"] == "masked_lm":
        return float(traffic["mask_fraction"])
    if traffic["objective"] == "block_diffusion":
        # a block's tokens are masked with probability t ~ U(t_min, 1]
        return (1.0 + traffic["t_min"]) / 2
    raise ValueError(f"unknown objective {traffic['objective']!r}")


def positions_per_token(traffic: dict) -> int:
    """Positions every layer runs for one data token: a block-diffusion
    step runs the noisy copy of a sequence beside the clean one."""
    return 2 if traffic["objective"] == "block_diffusion" else 1


def visible_pairs(model: dict, traffic: dict) -> float:
    """(query, key) pairs of one sequence that the mask shows: what the
    scores and the weighted values are required over.

    Full: T^2. Causal: counted as half of that, as since PR 22 (the
    diagonal's T/2 are left out). Block diffusion, block length b, over
    the 2T positions ``[noisy ; clean]`` with blk(i) = (i mod T) // b: a
    noisy query sees the noisy keys of its own block (T*b pairs) and the
    clean keys of earlier blocks (T^2/2 - T*b/2), a clean query the
    clean keys of its own and earlier blocks (T^2/2 + T*b/2):
    T^2 + T*b."""
    t = traffic["seq_len"]
    block = model.get("diffusion_block")
    if block:
        return t * t + t * block
    return t * t * (0.5 if model["causal"] else 1.0)


def head_dim(model: dict) -> int:
    if model.get("head_dim"):
        return model["head_dim"]
    width, rest = divmod(model["hidden_size"], model["num_heads"])
    if rest:
        raise ValueError(
            f"hidden_size {model['hidden_size']} over num_heads "
            f"{model['num_heads']} is no whole head width and the model "
            f"group states none (head_dim, or qk_nope_head_dim + "
            f"qk_rope_head_dim and v_head_dim)")
    return width


def kv_heads(model: dict) -> int:
    return model.get("num_kv_heads") or model["num_heads"]


def qk_head_dim(model: dict) -> int:
    """Width of one head's query and key: what the scores are taken
    over."""
    if "qk_nope_head_dim" in model:
        return model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return head_dim(model)


def v_head_dim(model: dict) -> int:
    """Width of one head's value and output."""
    return model.get("v_head_dim") or head_dim(model)


def projection_macs(model: dict) -> int:
    """Multiply-adds a token of one ``attention`` layer's projections
    (``layer_kinds/attention.py``)."""
    return load_kind(DEFAULT_KIND).mixer_macs(model)


def mlp_macs(model: dict) -> tuple:
    """Multiply-adds a token of (a plain MLP, the last kind of layer's
    MLP): the same where there are no experts. An expert layer is its
    shared experts for every token, the router at its published width,
    and ``experts_per_token * experts_held / num_experts`` routed
    experts: the expectation, under even routing, of how many of a
    token's experts live on this chip (all of them where all are held).
    How near routing is to even is for the program's counter of tokens
    a held expert to show; the required work does not follow it."""
    h = model["hidden_size"]
    matrices = 3 if model.get("activation") == "swiglu" else 2
    dense = matrices * h * int(h * model["mlp_ratio"])
    experts = model.get("num_experts", 0)
    if not experts:
        return dense, dense
    one = matrices * h * model["expert_mlp_dim"] \
        if "expert_mlp_dim" in model else dense
    routed = (model["experts_per_token"]
              * model.get("experts_held", experts) / experts)
    return dense, (model.get("shared_experts", 0) * one + routed * one
                   + h * experts)


def attention_flops_per_layer(model: dict, traffic: dict) -> float:
    """Scores and weighted values of one ``attention`` layer, a data
    token (``layer_kinds/attention.py``)."""
    return load_kind(DEFAULT_KIND).mixing_flops(model, traffic)


def forward_flops_per_token(model: dict, traffic: dict) -> dict:
    """Forward operations per data token, by part: ``blocks``, the
    matrix multiplications and convolutions of the ``num_layers``
    layers, each its kind's mixer (``layer_kinds``) and its MLP
    (``dense_layers`` of them a plain one), at every position a data
    token runs (``positions_per_token``); ``attention``, the scores and
    weighted values, over the pairs the mask shows, of the layers whose
    kind books there; one further part, under its own name, for each
    kind that books elsewhere (a recurrence); ``head``, the vocabulary
    head at the positions that have a target; and, where there are
    ``mtp_layers``, ``mtp``: for each one block of the last kind of
    MLP with an ``attention`` mixer, the product that takes the hidden
    state beside the next token's embedding from 2h to h, its
    attention, and the head once more at the positions that have a
    token two ahead. The keys read are listed at the top of this file
    and in the kinds' ``KEYS``."""
    h = model["hidden_size"]
    layers = model["num_layers"]
    dense_layers = model.get("dense_layers", 0) \
        if model.get("num_experts", 0) else layers
    dense, last = mlp_macs(model)
    positions = positions_per_token(traffic)
    mixers, mixing = 0, {}
    for name, count in layers_by_kind(model).items():
        kind = load_kind(name)
        mixers += count * kind.mixer_macs(model)
        mixing[kind.BOOKED_UNDER] = mixing.get(kind.BOOKED_UNDER, 0) \
            + count * kind.mixing_flops(model, traffic)
    blocks = positions * 2 * (
        mixers + dense_layers * dense + (layers - dense_layers) * last)
    head = 2 * h * model["vocab_size"]
    parts = {"blocks": blocks, **mixing,
             "head": head * head_positions_per_token(traffic)}
    if model.get("mtp_layers", 0):
        attention = load_kind(DEFAULT_KIND)
        parts["mtp"] = model["mtp_layers"] * (
            positions * (2 * (attention.mixer_macs(model) + last)
                         + 2 * 2 * h * h)
            + attention.mixing_flops(model, traffic)
            + head * head_positions_per_token(traffic, ahead=2))
    return parts


def train_flops_per_token(model: dict, traffic: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(model, traffic).values())


def attention_kernel_work(model: dict, traffic: dict) -> dict:
    """Required operations and least HBM bytes of one training step's
    attention on one chip: each layer's ``kernel_work`` over the layers
    whose kind runs the attention kernels (``layers_of``), and
    ``mtp_layers`` further ``attention`` layers."""
    work = {"flops": 0.0, "bytes": 0.0}
    counts = layers_of(model)
    if model.get("mtp_layers", 0):
        attention = load_kind(DEFAULT_KIND)
        counts[attention] = counts.get(attention, 0) + model["mtp_layers"]
    for kind, count in counts.items():
        one = kind.kernel_work(model, traffic)
        for key in work:
            work[key] += float(count * one[key])
    return work


def roofline_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """Least time the chip could take for ``work`` and which peak
    bounds it."""
    by_flops = work["flops"] / peak["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    if by_flops >= by_bytes:
        return by_flops, "compute"
    return by_bytes, "hbm"
