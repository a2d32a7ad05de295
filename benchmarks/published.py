"""A configuration file held to the source it names.

A file ``benchmarks/configs/<name>.json`` says what was published and
what is run. What was published is its ``published`` group, or, where
there is none, its own top-level keys (the form of a catalog entry's
``config``, where a key that was cut holds the value that is run).
What is run is its ``model`` group: the keyword arguments of the
program's model, built as written. :func:`check` holds the second to
the first through ROWS, the one written mapping between a source's key
and the ``model`` group, and refuses with a ``ValueError`` that names
the key:

* every key of a row that the source has equals the ``model`` group's
  value, except the keys that ``reduced`` names;
* ``reduced`` is a list of ``{"key", "published", "held", "why"}``, the
  key as the source spells it; ``published`` is the source's value,
  ``held`` the ``model`` group's, and the ``BENCHMARK.json`` entry's
  ``reduced`` lists the same keys;
* depth may be cut freely, to the leading dense layers (the ``model``
  group's ``dense_layers``, which count once) and whole periods of the
  layer pattern after them (the file's ``layer_period``, 1 where it
  states none);
* a count (experts, heads, rows of the vocabulary or of the position
  table) may be cut only in a file that states the ``deployment`` it is
  one chip's share of: ``{"chips": n, "divided": "how"}``. Where the
  experts are cut the ``model`` group's ``experts_held`` is what is
  held, and its ``num_experts``, the router's width, stays as
  published;
* a cut keeps to the floors of the ``model-configs`` guide: at least
  four layers after the leading dense ones in a model that has experts,
  at least 8 experts held, at least an eighth of the vocabulary;
* a width (hidden, head, latent, MLP or expert width, experts per
  token) never, and neither what makes a mechanism what it is (how
  many shared experts, leading dense layers and further prediction
  heads, how the chosen experts' weights are scaled);
* a source's ``layer_types``, one name a published layer, holds the
  ``model`` group's (:func:`hold_pattern`): each name is some kind's
  spelling (``SOURCE_NAMES`` of a file in ``benchmarks/layer_kinds``);
  the file's ``layer_period`` really is a period of the published list
  after the leading dense layers; the run pattern is the first layers
  of the published one (and, by the rule of depth above, the dense
  layers and whole periods of it); and a model of more than one kind
  of layer keeps at least four layers after the dense ones, experts or
  none. In either form of file the source's ``layer_types`` is the
  published list whole, and the ``model`` group's says what is run. The
  ``ROWS`` of a kind hold that kind's own sizes for a file whose
  pattern names it. A file with no ``layer_types`` is held as it was
  before a layer had a kind;
* a key of the source that no row knows is refused, unless the file's
  ``not_held`` lists it with a word on why it says nothing of the
  shape: ``{"rope_theta": "a constant of the position code"}``. In the
  top-level form every key of the file that FILE_KEYS does not name is
  a key of the source. A ``benchmark`` PR adds a row.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

from benchmarks import flops

# WIDTH is every key that is never cut: a width, and what makes a
# mechanism what it is
DEPTH, COUNT, WIDTH = "depth", "count", "width"
NEVER_CUT = (
    "is never cut: a width (hidden, head, latent, MLP or expert width, "
    "experts per token) or what makes a mechanism what it is (shared "
    "experts, leading dense layers, further prediction heads, the "
    "scaling of the chosen experts' weights, a tied head)")
# what a configuration file has of its own beside the source's keys
FILE_KEYS = {"name", "source", "family", "note", "published", "model",
             "tiny", "layer_period", "deployment", "reduced", "assumed",
             "not_held", "departures"}
REDUCED_KEYS = {"key", "published", "held", "why"}


class Row(NamedTuple):
    """One line of the mapping: the source's spellings, the kind, what
    the ``model`` group holds of it; what of the ``model`` group equals
    the published value even where the key is cut; and the least a cut
    may hold, with the floor's name, given the published value and the
    ``model`` group."""
    keys: tuple
    kind: str
    held: Callable
    as_published: Callable | None = None
    floor: Callable | None = None


ROWS = (
    Row(("n_layer", "num_hidden_layers"), DEPTH,
        lambda m: m["num_layers"],
        floor=lambda pub, m: (
            m.get("dense_layers", 0) + 4 if m.get("num_experts") else 1,
            "four layers after the leading dense ones where there are "
            "experts")),
    Row(("n_embd", "hidden_size"), WIDTH, lambda m: m["hidden_size"]),
    Row(("n_head", "num_attention_heads"), COUNT,
        lambda m: m["num_heads"]),
    Row(("num_key_value_heads",), COUNT, flops.kv_heads),
    Row(("head_dim",), WIDTH, flops.head_dim),
    # the width of a plain MLP, and of one expert where the source
    # gives the experts no width of their own
    Row(("n_inner", "intermediate_size"), WIDTH,
        lambda m: m["hidden_size"] * m["mlp_ratio"]),
    # a source that names the MLP every token goes through apart from
    # its experts' width: the plain MLP where there are no experts, the
    # shared experts together where there are
    Row(("shared_intermediate_size",), WIDTH,
        lambda m: (m.get("shared_experts", 0) * m.get(
            "expert_mlp_dim", m["hidden_size"] * m["mlp_ratio"])
            if m.get("num_experts") else
            m["hidden_size"] * m["mlp_ratio"])),
    Row(("moe_intermediate_size",), WIDTH,
        lambda m: m.get("expert_mlp_dim")),
    # the experts held here; the router keeps the published width
    Row(("num_experts", "n_routed_experts", "num_local_experts"), COUNT,
        lambda m: m.get("experts_held", m.get("num_experts", 0)),
        as_published=lambda m: m.get("num_experts", 0),
        floor=lambda pub, m: (8, "8 experts held")),
    Row(("num_experts_per_tok",), WIDTH,
        lambda m: m.get("experts_per_token", 0)),
    Row(("n_shared_experts",), WIDTH,
        lambda m: m.get("shared_experts", 0)),
    Row(("first_k_dense_replace",), WIDTH,
        lambda m: m.get("dense_layers", 0)),
    Row(("num_nextn_predict_layers",), WIDTH,
        lambda m: m.get("mtp_layers", 0)),
    Row(("routed_scaling_factor",), WIDTH,
        lambda m: m.get("routed_scaling_factor")),
    Row(("norm_topk_prob",), WIDTH, lambda m: m.get("norm_topk_prob")),
    Row(("q_lora_rank",), WIDTH, lambda m: m.get("q_lora_rank") or 0),
    Row(("kv_lora_rank",), WIDTH, lambda m: m.get("kv_lora_rank")),
    Row(("qk_nope_head_dim",), WIDTH,
        lambda m: m.get("qk_nope_head_dim")),
    Row(("qk_rope_head_dim",), WIDTH,
        lambda m: m.get("qk_rope_head_dim")),
    Row(("v_head_dim",), WIDTH, lambda m: m.get("v_head_dim")),
    Row(("vocab_size",), COUNT, lambda m: m["vocab_size"],
        floor=lambda pub, m: (-(-pub // 8),
                              "an eighth of the vocabulary")),
    Row(("n_positions", "n_ctx", "max_position_embeddings"), COUNT,
        lambda m: m["max_seq_len"]),
    # an untied head is a second [V, h] matrix
    Row(("tie_word_embeddings",), WIDTH, lambda m: m["tie_embeddings"]),
)
KNOWN = {key for row in ROWS for key in row.keys}
PATTERN = "layer_types"  # as a source spells it and as the model group
PATTERNED_FLOOR = (
    4, "four layers after the leading dense ones in a model of more "
    "than one kind of layer")
# what a source means by null, where it means something
NULL_MEANS = {
    # GPT-2's config.json: four times the width
    "n_inner": lambda source: 4 * source["n_embd"],
    # a latent-attention config.json: the query is full rank
    "q_lora_rank": lambda source: 0,
}


def source_of(body: dict) -> dict:
    """What a file says was published: its ``published`` group, or its
    top-level keys but for the file's own."""
    if "published" in body:
        return body["published"]
    return {k: v for k, v in body.items() if k not in FILE_KEYS}


def _source_value(key: str, source: dict):
    """What the source says of ``key``; None where it says nothing."""
    value = source.get(key)
    if value is None and key in source and key in NULL_MEANS:
        return NULL_MEANS[key](source)
    return value


def check(entry: dict, body: dict) -> None:
    """Raises ``ValueError`` unless the file ``body`` of the
    ``BENCHMARK.json`` configuration ``entry`` keeps the rules above."""
    name = entry["name"]

    def refuse(key, why):
        raise ValueError(f"configuration {name!r}, key {key!r}: {why}")

    if body.get("source") != entry["source"]:
        refuse("source", f"the file says {body.get('source')!r} and "
               f"BENCHMARK.json {entry['source']!r}")
    cuts = {}
    for cut in body["reduced"]:
        if not isinstance(cut, dict) or set(cut) != REDUCED_KEYS:
            refuse("reduced", f"each entry has the keys "
                   f"{sorted(REDUCED_KEYS)}, not {cut!r}")
        cuts[cut["key"]] = cut
    for key in sorted(set(cuts) ^ set(entry["reduced"])):
        refuse(key, f"the file's `reduced` names {sorted(cuts)} and "
               f"BENCHMARK.json's {sorted(entry['reduced'])}")

    grouped = "published" in body
    source, model = source_of(body), body["model"]
    rows, known, patterned = ROWS, KNOWN, False
    if PATTERN in source or PATTERN in model:
        kinds, patterned = hold_pattern(body, source, model, cuts,
                                        grouped, refuse)
        rows = ROWS + tuple(
            row for kind in kinds for row in kind.ROWS)
        known = {key for row in rows for key in row.keys} | {PATTERN}
    for row in rows:
        for key in row.keys:
            value = _source_value(key, source)
            if value is None:
                continue
            held = row.held(model)
            cut = cuts.get(key)
            published = value if cut is None or grouped \
                else cut["published"]
            if row.as_published and row.as_published(model) != published:
                refuse(key, f"published {published!r} and never cut "
                       f"itself (the router keeps its width where the "
                       f"experts held are cut); the model group holds "
                       f"{row.as_published(model)!r}")
            if cut is None:
                if held != value:
                    refuse(key, f"published {value!r}, the model group "
                           f"holds {held!r}, and `reduced` does not "
                           f"name it")
                continue
            if row.kind == WIDTH:
                refuse(key, NEVER_CUT)
            if row.kind == COUNT and not _deployment(body):
                refuse(key, "a count (experts, heads, rows) is cut only "
                       "in a file that states its `deployment`: "
                       '{"chips": n, "divided": "how"}')
            stated = cut["published"] if grouped else cut["held"]
            if value != stated or cut["held"] != held:
                refuse(key, f"`reduced` says published "
                       f"{cut['published']!r} held {cut['held']!r}; the "
                       f"file's own key holds {value!r} and its model "
                       f"group {held!r}")
            if not 0 < cut["held"] < cut["published"]:
                refuse(key, f"held {cut['held']!r} is no cut of "
                       f"{cut['published']!r}")
            period = body.get("layer_period", 1)
            after = cut["held"] - model.get("dense_layers", 0)
            if row.kind == DEPTH and (after < period or after % period):
                refuse(key, f"depth {cut['held']} is not the "
                       f"{model.get('dense_layers', 0)} leading dense "
                       f"layer(s) and whole periods of the layer pattern "
                       f"(`layer_period` {period})")
            least, floor = row.floor(cut["published"], model) \
                if row.floor else (0, "")
            if row.kind == DEPTH and patterned and least < \
                    model.get("dense_layers", 0) + PATTERNED_FLOOR[0]:
                least, floor = (model.get("dense_layers", 0)
                                + PATTERNED_FLOOR[0], PATTERNED_FLOOR[1])
            if cut["held"] < least:
                refuse(key, f"held {cut['held']} is under the floor of "
                       f"a cut, {least}: {floor}")
    for key in sorted(set(cuts) - known):
        refuse(key, "`reduced` names a key that no row of "
               "benchmarks/published.py maps to the model group")
    for key in sorted(set(cuts) - set(source)):
        refuse(key, "`reduced` names a key the source does not have")
    not_held = body.get("not_held", {})
    for key in sorted(set(source) - known - set(not_held)):
        refuse(key, "no row of benchmarks/published.py maps this key "
               "of the source to the model group: list it in the "
               "file's `not_held` with why it says nothing of the "
               "shape, or a `benchmark` PR adds the row")
    for key in sorted(not_held):
        if key in known or key not in source or not not_held[key]:
            refuse(key, "`not_held` lists, each with its reason, keys "
                   "the source has and no row knows")


def hold_pattern(body, source, model, cuts, grouped, refuse):
    """Holds the ``model`` group's pattern of layers to the source's
    ``layer_types`` (the rule is at the top of this file) and returns
    ``(the modules of the kinds the run pattern names, whether the
    model has more than one kind of layer)``."""
    if PATTERN in cuts:
        refuse(PATTERN, "is never listed in `reduced`: the source's "
               "list stays whole and the depth's key says the cut")
    try:
        run = flops.layer_kinds(model)
        kinds = [flops.load_kind(name) for name in dict.fromkeys(run)]
        for kind in kinds:  # a group its kind cannot count is refused here
            kind.mixer_macs(model)
    except KeyError as e:
        refuse(PATTERN, f"the model group lacks {e}, which a kind of "
               f"layer it names reads")
    except ValueError as e:
        refuse(PATTERN, str(e))
    listed = source.get(PATTERN)
    if listed is None:
        if set(run) != {flops.DEFAULT_KIND}:
            refuse(PATTERN, f"the model group names the kinds "
                   f"{sorted(set(run))} and the source has no "
                   f"`{PATTERN}` to hold them to")
        return kinds, False
    spelt = {spelling: name for name in flops.kinds_there()
             for spelling in flops.load_kind(name).SOURCE_NAMES}
    for spelling in listed:
        if spelling not in spelt:
            refuse(PATTERN, f"the source names a layer {spelling!r}, "
                   f"which no kind in benchmarks/layer_kinds spells; "
                   f"they spell {sorted(spelt)}")
    published = tuple(spelt[spelling] for spelling in listed)
    depth = next((_published_value(key, source, cuts, grouped)
                  for row in ROWS if row.kind == DEPTH
                  for key in row.keys if key in source), None)
    if depth != len(published):
        refuse(PATTERN, f"the source's list names {len(published)} "
               f"layers and its depth is {depth!r}: in either form of "
               f"file it is the published list, whole")
    dense = model.get("dense_layers", 0)
    period = body.get("layer_period", 1)
    after = published[dense:]
    if not isinstance(period, int) or period < 1 or any(
            kind != after[i % period] for i, kind in enumerate(after)):
        refuse("layer_period", f"{period!r} is no period of the "
               f"published pattern after its {dense} leading dense "
               f"layer(s): {_runs(after)}")
    # that a cut depth is the dense layers and whole periods is the
    # depth row's rule, below in `check`
    if run != published[:len(run)]:
        refuse(PATTERN, f"the model group runs {_runs(run)}, which is "
               f"not the first {len(run)} layers of the published "
               f"{_runs(published)}")
    return kinds, len(set(published)) > 1


def _runs(pattern) -> str:
    """A pattern by its runs: ``5 x mamba2, attention, 4 x mamba2``."""
    runs = []
    for kind in pattern:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return ", ".join(f"{n} x {kind}" if n > 1 else kind
                     for kind, n in runs)


def _published_value(key, source, cuts, grouped):
    """What was published of a key that may be cut: in the top-level
    form the file's own key holds what is run."""
    if key in cuts and not grouped:
        return cuts[key]["published"]
    return _source_value(key, source)


def _deployment(body: dict) -> bool:
    d = body.get("deployment")
    return (isinstance(d, dict) and isinstance(d.get("chips"), int)
            and bool(d.get("divided")))
