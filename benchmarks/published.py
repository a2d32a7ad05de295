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
* depth may be cut freely, to whole periods of the layer pattern (the
  file's ``layer_period``, 1 where it states none);
* a count (experts, heads, rows of the vocabulary or of the position
  table) may be cut only in a file that states the ``deployment`` it is
  one chip's share of: ``{"chips": n, "divided": "how"}``;
* a width (hidden, head, MLP or expert width, experts per token) never;
* a key no row knows is refused too: a ``benchmark`` PR adds the row.
"""

from __future__ import annotations

from benchmarks import flops

DEPTH, COUNT, WIDTH = "depth", "count", "width"

# (the source's spellings, kind, the ``model`` group's value)
ROWS = (
    (("n_layer", "num_hidden_layers"), DEPTH,
     lambda m: m["num_layers"]),
    (("n_embd", "hidden_size"), WIDTH, lambda m: m["hidden_size"]),
    (("n_head", "num_attention_heads"), COUNT, lambda m: m["num_heads"]),
    (("num_key_value_heads",), COUNT, flops.kv_heads),
    (("head_dim",), WIDTH, flops.head_dim),
    # the width of one MLP, or of one expert where there are experts
    (("n_inner", "intermediate_size"), WIDTH,
     lambda m: m["hidden_size"] * m["mlp_ratio"]),
    (("num_experts", "n_routed_experts", "num_local_experts"), COUNT,
     lambda m: m.get("num_experts", 0)),
    (("num_experts_per_tok",), WIDTH,
     lambda m: m.get("experts_per_token", 0)),
    (("vocab_size",), COUNT, lambda m: m["vocab_size"]),
    (("n_positions", "max_position_embeddings"), COUNT,
     lambda m: m["max_seq_len"]),
)
KNOWN = {key for keys, _, _ in ROWS for key in keys}
REDUCED_KEYS = {"key", "published", "held", "why"}


def _source_value(key: str, source: dict):
    """What the source says of ``key``; None where it says nothing."""
    value = source.get(key)
    if value is None and key == "n_inner" and "n_inner" in source:
        # GPT-2's config.json: null means four times the width
        return 4 * source["n_embd"]
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
    source = body["published"] if grouped else body
    model = body["model"]
    for keys, kind, held_by in ROWS:
        for key in keys:
            value = _source_value(key, source)
            if value is None:
                continue
            held = held_by(model)
            cut = cuts.get(key)
            if cut is None:
                if held != value:
                    refuse(key, f"published {value!r}, the model group "
                           f"holds {held!r}, and `reduced` does not "
                           f"name it")
                continue
            if kind == WIDTH:
                refuse(key, "a width is never cut (hidden, head, MLP "
                       "or expert width, experts per token)")
            if kind == COUNT and not _deployment(body):
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
            if kind == DEPTH and cut["held"] % period:
                refuse(key, f"depth {cut['held']} is not whole periods "
                       f"of the layer pattern (`layer_period` {period})")
    for key in sorted(set(cuts) - KNOWN):
        refuse(key, "`reduced` names a key that no row of "
               "benchmarks/published.py maps to the model group")
    for key in sorted(set(cuts) - set(source)):
        refuse(key, "`reduced` names a key the source does not have")


def _deployment(body: dict) -> bool:
    d = body.get("deployment")
    return (isinstance(d, dict) and isinstance(d.get("chips"), int)
            and bool(d.get("divided")))
