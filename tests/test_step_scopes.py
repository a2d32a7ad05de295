"""The compiled train step carries the names its readers look for.

``horovod_tpu/utils/scopes.py`` names the loss head and the optimizer
wrap's parts with ``jax.named_scope``; Flax names the model's modules.
Both end up as ``op_name`` in the compiled step's HLO, which is where
``benchmarks/scopes.py`` reads them. Here the tiny steps of two cells
are lowered and compiled on the test world's CPU devices and their
``op_name``s searched: a name lost to a refactor, or a Flax that stops
writing module names, fails here and not on the chip.
"""

import os
import re
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from horovod_tpu.utils import scopes  # noqa: E402

# cell -> devices of its tiny step here (gpt2m_dp4: fused cross entropy,
# buckets and all-reduces; bertl_s128: dense head, one device)
CELLS = {"gpt2m_dp4": 4, "bertl_s128": 1}
HVD_BUCKETS = (scopes.HVD_PACK, scopes.HVD_ALLREDUCE, scopes.HVD_UNPACK)
# what the step function itself does outside every scope, by the
# primitive the op_name ends in: ``apply_updates`` (add), the loss's
# ``psum`` and its division by n, constants and their broadcasts (an
# empty name), and the reducers of reductions and scatters, which the
# CPU compiler names by primitive alone (with the loop around them for
# the interpreted flash kernels' loop over a program's instances)
OWN_LINES = {"", "add", "div", "psum", "broadcast", "reduce_sum",
             "reduce_max", "scatter-add", "while/body/reduce_sum",
             "while/body/reduce_max"}
# differentiated but under no scope: the job's own transpose of the
# tied embedding in front of the fused head
UNSCOPED_DIFFERENTIATED = {"jvp()/transpose", "transpose(jvp())/transpose"}


def tiny_step(cell: str, n: int, seq_len=None, **model_sizes):
    """The cell's tiny step and its described arguments for ``n`` CPU
    devices; ``model_sizes`` stand in place of the tiny preset's, and
    ``seq_len`` in place of the tiny traffic's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmarks import harness
    from benchmarks.jobs import dp_train

    found = harness.load_cell(cell)
    sizes = {**found["config"]["model"], **found["config"]["tiny"],
             **model_sizes}
    traffic = {**found["traffic"], **found["traffic"]["tiny"]}
    if seq_len:
        traffic["seq_len"] = seq_len
    run = harness.Run(
        started=time.perf_counter(), workload=cell, chips=n,
        traffic=traffic, model_sizes=sizes, seed=0, seconds=0,
        trace=False, rehearse=True)
    mesh = Mesh(np.array(jax.devices()[:n]), ("hvd",))
    hvd.shutdown()
    built = dp_train.build(run, sizes, traffic, mesh=mesh)
    everywhere, split = (NamedSharding(mesh, P()),
                         NamedSharding(mesh, P("hvd")))

    def described(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    params = jax.eval_shape(
        built["plain_model"].init, jax.random.PRNGKey(0),
        jnp.zeros((1, traffic["seq_len"]), jnp.int32))["params"]
    opt_state = jax.eval_shape(built["opt"].init, params)
    batch = dp_train.make_batch(
        sizes, traffic, n * traffic["batch_per_chip"], 0)
    return built["step"], (
        described(params, everywhere), described(opt_state, everywhere),
        *described(batch, split))


# The tiny preset's matrices are 64 to 256 KiB and its vectors at most
# 2 KiB: under this constant the tiny step splits as the real one does
# under the module's (matrices ride direct, vectors are packed).
TINY_DIRECT_MIN_BYTES = 64 << 10


def compile_tiny_step(cell: str, n: int) -> str:
    """The cell's tiny step compiled for ``n`` CPU devices, as text."""
    from horovod_tpu.ops import fusion

    step, args = tiny_step(cell, n)
    real, fusion.DIRECT_MIN_BYTES = (fusion.DIRECT_MIN_BYTES,
                                     TINY_DIRECT_MIN_BYTES)
    try:
        return step.lower(*args).compile().as_text()
    finally:
        fusion.DIRECT_MIN_BYTES = real


_COMPILED: dict = {}


def compiled_text(cell: str) -> str:
    """Compiled once a cell and process; the world is shut down around
    every test, so nothing of it is kept but the string."""
    if cell not in _COMPILED:
        _COMPILED[cell] = compile_tiny_step(cell, CELLS[cell])
    return _COMPILED[cell]


def compiled(cell: str) -> list:
    """``op_name``s of the cell's compiled tiny step."""
    return re.findall(r'op_name="([^"]*)"', compiled_text(cell))


@pytest.fixture
def op_names(request):
    return request.param, compiled(request.param)


def parts(op_name: str) -> set:
    return set(re.split(r"[/()]", op_name))


def having(names, *wanted):
    return [o for o in names if all(w in o for w in wanted)]


@pytest.mark.parametrize("op_names", CELLS, indirect=True)
def test_loss_head_is_named_in_both_directions(op_names):
    _, names = op_names
    head = [o for o in names if scopes.LOSS_HEAD in parts(o)]
    assert having(head, "jvp("), "no forward loss_head instruction"
    assert having(head, "transpose("), "no backward loss_head instruction"
    forward_only = [o for o in head if "transpose(" not in o]
    assert forward_only, "every loss_head instruction is backward"


def test_fused_head_loops_and_dense_head_matmul_are_inside_loss_head():
    fused, dense = compiled("gpt2m_dp4"), compiled("bertl_s128")
    # the fused cross entropy's two scans, and nothing of it outside
    for direction in ("jvp(loss_head)/while",
                      "transpose(jvp(loss_head))/while"):
        assert having(fused, direction), direction
    assert not having(fused, "jvp()/while")
    # the dense head's matmul with the tied embedding, and the loss
    # function's gather
    assert having(dense, "jvp(Transformer)", "loss_head/tok_emb.attend")
    assert having(dense, "transpose(jvp(Transformer))",
                  "loss_head/tok_emb.attend")
    assert having(dense, "jvp(loss_head)", "take_along_axis")


@pytest.mark.parametrize("op_names", CELLS, indirect=True)
def test_optimizer_wrap_is_named_where_it_runs(op_names):
    cell, names = op_names
    for scope in HVD_BUCKETS:
        found = [o for o in names if scope in parts(o)]
        if CELLS[cell] > 1:
            assert found, f"{scope} names nothing at n={CELLS[cell]}"
            assert not having(found, "jvp("), found[:3]
        else:  # the n=1 path returns the gradients untouched
            assert not found, found[:3]
    # AdamW runs at every n
    assert [o for o in names if scopes.HVD_INNER_UPDATE in parts(o)]
    if CELLS[cell] > 1:
        assert having(names, scopes.HVD_ALLREDUCE + "/psum")
        assert having(names, scopes.HVD_UNPACK + "/dynamic_slice")
        # the small leaves pass through pack and unpack; a direct leaf
        # is cut out of nothing, so no slice under hvd_unpack is as
        # large as one
        sliced = [
            int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(
                r"= \w+\[([\d,]*)\][^\n]*op_name=\"[^\"]*"
                + scopes.HVD_UNPACK + r"/dynamic_slice",
                compiled_text(cell))]
        assert sliced and max(sliced) * 4 < TINY_DIRECT_MIN_BYTES, sliced


@pytest.mark.parametrize("op_names", CELLS, indirect=True)
@pytest.mark.parametrize("module", [
    "block_0/attn", "block_1/attn", "block_0/ln_attn", "block_0/ln_mlp",
    "ln_final", "block_0/mlp/fc1", "block_0/mlp/fc2", "tok_emb"])
def test_flax_writes_module_names(op_names, module):
    _, names = op_names
    assert having(names, "jvp(Transformer)", f"/{module}/"), module
    assert having(names, "transpose(jvp(Transformer))", f"/{module}/"), \
        module


@pytest.mark.parametrize("op_names", CELLS, indirect=True)
def test_little_is_left_unscoped(op_names):
    cell, names = op_names
    own, differentiated = set(), set()
    for o in names:
        if re.match(r"^(p|s|batch)(\[|$)", o):
            continue  # an argument's name
        path = re.sub(r"^jit\(step_fn\)/?(shard_map/?)?", "", o)
        path = re.sub(r"\.\d+", "", path)
        if "jvp(" in path:
            if not {"Transformer", scopes.LOSS_HEAD} & parts(path):
                differentiated.add(path)
        elif not parts(path) & {*HVD_BUCKETS, scopes.HVD_INNER_UPDATE}:
            own.add(path)
    assert own <= OWN_LINES, own - OWN_LINES
    assert differentiated <= UNSCOPED_DIFFERENTIATED, differentiated


def equations(jaxpr, outer=""):
    """``(name stack, equation)`` of every equation in the jaxpr and
    the jaxprs inside it, a kernel's own body left out."""
    for eqn in jaxpr.eqns:
        stack = "/".join(
            p for p in (outer, str(eqn.source_info.name_stack)) if p)
        yield stack, eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from equations(inner, stack)


_TRACED: dict = {}


def traced(cell: str, n: int, seq_len=None, **model_sizes) -> list:
    """``(op_name, equation)`` of the cell's tiny step as it is traced,
    before any compiler fuses or drops an operation: the name stack
    with the primitive's name, which is what lowering writes as
    ``op_name``. Traced once a cell, sizes and process."""
    import jax

    key = (cell, seq_len, *sorted(model_sizes.items()))
    if key not in _TRACED:
        step, args = tiny_step(cell, n, seq_len, **model_sizes)
        _TRACED[key] = [
            (stack + "/" + eqn.primitive.name, eqn) for stack, eqn
            in equations(jax.make_jaxpr(step)(*args).jaxpr)]
    return _TRACED[key]


@pytest.mark.parametrize("cell", CELLS)
def test_two_flash_kernels_a_layer_where_their_readers_look(cell):
    """Each ``pallas_call`` of the step is one Mosaic call on the chip
    (interpreted here, so the step's equations are read and not the
    CPU's HLO). A layer has exactly two: the forward kernel with (out,
    lse) in the forward phase and ONE backward call of three results
    (dk, dv, dq) in the backward phase. Since PR 41 each carries its
    name from ``utils/scopes.py`` as ``name=``, which is the innermost
    part of its name stack and on the chip its instruction's stem
    (``flash_fwd.3``); a flash call stands right under the Flax module
    ``attn`` and under no ``LAYER_SCOPES`` name (the tiny steps' heads
    are 64 wide, so they hold no call of the q/k pass, which stands
    under ``ATTN_PREP``: below), so the benchmark's readers
    still class it as layer ``attn`` (``attn_kernel_ms`` by its layer,
    ``benchmarks/scopes.kernel_kind`` by phase and arity: the backward
    call is a tuple, so ``attn_bwd_dkv_kernel_ms`` reads it and no call
    is left for ``attn_bwd_dq``), and ``benchmarks/kernel_names.py``
    reads it by its name."""
    from benchmarks import scopes as readers

    kinds: dict = {}
    for op_name, eqn in traced(cell, CELLS[cell]):
        if eqn.primitive.name != "pallas_call":
            continue
        block, stem, name, _ = op_name.split("/")[-4:]
        assert stem == "attn", op_name
        assert eqn.params["name"] == name, op_name
        assert not parts(op_name) & set(scopes.LAYER_SCOPES), op_name
        phase, layer = readers.classify(op_name)
        assert layer == "attn", op_name
        results = len(eqn.outvars)
        kind = readers.kernel_kind(
            "call", phase, layer, {"call"}, {"call"} if results > 1 else ())
        kinds.setdefault(block, []).append((name, kind, phase, results))
    assert kinds == {
        f"block_{i}": [
            (scopes.FLASH_FWD, readers.KERNEL_FWD, "forward", 2),
            (scopes.FLASH_BWD, readers.KERNEL_DKV, "backward", 3)]
        for i in range(2)}, kinds


# -- attention's own scopes --------------------------------------------------

PROJECTIONS = {"query", "key", "value", "out"}
ROUTED_CELL = "sdar_bd_s4096"
EVERY_CELL = {**CELLS, ROUTED_CELL: 1}


def under_attn(cell: str) -> list:
    """``(op_name, equation)`` of what the cell's tiny step traces
    under a Flax module ``attn``."""
    return [(o, e) for o, e in traced(cell, EVERY_CELL[cell])
            if "attn" in parts(o)]


@pytest.mark.parametrize("cell", EVERY_CELL)
@pytest.mark.parametrize("scope", [scopes.ATTN_PROJ, scopes.ATTN_PREP])
def test_attention_scopes_are_named_in_both_directions(cell, scope):
    from benchmarks import scopes as readers

    found = [o for o, _ in under_attn(cell) if scope in parts(o)]
    for phase, marker in (("forward", "jvp(Transformer)"),
                          ("backward", "transpose(jvp(Transformer))")):
        mine = [o for o in found if readers.classify(o)[0] == phase]
        assert mine, (scope, phase)
        assert all(marker in o for o in mine)
        assert {readers.classify(o) for o in mine} == {(phase, scope)}
        layers = 6 if cell == ROUTED_CELL else 2
        assert {p for o in mine for p in parts(o)
                if p.startswith("block_")} == {
            f"block_{i}" for i in range(layers)}


@pytest.mark.parametrize("cell", EVERY_CELL)
def test_kernel_names_are_in_their_directions(cell):
    from benchmarks import scopes as readers

    phases = {name: {readers.classify(o)[0] for o, _ in under_attn(cell)
                     if name in parts(o)}
              for name in (scopes.FLASH_FWD, scopes.FLASH_BWD)}
    again = {"backward"} if cell == ROUTED_CELL else set()  # `remat`
    assert phases == {scopes.FLASH_FWD: {"forward"} | again,
                      scopes.FLASH_BWD: {"backward"}}


FLASH_NAMES = {scopes.FLASH_FWD, scopes.FLASH_BWD}
QK_PREP_NAMES = {scopes.QK_PREP_FWD, scopes.QK_PREP_BWD}
GROUPED_MATMUL_NAMES = {scopes.GROUPED_MATMUL_FWD, scopes.GROUPED_MATMUL_DW}
# the routed cell's tiny step with heads as wide as the real one's: the
# width at which `Attention` runs q/k norms, rope and the kernels' layout
# as the one pass of `ops/attention_prep.py` (the tiny preset's 64 keeps
# the array passes)
WIDE = {"head_dim": 128}


@pytest.mark.parametrize("cell", EVERY_CELL)
def test_a_flash_call_stands_outside_every_layer_scope(cell):
    """The rule ``utils/scopes.py`` states: an ``op_name`` that holds a
    flash kernel's name holds no ``LAYER_SCOPES`` name and classes as
    layer ``attn``; and at the tiny preset's head width every
    ``pallas_call`` under a module ``attn`` is a flash call (the routed
    cell's expert products are calls too, under ``mlp``)."""
    from benchmarks import scopes as readers

    kernels = [o for o, _ in traced(cell, EVERY_CELL[cell])
               if parts(o) & {"pallas_call", *FLASH_NAMES}
               and not parts(o) & GROUPED_MATMUL_NAMES]
    assert kernels
    for o in kernels:
        assert parts(o) & FLASH_NAMES, o
        assert not parts(o) & set(scopes.LAYER_SCOPES), o
        assert readers.classify(o)[1] == "attn", o


def test_the_qk_pass_stands_inside_attn_prep():
    """The other half of the rule: the q/k pass's two calls sit under
    ``ATTN_PREP``, so the benchmark books them in layer ``attn_prep``
    (``attn_prep_ms`` holds the pass's own time) and no reader of the
    flash kernels takes one for a flash kernel, by layer
    (``kernel_kind``) or by name (``kernel_names``); the flash calls
    beside them stay where they were. One forward call a layer in the
    forward phase, one again in the backward phase for EVERY block
    (``remat``'s five second runs, and the last block keeps the flash
    calls' results but rebuilds the pass's), one backward call a
    layer."""
    from benchmarks import kernel_names, scopes as readers

    assert not QK_PREP_NAMES & set(scopes.LAYER_SCOPES)
    assert not QK_PREP_NAMES & set(kernel_names.kernel_names())
    calls: dict = {}
    for o, eqn in traced(ROUTED_CELL, 1, **WIDE):
        if eqn.primitive.name != "pallas_call" \
                or eqn.params["name"] in GROUPED_MATMUL_NAMES:
            continue
        name = eqn.params["name"]
        phase, layer = readers.classify(o)
        assert name in parts(o) and "attn" in parts(o), o
        results = len(eqn.outvars)
        kind = readers.kernel_kind(
            "call", phase, layer, {"call"}, {"call"} if results > 1 else ())
        if name in QK_PREP_NAMES:
            assert scopes.ATTN_PREP in parts(o), o
            assert layer == scopes.ATTN_PREP and kind is None, o
        else:
            assert name in FLASH_NAMES, o
            assert not parts(o) & set(scopes.LAYER_SCOPES), o
            assert layer == "attn" and kind is not None, o
        calls[(name, phase)] = calls.get((name, phase), 0) + 1
    layers = 6
    assert calls == {
        (scopes.QK_PREP_FWD, "forward"): layers,
        (scopes.QK_PREP_FWD, "backward"): layers,
        (scopes.QK_PREP_BWD, "backward"): layers,
        (scopes.FLASH_FWD, "forward"): layers,
        (scopes.FLASH_FWD, "backward"): layers - 1,
        (scopes.FLASH_BWD, "backward"): layers}, calls


def test_with_the_qk_pass_no_norm_or_rope_is_left_to_array_passes():
    """At the real head width the routed cell's layer ``attn_prep``
    holds the pass's calls, the gather of rope's rows, v's transpose and
    the flash function's work around its calls; the norms' ``rsqrt`` and
    rope's ``split`` are inside the pass. What is left in layer ``attn``
    is the flash calls alone, and the parameters are where they were."""
    from benchmarks import scopes as readers

    wide = [(o, e) for o, e in traced(ROUTED_CELL, 1, **WIDE)
            if "attn" in parts(o)]
    prep = {e.primitive.name for o, e in wide
            if readers.classify(o)[1] == scopes.ATTN_PREP}
    assert {"pallas_call", "transpose", "gather"} <= prep, prep
    assert not prep & {"rsqrt", "split"}, prep
    left = {e.primitive.name for o, e in wide
            if readers.classify(o)[1] == "attn"}
    assert {"pallas_call"} <= left <= {"pallas_call", "reduce_precision"}, \
        left
    products = [o for o, e in wide if e.primitive.name == "dot_general"]
    assert products and all(
        readers.classify(o)[1] == scopes.ATTN_PROJ for o in products)


@pytest.mark.parametrize("cell", EVERY_CELL)
def test_attention_is_projections_layout_work_and_kernels(cell):
    """Under ``attn`` every operation of ``query``, ``key``, ``value``
    and ``out`` is layer ``attn_proj`` and nothing else is; the q/k
    norms, rope and the flash function's work around its calls are
    ``attn_prep``; and what is left in layer ``attn`` is the kernel
    calls alone."""
    from benchmarks import scopes as readers

    left = set()
    for o, eqn in under_attn(cell):
        layer = readers.classify(o)[1]
        if parts(o) & PROJECTIONS:
            assert layer == scopes.ATTN_PROJ, o
        else:
            assert layer != scopes.ATTN_PROJ, o
        if parts(o) & {"q_norm", "k_norm"}:
            assert layer == scopes.ATTN_PREP, o
        if layer == "attn":
            left.add(eqn.primitive.name)
    # (`jax.checkpoint` marks a result its policy keeps with a
    # `reduce_precision` to the result's own precision, named as the
    # call that made it: the last block's flash output under `remat`)
    assert {"pallas_call"} <= left <= {"pallas_call", "reduce_precision"}, \
        left
    prep = {e.primitive.name for o, e in under_attn(cell)
            if readers.classify(o)[1] == scopes.ATTN_PREP}
    # into and out of the kernels' layout in every cell; rope's halves
    # and the norms' statistics where the model has them
    assert "transpose" in prep, prep
    if cell == ROUTED_CELL:
        assert {"split", "concatenate", "rsqrt"} <= prep, prep
        products = [o for o, e in under_attn(cell)
                    if e.primitive.name == "dot_general"]
        assert products and all(
            readers.classify(o)[1] == scopes.ATTN_PROJ for o in products)


# -- the routed MLP's own scopes ---------------------------------------------

def test_layer_scopes_are_the_routed_mlps_two_attentions_two_the_mixers_four():
    """And, since PR 48, the shared expert's and the post-norms', last:
    an entry put first or in the middle would read as a change to what
    was there."""
    assert scopes.LAYER_SCOPES == (
        "moe_dispatch", "moe_experts", "attn_proj", "attn_prep",
        "mamba_proj", "mamba_conv", "mamba_scan", "mamba_gate",
        "moe_shared", "post_norm") == (
        scopes.MOE_DISPATCH, scopes.MOE_EXPERTS, scopes.ATTN_PROJ,
        scopes.ATTN_PREP, scopes.MAMBA_PROJ, scopes.MAMBA_CONV,
        scopes.MAMBA_SCAN, scopes.MAMBA_GATE, scopes.MOE_SHARED,
        scopes.POST_NORM)
    # the kernels' names are no layer scopes
    assert (scopes.FLASH_FWD, scopes.FLASH_BWD) == ("flash_fwd", "flash_bwd")
    assert not {scopes.FLASH_FWD, scopes.FLASH_BWD} & set(
        scopes.LAYER_SCOPES)


def test_remat_runs_the_flash_forward_again_in_every_block_but_the_last():
    """The routed cell is the one built with ``remat``. Its tiny step
    holds one forward flash call (out, lse) a layer in the forward
    phase and one backward call (dk, dv, dq) a layer; the forward call
    a rematerialised block runs again carries ``FLASH_FWD`` in the
    backward phase, where ``attn_fwd_recompute_kernel_ms`` reads it by
    that name (and ``attn_bwd_dkv_kernel_ms``, by arity, beside the
    backward kernel), and there is one for every block but the last,
    whose flash results are kept from its first run
    (``TransformerConfig.remat``)."""
    from benchmarks import scopes as readers

    calls: dict = {}
    for op_name, eqn in traced(ROUTED_CELL, 1):
        if eqn.primitive.name != "pallas_call" \
                or eqn.params["name"] in GROUPED_MATMUL_NAMES:
            continue
        block = next(p for p in op_name.split("/") if p.startswith("block_"))
        phase, _ = readers.classify(op_name)
        assert eqn.params["name"] in parts(op_name)
        calls.setdefault(block, []).append(
            (phase, eqn.params["name"], len(eqn.outvars)))
    layers = 6
    first = ("forward", scopes.FLASH_FWD, 2)
    second = ("backward", scopes.FLASH_FWD, 2)
    back = ("backward", scopes.FLASH_BWD, 3)
    again = {f"block_{i}": [first, second, back] for i in range(layers - 1)}
    kept = {f"block_{layers - 1}": [first, back]}
    assert {b: sorted(c, key=lambda x: (x[0] == "backward", x[2]))
            for b, c in calls.items()} == {**again, **kept}, calls


@pytest.mark.parametrize("scope", [scopes.MOE_DISPATCH, scopes.MOE_EXPERTS])
def test_routed_mlp_is_named_in_both_directions(scope):
    """The compiled tiny step of the routed cell names both scopes under
    every layer's ``mlp``, forward and backward, and the benchmark's
    reduction classes such a name as the scope's layer, not as ``mlp``;
    the expert products (the interpreted kernels of
    ``ops/grouped_matmul.py`` at this shape) are inside ``moe_experts`` and
    the router, the choice, the gather and the combine inside
    ``moe_dispatch``."""
    from benchmarks import scopes as readers

    if ROUTED_CELL not in _COMPILED:
        _COMPILED[ROUTED_CELL] = compile_tiny_step(ROUTED_CELL, 1)
    names = compiled(ROUTED_CELL)
    for phase, marker in (("forward", "jvp(Transformer)"),
                          ("backward", "transpose(jvp(Transformer))")):
        for layer in range(6):
            found = [o for o in having(names, f"/block_{layer}/mlp/")
                     if scope in parts(o) and readers.classify(o)[0] == phase]
            assert found, (scope, phase, layer)
            assert all(marker in o for o in found)
            assert {readers.classify(o) for o in found} == {(phase, scope)}
    inside = {o.rsplit("/", 1)[-1].split(".")[0]
              for o in names if scope in parts(o)}
    if scope == scopes.MOE_EXPERTS:
        # nothing of the dispatch is under the products' name
        assert not inside & {"sort", "top_k", "gather", "scatter-add"}, \
            inside
    else:
        assert {"sort", "gather"} <= inside or {"sort", "top_k"} <= inside, \
            inside
    # nothing of the routed MLP is left under the bare module name but
    # the plumbing of the loop over the further products (its counter,
    # its conditions, the cotangents' fan-in) and a reshape
    bare = {o.rsplit("/", 1)[-1].split(".")[0] for o in names
            if "/mlp/" in o and not parts(o) & set(scopes.LAYER_SCOPES)}
    assert bare <= {"while", "cond", "closed_call", "lt", "gt", "mul", "sub",
                    "iota", "dynamic_slice", "convert_element_type",
                    "reshape", "remat2", "add_any", "add",
                    "dynamic_update_slice", "mlp"}, bare


def test_the_expert_products_kernels_stand_inside_moe_experts():
    """The rule ``utils/scopes.py`` states for ``GROUPED_MATMUL_*``. The
    routed cell's tiny preset is a shape the kernels take (512 rows of
    128 through experts of 768: ``ops/grouped_matmul.supports``), so
    every layer's expert products are kernel calls: each carries its
    name, lies under the Flax module ``mlp`` inside ``moe_experts``
    (once, and before the call's own name) and is layer ``moe_experts``
    to the benchmark's readers in both phases
    (``moe_experts_ms`` selects by that layer). A layer's forward phase
    holds the three products and the further products' three; its
    backward phase the second run's gate and up (the down product's
    result is no residual), the three gradients into the rows, which
    are products too, and the three into the weights, each for the main
    path and for the further products. The last block is no exception:
    ``_last_block_keeps`` keeps none of them. No ``ragged_dot`` is
    left."""
    from benchmarks import scopes as readers

    assert not GROUPED_MATMUL_NAMES & set(scopes.LAYER_SCOPES)
    calls: dict = {}
    for o, eqn in traced(ROUTED_CELL, 1):
        assert "ragged_dot" not in eqn.primitive.name, o
        if eqn.primitive.name != "pallas_call" \
                or eqn.params["name"] not in GROUPED_MATMUL_NAMES:
            continue
        assert "mlp" in parts(o) and eqn.params["name"] in parts(o), o
        phase, layer = readers.classify(o)
        assert layer == scopes.MOE_EXPERTS, o
        assert o.count(scopes.MOE_EXPERTS + "/") == 1 and o.index(
            scopes.MOE_EXPERTS) < o.index(eqn.params["name"]), o
        block = next(p for p in parts(o) if p.startswith("block_"))
        calls.setdefault(block, []).append((phase, eqn.params["name"]))
    a_layer = [("backward", scopes.GROUPED_MATMUL_FWD)] * 10 \
        + [("backward", scopes.GROUPED_MATMUL_DW)] * 6 \
        + [("forward", scopes.GROUPED_MATMUL_FWD)] * 6
    assert {b: sorted(c) for b, c in calls.items()} == {
        f"block_{i}": a_layer for i in range(6)}, calls


# -- the state-space mixer's four scopes --------------------------------------

HYBRID_CELL = "granite_h_lm"
MAMBA_SCOPES = {scopes.MAMBA_PROJ, scopes.MAMBA_CONV, scopes.MAMBA_SCAN,
                scopes.MAMBA_GATE}
# the tiny preset's pattern: three state-space layers around one
# attention layer
MAMBA_BLOCKS, ATTN_BLOCKS = {"block_0", "block_1", "block_3"}, {"block_2"}


def test_every_operation_of_the_state_space_mixer_is_in_one_scope():
    """The rule ``utils/scopes.py`` states for the Flax module
    ``mamba``: each of its operations, in both directions and in a
    rematerialised block's second run, lies in exactly one of
    ``mamba_proj``, ``mamba_conv``, ``mamba_scan`` and ``mamba_gate``,
    which is then its layer for the benchmark's readers; none is a
    Pallas call, and none is classed ``attn`` (a Mosaic call of that
    layer is a flash kernel to the kernels' readers)."""
    from benchmarks import scopes as readers

    assert MAMBA_SCOPES <= set(scopes.LAYER_SCOPES)
    under = [(o, e) for o, e in traced(HYBRID_CELL, 1)
             if "mamba" in parts(o)]
    assert under
    seen: dict = {}
    for o, eqn in under:
        mine = parts(o) & MAMBA_SCOPES
        assert len(mine) == 1, o
        assert not parts(o) & {"attn", *FLASH_NAMES, *QK_PREP_NAMES}, o
        assert eqn.primitive.name != "pallas_call", o
        phase, layer = readers.classify(o)
        assert {layer} == mine, o
        block = next(p for p in parts(o) if p.startswith("block_"))
        seen.setdefault((layer, phase), set()).add(block)
    assert seen == {(scope, phase): MAMBA_BLOCKS
                    for scope in MAMBA_SCOPES
                    for phase in ("forward", "backward")}, seen
    # and outside the module nothing carries one of the four names
    assert not [o for o, _ in traced(HYBRID_CELL, 1)
                if parts(o) & MAMBA_SCOPES and "mamba" not in parts(o)]


def test_the_state_space_scopes_hold_what_they_say():
    """The projections' scope holds the two products and nothing of the
    scan; the scan's holds the cumulative sums, the exponentials and
    the loop between chunks; the convolution's holds dt's softplus."""
    by_scope: dict = {}
    for o, eqn in traced(HYBRID_CELL, 1):
        for scope in parts(o) & MAMBA_SCOPES:
            by_scope.setdefault(scope, set()).add(eqn.primitive.name)
    assert "dot_general" in by_scope[scopes.MAMBA_PROJ]
    assert not by_scope[scopes.MAMBA_PROJ] & {"cumsum", "exp", "scan",
                                               "logistic"}
    assert {"cumsum", "exp", "scan", "dot_general"} <= \
        by_scope[scopes.MAMBA_SCAN]
    assert {"logistic", "pad"} <= by_scope[scopes.MAMBA_CONV]
    assert "dot_general" not in by_scope[scopes.MAMBA_CONV]
    assert {"rsqrt", "logistic"} <= by_scope[scopes.MAMBA_GATE]
    assert "dot_general" not in by_scope[scopes.MAMBA_GATE]


# the tiny preset at the least shape the scan's kernels take
# (`ops/ssd_scan.supports`): one chunk of 128 positions, eight heads of
# 32, a state of 128
KERNEL_SHAPE = dict(seq_len=128, mamba_n_heads=8, mamba_d_head=32,
                    mamba_d_state=128, mamba_chunk_size=128)
SSD_SCAN_NAMES = {scopes.SSD_SCAN_FWD, scopes.SSD_SCAN_BWD}


def test_the_scans_kernels_stand_inside_the_scans_scope():
    """The rule ``utils/scopes.py`` states for ``SSD_SCAN_*``: at a
    shape the kernels take, every state-space layer has its forward
    call in the forward phase, the backward call in the backward phase
    and, in a rematerialised block, the forward call again there; each
    carries its name, lies under the Flax module ``mamba`` inside
    ``mamba_scan``, and is layer ``mamba_scan`` to the benchmark's
    readers (``mamba_scan_ms`` selects by that layer and nothing else).
    The last block keeps its forward call's results
    (``_last_block_keeps``). Every other operation of the module still
    lies in exactly one of the four scopes."""
    from benchmarks import scopes as readers

    assert not SSD_SCAN_NAMES & set(scopes.LAYER_SCOPES)
    calls: dict = {}
    for o, eqn in traced(HYBRID_CELL, 1, **KERNEL_SHAPE):
        if "mamba" not in parts(o):
            continue
        assert len(parts(o) & MAMBA_SCOPES) == 1, o
        phase, layer = readers.classify(o)
        assert {layer} == parts(o) & MAMBA_SCOPES, o
        if eqn.primitive.name != "pallas_call":
            continue
        assert layer == scopes.MAMBA_SCAN, o
        assert eqn.params["name"] in parts(o) & SSD_SCAN_NAMES, o
        # the scan's scope stands before the kernel's name and once
        assert o.count(scopes.MAMBA_SCAN) == 1 and o.index(
            scopes.MAMBA_SCAN) < o.index(eqn.params["name"]), o
        block = next(p for p in parts(o) if p.startswith("block_"))
        calls.setdefault(block, []).append((phase, eqn.params["name"]))
    again = [("backward", scopes.SSD_SCAN_BWD),
             ("backward", scopes.SSD_SCAN_FWD),
             ("forward", scopes.SSD_SCAN_FWD)]
    assert {b: sorted(c) for b, c in calls.items()} == {
        "block_0": again, "block_1": again,
        "block_3": [again[0], again[2]]}, calls


def test_the_tiny_hybrid_preset_has_no_kernel_under_mamba():
    """Its shape (heads of 64 over a state of 16, chunks of 32) is not
    one the kernels take: the plain form, as before."""
    assert not [o for o, e in traced(HYBRID_CELL, 1)
                if "mamba" in parts(o) and e.primitive.name == "pallas_call"]


def test_the_hybrids_attention_layer_keeps_attentions_names():
    """One attention layer among the state-space ones: its projections
    and its two flash calls stand where every cell's do, in its block
    alone."""
    from benchmarks import scopes as readers

    names = [o for o, _ in traced(HYBRID_CELL, 1) if "attn" in parts(o)]
    assert {p for o in names for p in parts(o)
            if p.startswith("block_")} == ATTN_BLOCKS
    assert {readers.classify(o)[1] for o in names} == {
        "attn", scopes.ATTN_PROJ, scopes.ATTN_PREP}
    calls = [(e.params["name"], readers.classify(o)[0])
             for o, e in traced(HYBRID_CELL, 1)
             if e.primitive.name == "pallas_call"]
    # block_2 is rematerialised: its forward kernel runs again
    assert sorted(calls) == [
        (scopes.FLASH_BWD, "backward"), (scopes.FLASH_FWD, "backward"),
        (scopes.FLASH_FWD, "forward")]


# -- the window-and-full, gated, sigmoid-routed share (PR 48) -----------------

GATED_CELL = "trinity_mini_s8192"
# its pattern: two leading dense layers, window layers but for block_3
WINDOW_BLOCKS = {"block_0", "block_1", "block_2", "block_4", "block_5"}
ROUTED_BLOCKS = {"block_2", "block_3", "block_4", "block_5"}


def block_of(op_name: str) -> str:
    return next(p for p in parts(op_name) if p.startswith("block_"))


def test_the_gates_product_is_a_projection_and_its_sigmoid_layout_work():
    """``attn_proj_roofline`` counts five products a layer since PR 47:
    the gate's `dot_general` stands inside ``ATTN_PROJ`` in every layer,
    both directions, and the sigmoid and the multiply with the heads'
    output inside ``ATTN_PREP``; nothing of the gate is left under the
    bare ``attn``."""
    from benchmarks import scopes as readers

    names = [o for o, _ in traced(GATED_CELL, 1) if "attn" in parts(o)]
    gate = [o for o in names if "gate" in parts(o)]
    assert {block_of(o) for o in gate} == {f"block_{i}" for i in range(6)}
    assert {readers.classify(o)[1] for o in gate} == {scopes.ATTN_PROJ}
    for phase in ("forward", "backward"):
        assert [o for o in gate if o.endswith("dot_general")
                and readers.classify(o)[0] == phase]
    logistic = [o for o in names if o.endswith("/logistic")]
    assert len({block_of(o) for o in logistic}) == 6
    assert {readers.classify(o)[1] for o in logistic} == {scopes.ATTN_PREP}
    # layer `attn` holds the kernels' calls and nothing else
    bare = [(o, e) for o, e in traced(GATED_CELL, 1)
            if "attn" in parts(o) and readers.classify(o)[1] == "attn"]
    assert bare and all(e.primitive.name == "pallas_call" or
                        o.split("/")[-2] in (scopes.FLASH_FWD,
                                             scopes.FLASH_BWD)
                        for o, e in bare), [o for o, _ in bare][:5]


def test_the_windowed_flash_calls_keep_their_names_and_their_layer():
    """A window layer's calls are ``flash_fwd`` / ``flash_bwd`` as a
    full layer's are, outside every ``LAYER_SCOPES`` name, so the six
    attention readers read them as they are; the window reaches the
    kernels as the call's static argument, in the window layers alone;
    under ``remat`` every block but the last runs its forward again."""
    from benchmarks import scopes as readers

    calls: dict = {}
    for o, eqn in traced(GATED_CELL, 1):
        if eqn.primitive.name != "pallas_call":
            continue
        phase, layer = readers.classify(o)
        assert layer == "attn" and not parts(o) & set(scopes.LAYER_SCOPES)
        assert eqn.params["name"] in parts(o)
        calls.setdefault(block_of(o), []).append(
            (phase, eqn.params["name"]))
    again = [("backward", scopes.FLASH_BWD), ("backward", scopes.FLASH_FWD),
             ("forward", scopes.FLASH_FWD)]
    assert {b: sorted(c) for b, c in calls.items()} == {
        **{f"block_{i}": again for i in range(5)},
        "block_5": [again[0], again[2]]}, calls


def test_the_window_layers_are_what_the_windows_reader_takes():
    """``attn_window_kernel_ms`` takes the Mosaic calls of layer
    ``attn`` under a ``block_<i>`` the model group calls
    ``window_attention``: on the traced names, the five window blocks'
    calls and not block_3's."""
    from benchmarks import harness
    from benchmarks.layer_metrics import attn_window_kernel_ms as reader

    found = harness.load_cell(GATED_CELL)
    sizes = {**found["config"]["model"], **found["config"]["tiny"]}
    layers = reader.window_layers(sizes)
    assert {f"block_{i}" for i in layers} == WINDOW_BLOCKS
    lines = ["HloModule jit_step_fn", "",
             "ENTRY %main (p: bf16[8,128]) -> bf16[8,128] {",
             "  %p = bf16[8,128]{1,0} parameter(0)"]
    kernels = [o for o, e in traced(GATED_CELL, 1)
               if e.primitive.name == "pallas_call"]
    for i, o in enumerate(kernels):
        lines.append(
            f'  %call.{i} = bf16[8,128]{{1,0}} custom-call(%p), '
            f'custom_call_target="tpu_custom_call", '
            f'metadata={{op_name="{o}"}}')
    lines += ["  ROOT %r = bf16[8,128]{1,0} copy(%p)", "}"]
    taken = reader.window_calls("\n".join(lines), layers)
    assert len(kernels) == 17 and len(taken) == 14
    assert {block_of(kernels[int(c.split(".")[1])])
            for c in taken} == WINDOW_BLOCKS
    assert reader.window_calls("\n".join(lines), []) == {}


@pytest.mark.parametrize("scope,modules", [
    (scopes.MOE_SHARED, {"shared_gate", "shared_up", "shared_down"}),
    (scopes.POST_NORM, {"ln_post_attn", "ln_post_mlp"}),
])
def test_the_shared_expert_and_the_post_norms_are_layers_of_their_own(
        scope, modules):
    """Both directions, in every block that has them; the benchmark's
    reduction classes the names as the scope's layer, not as ``mlp`` or
    ``other``; ``ln_attn`` and ``ln_mlp`` stay layer ``norm``."""
    from benchmarks import scopes as readers

    names = [o for o, _ in traced(GATED_CELL, 1) if scope in parts(o)]
    blocks = ROUTED_BLOCKS if scope == scopes.MOE_SHARED else {
        f"block_{i}" for i in range(6)}
    for phase in ("forward", "backward"):
        mine = [o for o in names if readers.classify(o)[0] == phase]
        assert {block_of(o) for o in mine} == blocks, (scope, phase)
        assert {readers.classify(o)[1] for o in mine} == {scope}
    assert {p for o in names for p in parts(o)} >= modules
    # every operation of those modules is inside the scope
    everything = [o for o, _ in traced(GATED_CELL, 1)
                  if parts(o) & modules]
    assert everything and all(scope in parts(o) for o in everything)
    pre = [o for o, _ in traced(GATED_CELL, 1)
           if parts(o) & {"ln_attn", "ln_mlp"}]
    assert {readers.classify(o)[1] for o in pre} == {"norm"}


def test_the_sigmoid_routers_arithmetic_is_dispatch():
    """The sigmoid, the correction, the choice, the renormalisation and
    the scale stand inside ``MOE_DISPATCH``; the shared expert's
    products are no part of ``MOE_EXPERTS``; the dense layers' ``mlp``
    is the bare module."""
    from benchmarks import scopes as readers

    mlp = [(o, e) for o, e in traced(GATED_CELL, 1) if "mlp" in parts(o)]
    dispatch = {o.rsplit("/", 1)[-1] for o, _ in mlp
                if scopes.MOE_DISPATCH in parts(o)}
    assert {"logistic", "top_k", "sort", "gather"} <= dispatch, dispatch
    experts = {o.rsplit("/", 1)[-1] for o, _ in mlp
               if scopes.MOE_EXPERTS in parts(o)}
    assert "ragged_dot_general" in experts or "ragged_dot" in experts
    assert not [o for o, _ in mlp if scopes.MOE_EXPERTS in parts(o)
                and scopes.MOE_SHARED in parts(o)]
    dense = [o for o, _ in mlp if block_of(o) in ("block_0", "block_1")]
    assert dense and {readers.classify(o)[1] for o in dense} == {"mlp"}
    assert not [o for o, _ in mlp if block_of(o) in ROUTED_BLOCKS
                and o.endswith("dot_general")
                and readers.classify(o)[1] == "mlp"]
