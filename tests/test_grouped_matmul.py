"""The routed experts' products as kernels (`ops/grouped_matmul.py`,
interpreted on the CPU) against `lax.ragged_dot` behind its cast, which
they stand for: value and all three gradients over divisions of the rows
that cut tiles every way, the table of shapes taken and refused, and
`models/moe.RoutedMlp` with the kernels against the same layer forced
plain."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import moe
from horovod_tpu.ops import grouped_matmul as gm

M, A, C, G = 1024, 128, 256, 4
# divisions of 1,024 rows (four pieces of 256: one row tile of 1,024,
# or two of 512 where `ROWS` says so) over four groups
DIVISIONS = {
    "even": [256, 256, 256, 256],
    "uneven": [100, 300, 424, 200],
    "an_empty_group": [300, 0, 424, 300],
    "a_group_smaller_than_a_row_tile": [500, 24, 300, 200],
    "boundaries_that_cut_tiles": [511, 2, 255, 256],
    "all_rows_in_the_last_group": [0, 0, 0, 1024],
    "all_rows_in_the_first_group": [1024, 0, 0, 0],
    "one_group_a_tile": [512, 512, 0, 0],
    "rows_past_the_last_groups_end": [100, 50, 20, 30],
}


def operands(dtype, m=M, a=A, c=C, g=G):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(keys[0], (m, a), jnp.float32).astype(dtype)
    weights = jax.random.normal(keys[1], (g, a, c), jnp.float32) * a ** -0.5
    d_out = jax.random.normal(keys[2], (m, c), jnp.float32).astype(dtype)
    return rows, weights, d_out


def plain(rows, weights, groups):
    return lax.ragged_dot(rows, weights.astype(rows.dtype), groups,
                          preferred_element_type=rows.dtype)


def value_and_gradients(product, rows, weights, d_out, groups):
    out, vjp = jax.vjp(lambda r, w: product(r, w, groups), rows, weights)
    return (out, *vjp(d_out))


def distance(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.fixture(params=[(1024, 512), (512,)],
                ids=["tiles_of_1024", "tiles_of_512"])
def row_tiles(request, monkeypatch):
    """The module's own choice at 1,024 rows (one tile) and, with the
    larger tile taken away, two tiles of 512."""
    monkeypatch.setattr(gm, "_ROWS", request.param)
    jax.clear_caches()
    yield request.param[0]
    jax.clear_caches()


@pytest.mark.parametrize("division", DIVISIONS)
def test_value_and_gradients_are_ragged_dots(division, row_tiles):
    """bf16 rows through float32 experts: the result, the gradient into
    the rows and the gradient into the experts, to bf16's rounding of
    sums made in another order; nothing is left unwritten (the
    interpreter fills a fresh output with NaN)."""
    assert gm._row_tile(M, A, C, 2) == row_tiles
    groups = jnp.asarray(DIVISIONS[division], jnp.int32)
    rows, weights, d_out = operands(jnp.bfloat16)
    got = jax.jit(lambda *a: value_and_gradients(gm.grouped_matmul, *a))(
        rows, weights, d_out, groups)
    want = jax.jit(lambda *a: value_and_gradients(plain, *a))(
        rows, weights, d_out, groups)
    for mine, theirs in zip(got, want):
        assert mine.shape == theirs.shape
        assert np.isfinite(np.asarray(mine, np.float32)).all()
        assert distance(mine, theirs) < 2e-3
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    assert got[2].dtype == jnp.float32
    past = int(sum(DIVISIONS[division]))
    assert not np.asarray(got[0][past:], np.float32).any()
    # an empty group's experts get a zero gradient, not what was there
    for g, size in enumerate(DIVISIONS[division]):
        assert bool(np.asarray(got[2][g]).any()) == (size > 0)


@pytest.mark.parametrize("division", ["even", "boundaries_that_cut_tiles",
                                      "an_empty_group"])
def test_float32_rows_are_the_control(division):
    """With float32 rows nothing is rounded on the way in: the kernels'
    sums differ from `ragged_dot`'s by their order alone."""
    groups = jnp.asarray(DIVISIONS[division], jnp.int32)
    rows, weights, d_out = operands(jnp.float32)
    got = value_and_gradients(gm.grouped_matmul, rows, weights, d_out,
                              groups)
    want = value_and_gradients(plain, rows, weights, d_out, groups)
    for mine, theirs in zip(got, want):
        assert mine.dtype == jnp.float32
        assert distance(mine, theirs) < 1e-6


def test_a_wide_expert_is_taken_in_blocks_of_its_width(monkeypatch):
    """Where a whole expert is more than the VMEM budget holds, a block
    is whole lane tiles of its output width and the walk runs once a
    block: the same numbers."""
    groups = jnp.asarray(DIVISIONS["uneven"], jnp.int32)
    rows, weights, d_out = operands(jnp.bfloat16, a=256, c=256)
    whole = value_and_gradients(gm.grouped_matmul, rows, weights, d_out,
                                groups)
    monkeypatch.setattr(gm, "_VMEM_BUDGET", 3 * 2**19)
    jax.clear_caches()
    try:
        itemsize = rows.dtype.itemsize
        assert gm._row_tile(M, 256, 256, itemsize) == 512
        assert gm._width_tile(512, 256, 256, itemsize) == 128
        assert gm._width_tile(512, 256, 256, itemsize, dw=True) == 128
        blocks = value_and_gradients(gm.grouped_matmul, rows, weights,
                                     d_out, groups)
    finally:
        jax.clear_caches()
    for mine, theirs in zip(blocks, whole):
        np.testing.assert_array_equal(np.asarray(mine, np.float32),
                                      np.asarray(theirs, np.float32))


@pytest.mark.parametrize("m, a, c, dtype, taken", [
    (16384, 2048, 768, jnp.bfloat16, True),   # sdar_bd_s4096, up and gate
    (16384, 768, 2048, jnp.bfloat16, True),   # its down
    (8192, 2048, 1024, jnp.bfloat16, True),   # trinity_mini_s8192
    (8192, 1024, 2048, jnp.bfloat16, True),
    (512, 128, 768, jnp.bfloat16, True),      # sdar's tiny preset
    (512, 128, 768, jnp.float32, True),
    (512, 64, 128, jnp.bfloat16, False),      # trinity's tiny widths
    (512, 128, 64, jnp.bfloat16, False),
    (384, 128, 768, jnp.bfloat16, False),     # no whole row tile
    (500, 128, 768, jnp.bfloat16, False),
    (0, 128, 768, jnp.bfloat16, False),
    (512, 128, 768, jnp.int8, False),
    (1536, 128, 768, jnp.bfloat16, True),     # three tiles of 512
    (512, 4096, 4096, jnp.bfloat16, True),    # in blocks of its width
    (512, 131072, 128, jnp.bfloat16, False),  # no lane tile of it fits
])
def test_supports_is_a_table_of_shapes(m, a, c, dtype, taken):
    assert gm.supports(m, a, c, dtype) is taken
    assert gm.supports(m, c, a, dtype) is taken


def test_supports_refuses_a_backend_that_runs_neither_form(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert not gm.supports(512, 128, 768, jnp.bfloat16)


# -- the layer ---------------------------------------------------------------

def layer(**kw):
    return moe.RoutedMlp(num_experts=8, experts_held=8, experts_per_token=2,
                         mlp_dim=256, dtype=jnp.bfloat16, **kw)


@pytest.mark.parametrize("kw", [{}, {"score_func": "sigmoid",
                                     "shared_experts": 1}])
def test_the_layer_with_kernels_is_the_layer_forced_plain(monkeypatch, kw):
    """256 tokens of 128 choose 2 of 8 experts of 256: 512 rows, a
    shape the kernels take, by `expert_product`'s own choice; and the
    same layer with `supports` saying no. Outputs and parameter
    gradients agree to the products' rounding."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 128),
                          jnp.float32).astype(jnp.bfloat16)
    params = layer(**kw).init(jax.random.PRNGKey(2), x)["params"]
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)

    def run():
        calls = []
        real = gm.grouped_matmul
        monkeypatch.setattr(
            gm, "grouped_matmul",
            lambda *a: calls.append(a[0].shape) or real(*a))

        def loss(p):
            return jnp.sum(layer(**kw).apply({"params": p}, x).astype(
                jnp.float32) * ct)
        out = layer(**kw).apply({"params": params}, x)
        return out, jax.grad(loss)(params), calls

    out, grads, calls = run()
    assert calls[:3] == [(512, 128), (512, 128), (512, 256)]
    monkeypatch.setattr(gm, "supports", lambda *_: False)
    plain_out, plain_grads, plain_calls = run()
    assert not plain_calls
    assert distance(out, plain_out) < 2e-3
    flat, plain_flat = (dict(jax.tree_util.tree_leaves_with_path(g))
                        for g in (grads, plain_grads))
    assert flat.keys() == plain_flat.keys()
    for path, leaf in flat.items():
        assert leaf.dtype == jnp.float32
        assert distance(leaf, plain_flat[path]) < 5e-3, path


def test_the_further_products_run_the_kernels_under_their_cond():
    """A share sent more than its even share: the rows past
    `rows_static` go through further products of the same size, each
    under `lax.cond` and rematerialised, and those are kernels too."""
    held = moe.RoutedMlp(num_experts=8, experts_held=4, experts_per_token=2,
                         mlp_dim=128, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 256, 128),
                          jnp.float32).astype(jnp.bfloat16)
    params = held.init(jax.random.PRNGKey(5), x)["params"]
    # non-negative activations, the held experts' columns positive and
    # the others' negative: both of every token's choices live here,
    # twice the even share
    kernel = np.abs(np.asarray(params["router"]["kernel"]))
    kernel[:, 4:] *= -1
    params = {**params, "router": {"kernel": jnp.asarray(kernel)}}
    x = jnp.abs(x)
    _, static, most = moe.rows_static(1024, 2, 4, 8)
    assert (static, most) == (1024, 2048)
    _, sown = held.apply({"params": params}, x, mutable=["choices"])
    assert int(np.sum(np.asarray(sown["choices"]["experts"][0]) < 4)) == most

    def loss(p):
        return jnp.sum(held.apply({"params": p}, x).astype(
            jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss)).lower(params).as_text()
    assert text.count("stablehlo.case") + text.count("stablehlo.if") > 0
    grads = jax.grad(loss)(params)
    real_supports = gm.supports
    try:
        gm.supports = lambda *_: False
        plain_grads = jax.grad(loss)(params)
    finally:
        gm.supports = real_supports
    for name in ("gate", "up", "down"):
        assert np.asarray(grads[name]).any()
        assert distance(grads[name], plain_grads[name]) < 5e-3, name
