"""The state-space scan's two Pallas kernels (``horovod_tpu/ops/ssd_scan.py``,
interpreted here) against the plain chunked form they stand for
(``models/mamba._scan_chunks``) and against the recurrence run position
by position: the output and the gradient by every argument (x, dt, a,
B, C, D), float32 tight and bf16 within the limits
``tests/test_mamba_mixer.py`` uses; and the choice between the two
forms, which shapes alone decide.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import mamba
from horovod_tpu.ops import ssd_scan
from horovod_tpu.utils import scopes

HEADS, D_HEAD, D_STATE = 16, 16, 128
NAMES = ("x", "dt", "a", "b", "c", "d")


def seeded(t, groups, dtype, batch=2, seed=0):
    """(x, dt, a, b, c, d) as the mixer hands them to `ssd_scan`, and a
    cotangent for y."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (batch, t, HEADS, D_HEAD)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, t, HEADS)) - 2)
    a = -jnp.exp(0.3 * jax.random.normal(keys[2], (HEADS,)))
    b, c = (0.3 * jax.random.normal(key, (batch, t, groups, D_STATE))
            for key in keys[3:5])
    d = 1 + 0.5 * jax.random.normal(keys[5], (HEADS,))
    return (x, dt, a, b.astype(dtype), c.astype(dtype), d), \
        jax.random.normal(keys[6], x.shape)


def kernels(x, dt, a, b, c, d, chunk):
    assert mamba.scan_runs_as_kernels(
        x.shape[1], chunk, D_HEAD, D_STATE, HEADS // b.shape[2], x.dtype)
    return mamba.ssd_scan(x, dt, a, b, c, d, chunk)


def plain(x, dt, a, b, c, d, chunk, monkeypatch):
    """`ssd_scan` with the kernels refused."""
    with monkeypatch.context() as m:
        m.setattr(ssd_scan, "supports", lambda *_: False)
        return mamba.ssd_scan(x, dt, a, b, c, d, chunk)


def recurrence(x, dt, a, b, c, d):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D
    x_t, one position at a time, in float32."""
    per_group = HEADS // b.shape[2]
    x, b, c = (z.astype(jnp.float32) for z in (x, b, c))
    b, c = (jnp.repeat(z, per_group, axis=2) for z in (b, c))

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state + jnp.einsum(
            "bh,bhp,bhn->bhpn", dt_t, x_t, b_t)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    with jax.default_matmul_precision("highest"):
        _, y = lax.scan(
            step, jnp.zeros((x.shape[0], HEADS, D_HEAD, D_STATE)),
            tuple(jnp.moveaxis(z, 1, 0) for z in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def relative(a, b):
    a, b = (np.asarray(z, np.float64) for z in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def with_gradients(fn, args, ct):
    y, vjp = jax.vjp(fn, *args)
    return y, vjp(ct)


# (positions, chunk, groups): two chunks; two groups; a sequence padded
# to a chunk; the other chunk size; three chunks of two groups
CASES = [(256, 128, 1), (256, 128, 2), (200, 128, 1), (512, 256, 1),
         (384, 128, 2)]


@pytest.mark.parametrize("t,chunk,groups", CASES)
def test_float32_is_the_plain_form_and_the_recurrence(t, chunk, groups,
                                                      monkeypatch):
    args, ct = seeded(t, groups, jnp.float32)
    y, grads = with_gradients(lambda *z: kernels(*z, chunk), args, ct)
    y_plain, grads_plain = with_gradients(
        lambda *z: plain(*z, chunk, monkeypatch), args, ct)
    y_ref, grads_ref = with_gradients(recurrence, args, ct)
    assert y.shape == y_ref.shape and y.dtype == jnp.float32
    assert relative(y, y_plain) < 1e-6 and relative(y, y_ref) < 2e-5
    for name, g, g_plain, g_ref in zip(NAMES, grads, grads_plain,
                                       grads_ref):
        assert g.shape == g_ref.shape and g.dtype == g_ref.dtype, name
        assert relative(g, g_plain) < 2e-5, name
        assert relative(g, g_ref) < 1e-4, name


@pytest.mark.parametrize("t,chunk,groups", CASES[1:4])
def test_bf16_is_near_the_recurrence_and_nearer_the_plain_form(
        t, chunk, groups, monkeypatch):
    """The roundings stand where the plain form's stand, so the output
    is the plain form's to float32's last places; the gradients differ
    from it by less than either differs from the recurrence (the limits
    of `tests/test_mamba_mixer.py`: 3e-2 over all, 1e-1 a leaf)."""
    args, ct = seeded(t, groups, jnp.bfloat16)
    y, grads = with_gradients(lambda *z: kernels(*z, chunk), args, ct)
    y_plain, grads_plain = with_gradients(
        lambda *z: plain(*z, chunk, monkeypatch), args, ct)
    y_ref, grads_ref = with_gradients(recurrence, args, ct)
    assert relative(y, y_plain) < 1e-5 and relative(y, y_ref) < 1e-2
    flat = [np.concatenate([np.ravel(np.asarray(g, np.float64))
                            for g in gs]) for gs in (grads, grads_ref)]
    assert relative(*flat) < 3e-2
    for name, g, g_plain, g_ref in zip(NAMES, grads, grads_plain,
                                       grads_ref):
        assert g.dtype == g_plain.dtype, name
        assert relative(g, g_ref) < 1e-1, name
        assert relative(g, g_plain) < 1e-2, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_change_at_position_i_moves_no_output_before_i(dtype):
    """Across chunk boundaries too: the outputs before the changed
    position are the same bits."""
    (x, *rest), _ = seeded(384, 1, dtype, batch=1)
    y = kernels(x, *rest, 128)
    for i in (0, 127, 128, 300):
        moved = kernels(x.at[:, i].add(1.0), *rest, 128)
        assert np.array_equal(np.asarray(y[:, :i]),
                              np.asarray(moved[:, :i])), i
        assert not np.array_equal(np.asarray(y[:, i]),
                                  np.asarray(moved[:, i])), i


@pytest.mark.parametrize("chunk,d_head,d_state,heads,dtype,takes", [
    (256, 64, 128, 64, jnp.bfloat16, True),   # granite_h_lm's layer
    (128, 16, 128, 8, jnp.float32, True),     # this file's
    (16, 16, 8, 4, jnp.float32, False),       # the tests' tiny preset
    (32, 64, 16, 4, jnp.bfloat16, False),     # the cell's tiny preset
    (64, 64, 128, 64, jnp.bfloat16, False),   # a chunk under a lane tile
    (256, 64, 64, 64, jnp.bfloat16, False),   # a state under a lane tile
    (256, 64, 128, 4, jnp.bfloat16, False),   # heads under a sublane tile
    (256, 24, 128, 8, jnp.bfloat16, False),   # a block no whole lane tiles
    (2048, 64, 128, 64, jnp.bfloat16, False),  # tiles VMEM does not hold
    (256, 64, 128, 64, jnp.int8, False),
])
def test_shapes_alone_choose_the_form(chunk, d_head, d_state, heads, dtype,
                                      takes):
    assert ssd_scan.supports(chunk, d_head, d_state, heads, dtype) is takes


def pallas_calls(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            pallas_calls(sub, found)
    return found


def test_the_cells_shape_runs_the_two_kernels_and_the_tiny_one_none():
    """`granite_h_lm`'s layer, traced and not run: one forward call and
    one backward call, by name; the tiny preset's shape none."""
    def calls(t, heads, d_head, d_state, chunk, dtype):
        shapes = (jax.ShapeDtypeStruct((1, t, heads, d_head), dtype),
                  jax.ShapeDtypeStruct((1, t, heads), jnp.float32),
                  jax.ShapeDtypeStruct((heads,), jnp.float32),
                  jax.ShapeDtypeStruct((1, t, 1, d_state), dtype),
                  jax.ShapeDtypeStruct((1, t, 1, d_state), dtype),
                  jax.ShapeDtypeStruct((heads,), jnp.float32))

        def loss(*z):
            return jnp.sum(mamba.ssd_scan(*z, chunk))

        return pallas_calls(jax.make_jaxpr(
            jax.grad(loss, argnums=tuple(range(6))))(*shapes).jaxpr)

    assert sorted(calls(8192, 64, 64, 128, 256, jnp.bfloat16)) == [
        scopes.SSD_SCAN_BWD, scopes.SSD_SCAN_FWD]
    assert calls(64, 4, 64, 16, 32, jnp.bfloat16) == []
    # a sequence shorter than a chunk is one chunk of its own length
    assert mamba.scan_runs_as_kernels(8192, 256, 64, 128, 64, jnp.bfloat16)
    assert not mamba.scan_runs_as_kernels(64, 256, 64, 128, 64, jnp.bfloat16)


def test_what_a_call_holds_in_vmem_at_the_cells_shape():
    """The reckoning `supports` admits by: the cell's backward call
    holds under 16 MiB, and the limit the calls state is above it."""
    hb = ssd_scan.heads_block(64, 64)
    charge = ssd_scan._vmem_charge(256, hb, 64, 128, 64, 2)
    assert hb % 8 == 0 and (hb * 64) % 128 == 0
    assert charge < 16 * 2**20 <= ssd_scan._VMEM_LIMIT_LEAST
    assert charge < ssd_scan._VMEM_BUDGET
