"""Hybrid device-mesh construction: dp / fsdp / tp / sp / ep / pp axes.

The reference is data-parallel only; hand-rolled hybrid schemes use
process sets (SURVEY.md §2.5). The TPU-native framework makes hybrid
parallelism first-class: one `Mesh` with named axes, shardings annotated
per tensor, XLA inserting collectives that ride ICI (the scaling-book
recipe).

Axis vocabulary (canonical order):
  dp    pure data parallel (params replicated)
  fsdp  data parallel with parameter sharding (ZeRO-3 style)
  tp    tensor parallel (attention heads / mlp hidden)
  sp    sequence/context parallel (ring attention / Ulysses)
  ep    expert parallel (MoE all-to-all)
  pp    pipeline parallel
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "ep", "tp")


def make_mesh(
    dp: int = 0,
    fsdp: int = 1,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
    pp: int = 1,
    devices=None,
):
    """Build a Mesh over all devices with the requested axis sizes.

    `dp=0` (default) means "whatever is left": dp absorbs the remaining
    device count after the explicit axes. Axis order follows AXIS_ORDER —
    tp innermost (fastest-varying → nearest neighbors on the ICI torus,
    where tp's latency-sensitive collectives belong; the scaling-book
    layout), pp outermost (DCN-friendly point-to-point).
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    sizes = {"pp": pp, "dp": dp, "fsdp": fsdp, "sp": sp, "ep": ep, "tp": tp}
    explicit = int(np.prod([v for v in sizes.values() if v > 0]))
    if dp == 0:
        if n % explicit:
            raise ValueError(
                f"explicit axes {sizes} (product {explicit}) do not divide "
                f"{n} devices"
            )
        sizes["dp"] = n // explicit
    total = int(np.prod(list(sizes.values())))
    if total != n:
        raise ValueError(f"mesh {sizes} needs {total} devices, have {n}")

    shape = tuple(sizes[a] for a in AXIS_ORDER)
    if devices[0].platform == "tpu":
        # topology-aware placement; a shape the physical topology cannot
        # host raises here — never a silent change of layout
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        # virtual CPU meshes have no topology: plain reshape
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def squeeze_mesh(mesh):
    """Drop size-1 axes (cosmetic; specs may still name them)."""
    return mesh


def data_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes of size > 1 over which the batch is sharded (the
    gradient-reduction world); empty tuple if neither dp nor fsdp is
    present with extent."""
    present = dict(zip(mesh.axis_names, mesh.devices.shape))
    return tuple(a for a in ("dp", "fsdp") if present.get(a, 1) > 1)
