"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state. Shapes per the deployment plan:

* single pod : (16, 16)    -> ("data", "model")   = 256 chips (v5e pod)
* multi-pod  : (2, 16, 16) -> ("pod", "data", "model") = 512 chips

The "pod" axis carries only data parallelism (gradient all-reduce) —
cross-pod links are the slow DCN/ICI hops that the takum-compressed
collectives target.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "dp_axes", "batch_spec_axes"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the layers constrain shardings through
    # with_sharding_constraint, which refuses Explicit mesh axes
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_spec_axes(mesh, global_batch: int) -> tuple:
    """Largest prefix of the DP axes that divides the batch (B=1 decode
    replicates; B=128 multi-pod uses ("pod","data")).

    A batch that divides *no* DP axis is a config error, not a request
    for replication: silently returning ``()`` used to make every
    device process the full batch — an N-fold redundant step that looks
    like a working run with N-times-too-slow throughput. Raise instead,
    naming the mesh and the batch; ``global_batch == 1`` (lockstep
    decode) legitimately replicates and stays allowed.
    """
    if global_batch == 1:
        return ()
    axes = []
    div = 1
    for a in dp_axes(mesh):
        if global_batch % (div * mesh.shape[a]) == 0:
            axes.append(a)
            div *= mesh.shape[a]
    if not axes:
        dp = {a: mesh.shape[a] for a in dp_axes(mesh)}
        raise ValueError(
            f"global_batch={global_batch} divides no DP axis of mesh "
            f"{dict(mesh.shape)} (DP axes: {dp or 'none'}); pick a "
            "batch divisible by a DP axis size or reshape the mesh")
    return tuple(axes)
