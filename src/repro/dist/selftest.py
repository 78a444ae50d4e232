"""Multi-device functional selftest for repro.dist.

Run as ``python -m repro.dist.selftest`` (tests/test_dist.py and
``make dist-selftest`` drive it in subprocesses so the main pytest
process keeps seeing 1 device). ``REPRO_HOST_DEVICES`` picks the forced
host device count (default 8); with fewer than 8 devices the ring
checks degrade to the size-1 identity contract instead of skipping
silently. Prints ``SELFTEST OK`` and exits 0 on success.

Covered (8 devices):
* ring_reduce_scatter / ring_all_gather / ring_all_reduce vs the lax
  references, exactly (integer-valued floats: addition order cannot bite);
* compressed all-reduce: wire error bounded and error-feedback residual
  consistent (residual + wire == input, to f32 round-off);
* annotate/use_rules producing the expected NamedSharding under jit;
* param_spec FSDP x TP placements on representative parameter names;
* the mixture-of-experts layer under those placements: the same result
  as on one device, and no expert stack gathered whole.

Covered (1 device): size-1 collectives are exact identities with zero
residual, and ``wire_roundtrip`` honours both spec families (QuantSpec
and registry FormatSpec) — the contract the single-pod serve path and
``serve.shard`` rely on.
"""

import os

N_DEV = int(os.environ.get("REPRO_HOST_DEVICES", "8"))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEV} "
    + os.environ.get("XLA_FLAGS", ""))

import functools  # noqa: E402

import jax                    # noqa: E402
import jax.numpy as jnp       # noqa: E402
import numpy as np            # noqa: E402
from jax import lax           # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.quant import QuantSpec           # noqa: E402
from repro.dist import collectives as coll       # noqa: E402
from repro.dist import sharding as shd           # noqa: E402

TAKUM16 = QuantSpec(fmt="takum", n=16, scale="none")


def _mesh(shape, axes):
    # Auto axes: annotate() constrains shardings with
    # with_sharding_constraint, which refuses Explicit mesh axes
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _mesh1d(size=8):
    return _mesh((size,), ("data",))


def check_reduce_scatter(mesh):
    size = 8
    g = 8 * 16
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-8, 8, size=(size, g)).astype(np.float32))

    fn = jax.jit(jax.shard_map(
        lambda v: coll.ring_reduce_scatter(v[0], "data", size)[0][None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
    got = np.asarray(fn(x)).reshape(-1)
    want = np.asarray(x).sum(axis=0)  # rank r owns chunk r -> concat = sum
    np.testing.assert_array_equal(got, want)


def check_all_gather(mesh):
    size = 8
    c = 16
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-8, 8, size=(size, c)).astype(np.float32))
    fn = jax.jit(jax.shard_map(
        lambda v: coll.ring_all_gather(v[0], "data", size)[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data", None),
        check_vma=False))
    got = np.asarray(fn(x))
    want = np.tile(np.asarray(x).reshape(-1), (size, 1)).reshape(got.shape)
    np.testing.assert_array_equal(got, want)


def check_all_reduce(mesh, spec, exact: bool):
    size = 8
    c = 40  # deliberately not divisible by 8: exercises internal padding
    rng = np.random.default_rng(2)
    base = rng.integers(-8, 8, size=(size, c)).astype(np.float32)
    if not exact:
        base = base * 10.0 ** rng.uniform(-3, 3, size=(size, c)).astype(
            np.float32)
    x = jnp.asarray(base)

    fn = jax.jit(jax.shard_map(
        functools.partial(_ar_local, size=size, spec=spec),
        mesh=mesh, in_specs=P("data"), out_specs=(P("data", None),
                                                  P("data", None)),
        check_vma=False))
    y, resid = fn(x)
    y, resid = np.asarray(y), np.asarray(resid)
    want = base.sum(axis=0)
    if exact:
        np.testing.assert_array_equal(y[0], want)
        np.testing.assert_array_equal(resid, np.zeros_like(resid))
    else:
        # all ranks agree bit-for-bit on the wire result
        for r in range(1, size):
            np.testing.assert_array_equal(y[r], y[0])
        ok = want != 0
        rel = np.abs(y[0][ok] - want[ok]) / np.abs(want[ok])
        assert np.median(rel) < 2e-3, np.median(rel)  # takum16 wire error


def _ar_local(v, *, size, spec):
    y, resid = coll.ring_all_reduce(v[0], "data", size, spec=spec)
    return y[None], resid[None]


def check_annotate():
    mesh = _mesh((2, 4), ("data", "model"))
    rules = shd.RULES_2D

    @jax.jit
    def f(x):
        with shd.use_rules(mesh, rules):
            return shd.annotate(x, "batch", "seq", "ff")

    x = jnp.zeros((4, 3, 8))
    out = f(x)
    want = NamedSharding(mesh, P("data", None, "model"))
    assert out.sharding.is_equivalent_to(want, 3), out.sharding
    # identity outside a rules context
    assert shd.annotate(x, "batch", "seq", "ff") is x
    # non-divisible dims are dropped, not errors
    y = f(jnp.zeros((3, 3, 5)))
    assert y.shape == (3, 3, 5)


def check_param_spec():
    rules = shd.RULES_2D
    sizes = {"data": 2, "model": 4}
    assert shd.param_spec("blk/attn/wq", (64, 128), rules,
                          axis_sizes=sizes) == P("data", "model")
    assert shd.param_spec("blk/attn/wo", (128, 64), rules,
                          axis_sizes=sizes) == P("model", "data")
    assert shd.param_spec("blk/norm/scale", (64,), rules,
                          axis_sizes=sizes) == P()
    assert shd.param_spec("embed/embed_tokens", (1024, 64), rules,
                          axis_sizes=sizes) == P("model", "data")
    # stacked layer dim stays unsharded
    spec = shd.param_spec("stack/mlp/w1", (12, 64, 256), rules,
                          axis_sizes=sizes)
    assert spec[0] is None and spec[2] == "model", spec
    # divisibility guard
    assert shd.param_spec("blk/attn/wq", (63, 127), rules,
                          axis_sizes=sizes) == P(None, None)


def check_moe_sharding():
    """The dropless MoE layer with its stacks placed by ``param_spec``
    (FSDP on d_model, TP on the expert width) and the batch on the data
    axis: equal to the one-device result, and the compiled program
    gathers no expert stack whole (each device multiplies its shard)."""
    import re

    from repro.configs.base import ModelConfig
    from repro.models import moe
    mesh = _mesh((2, 4), ("data", "model"))
    rules, sizes = shd.RULES_2D, {"data": 2, "model": 4}
    e, d, ff = 16, 128, 256
    cfg = ModelConfig(name="moe-shard", family="moe", n_layers=1,
                      d_model=d, n_heads=4, n_kv_heads=2, d_ff=ff, vocab=64,
                      n_experts=e, top_k=4, dtype="f32")
    params = moe.moe_init(jax.random.PRNGKey(0), d, ff, e)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d))

    def f(p, x):
        with shd.use_rules(mesh, rules):
            return moe.moe_apply(p, x, cfg)[0]

    want = jax.jit(f)(params, x)
    placed = {k: jax.device_put(v, NamedSharding(mesh, shd.param_spec(
        k, v.shape, rules, axis_sizes=sizes))) for k, v in params.items()}
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    step = jax.jit(f).lower(placed, xs).compile()
    np.testing.assert_allclose(np.asarray(step(placed, xs)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    whole = (f"f32[{e},{d},{ff}]", f"f32[{e},{ff},{d}]")
    gathers = [ln for ln in step.as_text().splitlines()
               if re.search(r"= [^ ]+ all-gather\(", ln)]
    assert not [g for g in gathers if any(w in g for w in whole)], gathers
    assert placed["experts_wg"].addressable_shards[0].data.shape == \
        (e, d // 2, ff // 4)


def check_size1():
    """The single-device contract: every collective is the exact
    identity with a zero residual, and wire_roundtrip accepts both a
    QuantSpec and a registry FormatSpec."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(40,)).astype(np.float32))
    mesh = _mesh1d(1)
    for fn in (
        lambda v: coll.ring_reduce_scatter(v, "data", 1)[0],
        lambda v: coll.ring_all_gather(v, "data", 1),
        lambda v: coll.ring_all_reduce(v, "data", 1)[0],
    ):
        got = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(),
                                    out_specs=P(), check_vma=False))(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    # both spec families through the same wire seam
    from repro import formats
    for spec in (None, TAKUM16, formats.resolve("takum16"),
                 formats.resolve("none")):
        y, res = coll.wire_roundtrip(x, spec)
        np.testing.assert_allclose(np.asarray(y) + np.asarray(res),
                                   np.asarray(x), rtol=0, atol=1e-6)
        if spec is None or getattr(spec, "is_identity", False) \
                or getattr(spec, "fmt", None) == "none":
            np.testing.assert_array_equal(np.asarray(res),
                                          np.zeros_like(res))


def main() -> int:
    assert jax.device_count() >= N_DEV, (jax.device_count(), N_DEV)
    if jax.device_count() >= 8:
        mesh = _mesh1d()
        check_reduce_scatter(mesh)
        check_all_gather(mesh)
        check_all_reduce(mesh, spec=None, exact=True)
        check_all_reduce(mesh, spec=TAKUM16, exact=False)
        check_annotate()  # needs the (2, 4) mesh
        check_moe_sharding()
    check_size1()
    check_param_spec()
    print("SELFTEST OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
