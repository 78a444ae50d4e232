import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
"""Multi-pod AOT dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any jax import: jax locks the device
count at first init, and the production meshes need 512 host devices.
(Smoke tests / benches never import this module, so they see 1 device.)

Per cell this produces:
  * compiled.memory_analysis()  -> bytes/device (does it fit 16 GB HBM?)
  * compiled.cost_analysis()    -> HLO flops & bytes for §Roofline
  * collective byte census      -> parsed from compiled HLO text
all dumped as JSON under experiments/dryrun/ for benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b \
      --shape train_4k [--multi-pod] [--dp-mode manual] [--out DIR]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse   # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_arch, list_archs, shape_applicable  # noqa: E402
from repro.configs.base import RuntimeConfig  # noqa: E402
from repro.dist import sharding as shd  # noqa: E402
from repro.launch import specs as specs_mod  # noqa: E402
from repro.launch.mesh import batch_spec_axes, make_production_mesh  # noqa: E402
from repro.models import model  # noqa: E402
from repro.optim import adamw as opt  # noqa: E402
from repro.train import trainer  # noqa: E402

DEFAULT_OUT = "experiments/dryrun"

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s32|u32|s16|u16|s8|u8|pred|s64|u64)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
          "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8,
          "u64": 8}


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _BYTES[dt]


def collective_census(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the optimized HLO,
    bucketed by kind. 'start' variants counted once ('done' skipped)."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.lstrip()
        m = re.search(r"=\s*(?:\([^)]*\)\s*)?(\S+)\s", s)
        if m is None:
            continue
        for kind in _COLLECTIVES:
            token = s.split("=", 1)[1] if "=" in s else s
            if re.search(rf"\b{kind}(-start)?\(", token):
                shapes = _SHAPE_RE.finditer(s.split("=", 1)[0] + " " +
                                            token.split("(", 1)[0])
                b = sum(_shape_bytes(x) for x in shapes)
                out[kind]["count"] += 1
                out[kind]["bytes"] += b
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _sharded_struct(tree, mesh, spec_fn):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    def visit(path, leaf):
        name = "/".join(str(p) for p in path)
        spec = spec_fn(name, leaf.shape)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree_util.tree_map_with_path(visit, tree)


def _zero1ify(spec: P, shape, mesh, enabled: bool) -> P:
    """Shard optimizer moments over a DP axis the param spec left unused
    (params are already FSDP x TP; ZeRO-1 grabs "pod" when available)."""
    if not enabled:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for p in parts:
        for a in (p if isinstance(p, tuple) else (p,)):
            if a:
                used.add(a)
    for axis in ("data", "pod"):
        if axis not in mesh.axis_names or axis in used:
            continue
        asize = mesh.shape[axis]
        for i, (p, s) in enumerate(zip(parts, shape)):
            if p is None and s % asize == 0 and s >= asize:
                parts[i] = axis
                used.add(axis)
                break
    return P(*parts)


DEFAULT_TRAIN_RUNTIME = RuntimeConfig(microbatch=8)


def build_cell(arch: str, shape_name: str, mesh, *, dp_mode: str = "gspmd",
               runtime: RuntimeConfig = None, overrides: dict = None):
    """Returns (fn, example_args_structs) ready for jit().lower().

    ``overrides``: perf-iteration knobs — {"kv_quant": "takum8",
    "param_dtype": "bf16", "weight_wire": "takum8", "microbatch": k}.
    """
    import dataclasses as _dc
    spec = get_arch(arch)
    cfg = spec.config
    ov = overrides or {}
    cfg_over = {k: ov[k] for k in ("kv_quant", "param_dtype", "dtype")
                if k in ov}
    if cfg_over:
        cfg = _dc.replace(cfg, **cfg_over)
    shape = SHAPES[shape_name]
    if runtime is None:
        # baseline: 8-way gradient accumulation keeps live activations at
        # (global_batch/8) sequences per step — the standard answer for a
        # 1M-token global batch
        runtime = DEFAULT_TRAIN_RUNTIME if shape.kind == "train" \
            else RuntimeConfig()
    rules = shd.RULES_3D if "pod" in mesh.axis_names else shd.RULES_2D
    dp = batch_spec_axes(mesh, shape.global_batch)

    axis_sizes = dict(mesh.shape)

    def param_spec_fn(name, shp):
        return shd.param_spec(name, shp, rules, axis_sizes=axis_sizes)

    params_s = jax.eval_shape(
        lambda k: model.init(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32))
    if ov.get("weight_wire"):
        # store >=2D weights as takum words on the wire/HBM (serving only)
        assert shape.kind != "train", "weight_wire is a serving option"
        from repro.core.bitops import word_dtype
        wdt = word_dtype(int(ov["weight_wire"].replace("takum", "")))

        def to_wire(path, s):
            if len(s.shape) >= 2 and jnp.issubdtype(s.dtype, jnp.floating):
                return jax.ShapeDtypeStruct(s.shape, wdt)
            return s

        params_s = jax.tree_util.tree_map_with_path(to_wire, params_s)
    params_sharded = _sharded_struct(params_s, mesh, param_spec_fn)

    batch_s = specs_mod.input_specs(cfg, shape)

    def batch_spec_fn(name, shp):
        return P(*(dp,) + (None,) * (len(shp) - 1)) if shp and shp[0] == \
            shape.global_batch else P()

    batch_sharded = _sharded_struct(batch_s, mesh, batch_spec_fn)

    if shape.kind == "train":
        ocfg = opt.AdamWConfig()
        if dp_mode == "manual":
            return _build_manual_train(cfg, shape, mesh, runtime, ocfg,
                                       params_s, params_sharded,
                                       batch_sharded, rules)
        opt_s = jax.eval_shape(opt.init_state, params_s)

        def opt_spec_fn(name, shp):
            # m/v follow the param TP sharding + ZeRO-1 over "data"
            base = shd.param_spec(name, shp, rules)
            return _zero1ify(base, shp, mesh, runtime.zero1)

        opt_sharded = _sharded_struct(opt_s, mesh, opt_spec_fn)
        step = trainer.make_train_step_gspmd(cfg, ocfg, runtime)

        def fn(params, opt_state, batch):
            with shd.use_rules(mesh, rules):
                return step(params, opt_state, batch)

        return fn, (params_sharded, opt_sharded, batch_sharded)

    enc_len = max(shape.seq_len // 4, 8)
    if shape.kind == "prefill":
        cache_s = jax.eval_shape(
            lambda: model.init_cache(cfg, shape.global_batch,
                                     shape.seq_len + 64, enc_len=enc_len))
        cache_sharded = _sharded_struct(
            cache_s, mesh, lambda n, s: _cache_spec(n, s, cfg, shape, dp, mesh))

        def fn(params, batch, cache):
            with shd.use_rules(mesh, rules):
                media = batch.get("media")
                return model.prefill(params, batch["tokens"], cfg, cache,
                                     media=media)

        return fn, (params_sharded, batch_sharded, cache_sharded)

    # decode
    cache_s = jax.eval_shape(
        lambda: model.init_cache(cfg, shape.global_batch, shape.seq_len + 64,
                                 enc_len=enc_len))
    cache_sharded = _sharded_struct(
        cache_s, mesh, lambda n, s: _cache_spec(n, s, cfg, shape, dp, mesh))
    pos_s = jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))

    def fn(params, batch, cache, pos):
        with shd.use_rules(mesh, rules):
            return model.decode_step(params, batch["tokens"], cfg, cache,
                                     pos=pos)

    return fn, (params_sharded, batch_sharded, cache_sharded, pos_s)


def _flat_spec_of(params_s, pad_to: int):
    """flatten_like's unflatten spec, computed from structs (no tracing)."""
    import numpy as np
    leaves, treedef = jax.tree_util.tree_flatten(params_s)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    shapes = [tuple(l.shape) for l in leaves]
    dtypes = [l.dtype for l in leaves]
    total = sum(sizes)
    pad = (-total) % pad_to
    return (treedef, sizes, shapes, dtypes, pad), total + pad


def _build_manual_train(cfg, shape, mesh, runtime, ocfg, params_s,
                        params_sharded, batch_sharded, rules):
    """Manual-DP ZeRO-1 step with takum-compressed cross-pod collectives —
    the beyond-paper optimised train path (§Perf)."""
    dp = mesh.shape["data"]
    npod = mesh.shape.get("pod", 1)
    flat_spec, g = _flat_spec_of(params_s, pad_to=dp)
    compress = trainer.grad_spec_from_quant(runtime.quant.grad_allreduce)
    step = trainer.make_train_step_manual(cfg, ocfg, runtime, mesh,
                                          flat_spec, compress=compress)
    state_s = trainer.TrainStateFlat(
        m=jax.ShapeDtypeStruct((g,), jnp.float32,
                               sharding=NamedSharding(mesh, P("data"))),
        v=jax.ShapeDtypeStruct((g,), jnp.float32,
                               sharding=NamedSharding(mesh, P("data"))),
        ef=jax.ShapeDtypeStruct(
            (npod, dp, g // dp), jnp.float32,
            sharding=NamedSharding(mesh, P("pod", "data", None)
                                   if "pod" in mesh.axis_names
                                   else P(None, "data", None))),
        step=jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=NamedSharding(mesh, P())))

    def fn(params, state, batch):
        with shd.use_rules(mesh, rules):
            return step(params, state, batch)

    return fn, (params_sharded, state_s, batch_sharded)


def _cache_spec(name, shp, cfg, shape, dp, mesh) -> P:
    """Cache/state leaves (with or without a leading layer-stack dim):
    the batch dim (matched by size) rides the DP axes; the first large
    "model"-divisible dim gets the model axis — for KV caches that is the
    sequence dim (flash-decode style partial attention + tiny psum: kv
    head counts rarely divide 16 but the cache depth always does)."""
    b = shape.global_batch
    if not shp:
        return P()
    parts: list = [None] * len(shp)
    msize = mesh.shape["model"]
    dpsize = 1
    for a in dp:
        dpsize *= mesh.shape[a]
    bdim = -1
    for i, s in enumerate(shp[:2]):
        if s == b:
            if dp and b % dpsize == 0:
                parts[i] = dp
            bdim = i
            break
    for i in range(bdim + 1, len(shp)):
        if shp[i] >= msize and shp[i] % msize == 0:
            parts[i] = "model"
            break
    return P(*parts)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = DEFAULT_OUT, dp_mode: str = "gspmd",
             runtime: RuntimeConfig = None, tag: str = "",
             overrides: dict = None) -> dict:
    cfg = get_arch(arch).config
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "dp_mode": dp_mode, "tag": tag}
    if not ok:
        cell["status"] = "skipped"
        cell["reason"] = reason
        _dump(cell, out_dir, tag)
        return cell

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args = build_cell(arch, shape_name, mesh, dp_mode=dp_mode,
                              runtime=runtime, overrides=overrides)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn).lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        census = collective_census(compiled.as_text())
        cell.update({
            "status": "ok",
            "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "flops": float(cost.get("flops", -1)) if cost else -1,
            "bytes_accessed": float(cost.get("bytes accessed", -1))
            if cost else -1,
            "collectives": census,
            "memory": _mem_dict(mem),
            "n_devices": 512 if multi_pod else 256,
            "params": get_arch(arch).config.param_count(),
            "active_params": get_arch(arch).config.active_param_count(),
        })
    except Exception as e:  # noqa: BLE001
        cell["status"] = "error"
        cell["error"] = f"{type(e).__name__}: {e}"
        cell["traceback"] = traceback.format_exc()[-4000:]
    _dump(cell, out_dir, tag)
    return cell


def _mem_dict(mem):
    if mem is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        try:
            out[k] = int(getattr(mem, k))
        except Exception:  # noqa: BLE001
            pass
    return out


def _dump(cell, out_dir, tag=""):
    os.makedirs(out_dir, exist_ok=True)
    sfx = f"__{tag}" if tag else ""
    path = os.path.join(
        out_dir, f"{cell['arch']}__{cell['shape']}__{cell['mesh']}{sfx}.json")
    slim = {k: v for k, v in cell.items() if k != "traceback"}
    with open(path, "w") as f:
        json.dump(slim, f, indent=1)
    if cell.get("traceback"):
        with open(path + ".err", "w") as f:
            f.write(cell["traceback"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--dp-mode", default="gspmd")
    ap.add_argument("--tag", default="")
    # perf-iteration knobs (EXPERIMENTS.md §Perf)
    ap.add_argument("--param-dtype", default="")
    ap.add_argument("--kv-quant", default="")
    ap.add_argument("--weight-wire", default="")
    args = ap.parse_args()
    overrides = {}
    if args.param_dtype:
        overrides["param_dtype"] = args.param_dtype
    if args.kv_quant:
        overrides["kv_quant"] = args.kv_quant
    if args.weight_wire:
        overrides["weight_wire"] = args.weight_wire

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_ok = n_skip = n_err = 0
    for arch, shape in cells:
        for mp in meshes:
            cell = run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                            dp_mode=args.dp_mode, tag=args.tag,
                            overrides=overrides)
            status = cell["status"]
            n_ok += status == "ok"
            n_skip += status == "skipped"
            n_err += status == "error"
            msg = (f"[{status:7s}] {arch:24s} {shape:12s} "
                   f"{'2x16x16' if mp else '16x16':8s}")
            if status == "ok":
                msg += (f" compile={cell['compile_s']:7.1f}s "
                        f"flops={cell['flops']:.3e} "
                        f"coll={cell['collectives']['total_bytes']:.3e}B")
            elif status == "error":
                msg += " " + cell["error"][:120]
            print(msg, flush=True)
    print(f"dryrun: {n_ok} ok, {n_skip} skipped, {n_err} errors", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
