import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS") or
                           "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, and fits — and extract exact roofline inputs.

Per cell, three artifacts go into one JSON:

1. REAL compile (scan-based stacks, production microbatching):
   ``.lower().compile()`` on the target mesh + ``memory_analysis()``
   (per-device argument/output/temp bytes — the HBM-fit proof) +
   ``cost_analysis()`` + trip-count-weighted collective bytes parsed from
   ``compiled.as_text()``.

2. CALIBRATION compiles (single-pod only): XLA's CPU cost model counts
   while-loop bodies ONCE, so scan-based flop counts are not per-step
   totals. We therefore lower python-UNROLLED variants with 2 and 3 layer
   units (unit = layer; hybrid = one mamba group + shared block; whisper =
   one enc + one dec layer) and extrapolate linearly in depth — exact for
   homogeneous stacks (k=1 avoided: GSPMD partitions single-layer graphs
   differently; from k>=2 increments are verified linear):

       F_step(L) = F(2) + (L - 2) * (F(3) - F(2))

   For train the microbatch loop is also removed (1 microbatch of B/M
   sequences lowered; the fused-update epilogue F_upd is compiled separately
   on the full config):

       F_total = M * F_step(L) - (M - 1) * F_upd

3. Analytic MODEL_FLOPS (6ND / 2ND) for the usefulness ratio.

Usage:
    python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
        --mesh single --out artifacts/dryrun
    python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp


def _cells(archs, shapes):
    from repro.configs import get_config
    from repro.launch.shapes import SHAPES, applicable
    for a in archs:
        cfg = get_config(a)
        for s in shapes:
            if applicable(cfg, s):
                yield a, s


def _layer_units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.hybrid_period
    return cfg.num_layers


def _variant(cfg, k: int):
    """Unrolled k-layer-unit variant of cfg (identical per-unit compute)."""
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, num_layers=k * cfg.hybrid_period,
                                   scan_layers=False)
    if cfg.family == "audio":
        return dataclasses.replace(cfg, num_layers=k, encoder_layers=k,
                                   scan_layers=False)
    return dataclasses.replace(cfg, num_layers=k, scan_layers=False)


def _analyse(compiled, cfg=None):
    from repro.launch.hlo import (collective_group_sizes, collective_summary,
                                  hbm_bytes, quadratic_traffic)
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # jax <= 0.4.x: one dict per program
        ca = ca[0] if ca else {}
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    # Attention-score tensors are identified by their trailing (.., bq, Sk)
    # dims; logits / MLP-hidden / residual tensors are rank-2/3 lookalikes
    # after XLA flattening, so every model width (and its shard extents) is
    # excluded from the last-dim match.
    ex = set()
    if cfg is not None:
        for w in (cfg.vocab_size, cfg.d_ff, cfg.d_model,
                  getattr(cfg, "d_inner", 0)):
            for d in (1, 2, 4, 8, 16, 32):
                if w and w % d == 0:
                    ex.add(w // d)
    ex = frozenset(ex)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "hbm_bytes": float(hbm_bytes(text)),
        "attn_quad_bytes": float(quadratic_traffic(text, 2048, (-2, -1),
                                                   second_min=256,
                                                   exclude_last=ex)),
        "ssd_quad_bytes": float(quadratic_traffic(text, 256, (-3, -2),
                                                  rank_min=4)),
        "collectives": collective_summary(text),
        "collective_group_sizes": collective_group_sizes(text),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        },
    }


def _lower_compile(cfg, shape, mesh, **kw):
    from repro.launch.steps import lower_cell
    t0 = time.time()
    lowered, kind = lower_cell(cfg, shape, mesh, **kw)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    out = _analyse(compiled, cfg=cfg)
    out.update({"kind": kind, "lower_s": t1 - t0, "compile_s": t2 - t1})
    return out


def _calibrate(cfg, shape, mesh, *, microbatches, fsdp):
    """Unrolled 2/3-unit compiles -> exact per-step totals.

    k=1 is deliberately avoided: GSPMD picks a different partitioning for a
    single-layer graph (observed 2x per-device flops vs the per-layer cost
    in deeper graphs); from k>=2 the per-unit increments are exactly linear
    (verified: F(3)-F(2) == F(4)-F(3) to 5 digits)."""
    from repro.launch.shapes import SHAPES
    from repro.launch.steps import make_update_step, param_shardings

    spec = SHAPES[shape]
    L = _layer_units(cfg)
    K1, K2 = 2, 3
    pts = {}
    for k in (K1, K2):
        kw = dict(microbatches=1) if spec.kind == "train" else {}
        pts[k] = _lower_compile(_variant(cfg, k), shape, mesh,
                                fsdp=fsdp,
                                batch_div=(microbatches if spec.kind == "train"
                                           else 1), **kw)

    def extrap(get):
        f1, f2 = get(pts[K1]), get(pts[K2])
        return f1 + (L - K1) * (f2 - f1)

    out = {
        "flops": extrap(lambda p: p["flops"]),
        "bytes_accessed": extrap(lambda p: p["bytes_accessed"]),
        "hbm_bytes": extrap(lambda p: p["hbm_bytes"]),
        "attn_quad_bytes": extrap(lambda p: p["attn_quad_bytes"]),
        "ssd_quad_bytes": extrap(lambda p: p["ssd_quad_bytes"]),
        "collectives": {},
        "collective_group_sizes": pts[K2]["collective_group_sizes"],
        "layer_units": L,
        "points": {k: {kk: pts[k][kk] for kk in
                       ("flops", "bytes_accessed", "hbm_bytes",
                        "collectives")}
                   for k in (K1, K2)},
    }
    keys = set(pts[K1]["collectives"]) | set(pts[K2]["collectives"])
    for key in keys:
        out["collectives"][key] = extrap(
            lambda p, key=key: p["collectives"].get(key, 0.0))

    if spec.kind == "train" and microbatches > 1:
        # F_total = M * F_step - (M-1) * F_upd (fused update compiled once)
        upd = make_update_step(cfg)
        pshape, pshard = param_shardings(cfg, mesh, fsdp=fsdp)
        vshape = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), pshape)
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(mesh, P())
        with jax.set_mesh(mesh):
            c = jax.jit(upd, in_shardings=(pshard, pshard, pshard, repl)) \
                .lower(pshape, vshape, vshape,
                       jax.ShapeDtypeStruct((), jnp.int32)).compile()
        u = _analyse(c, cfg=cfg)
        out["update_epilogue"] = {k: u[k] for k in
                                  ("flops", "bytes_accessed", "hbm_bytes",
                                   "collectives")}
        M = microbatches
        for k in ("flops", "bytes_accessed", "hbm_bytes", "attn_quad_bytes",
                  "ssd_quad_bytes"):
            out[k] = M * out[k] - (M - 1) * u.get(k, 0.0)
        for key in list(out["collectives"]):
            out["collectives"][key] = (
                M * out["collectives"][key]
                - (M - 1) * u["collectives"].get(key, 0.0))
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str,
             calibrate: bool = True, overrides: dict | None = None,
             microbatches: int | None = None, fsdp: bool | None = None,
             suffix: str = "") -> dict:
    from repro.configs import get_config
    from repro.launch.flops import model_flops
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import (SHAPES, TRAIN_MICROBATCHES,
                                     production_config)

    cfg = get_config(arch)
    if overrides is None:
        cfg, applied = production_config(cfg, shape)
    else:
        applied = overrides
        cfg = dataclasses.replace(cfg, **overrides) if overrides else cfg
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    spec = SHAPES[shape]
    if spec.kind != "train":
        M = 1
    elif microbatches is not None:
        M = microbatches
    else:
        M = TRAIN_MICROBATCHES

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "config_overrides": applied,
        "mesh_shape": dict(zip(mesh.axis_names,
                               [mesh.shape[a] for a in mesh.axis_names])),
        "n_devices": mesh.size,
        "microbatches": M,
        "param_count": int(cfg.param_count()),
        "active_param_count": int(cfg.active_param_count()),
        "model_flops": model_flops(cfg, shape),
        "status": "ok",
    }
    try:
        rec["real"] = _lower_compile(cfg, shape, mesh, microbatches=M,
                                     fsdp=fsdp)
        if calibrate and mesh_kind == "single":
            rec["calibrated"] = _calibrate(cfg, shape, mesh,
                                           microbatches=M, fsdp=fsdp)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"{arch}__{shape}__{mesh_kind}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    from repro.configs import ALIASES, ARCHS
    from repro.launch.shapes import SHAPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ([a.replace("_", "-") if ALIASES.get(a) is None else a
              for a in ([args.arch] if args.arch else
                        [x.replace('_', '-') for x in ARCHS])])
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not (args.all or args.arch):
        ap.error("pass --arch or --all")

    results = []
    for mesh_kind in meshes:
        for arch, shape in _cells(archs, shapes):
            path = os.path.join(args.out, f"{arch}__{shape}__{mesh_kind}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {arch} {shape} {mesh_kind}")
                continue
            t0 = time.time()
            rec = run_cell(arch, shape, mesh_kind, args.out,
                           calibrate=not args.no_calibrate)
            dt = time.time() - t0
            mem = rec.get("real", {}).get("memory", {})
            print(f"[{rec['status']:5s}] {arch:22s} {shape:12s} {mesh_kind:6s}"
                  f" {dt:7.1f}s  temp/dev="
                  f"{mem.get('temp_bytes', 0) / 2**30:7.2f}GiB "
                  f"args/dev={mem.get('argument_bytes', 0) / 2**30:7.2f}GiB",
                  flush=True)
            if rec["status"] == "error":
                print(rec["error"], flush=True)
            results.append(rec)
    n_err = sum(r["status"] != "ok" for r in results)
    print(f"done: {len(results) - n_err}/{len(results)} cells ok")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
