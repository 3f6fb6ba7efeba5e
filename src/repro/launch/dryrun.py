# The 512 virtual devices MUST be requested before jax initializes —
# before any other import, including `from repro...` (spec requirement).
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver jits the real step function (train_step /
prefill / decode_step / detect_step) with production in/out shardings,
lowers it against ShapeDtypeStruct inputs (no allocation), compiles for the
512-virtual-device CPU platform, and records memory_analysis(),
cost_analysis() and the HLO collective schedule into a JSON artifact that
EXPERIMENTS.md §Dry-run/§Roofline reads.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch command-r-35b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import functools
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import dist
from repro.configs import ALL_ARCHS, LM_ARCHS, get_config, get_module
from repro.configs.shapes import LM_SHAPES, input_specs, shapes_for
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh
from repro.models import (ModelConfig, cache_sharding_rules, decode_step,
                          init_cache, param_sharding_rules, prefill)
from repro.models.config import ModelConfig as MC
from repro.train.loop import TrainState, init_train_state, make_train_step
from repro.train.optimizer import (OptimizerConfig, init_opt_state,
                                   opt_state_sharding_rules)


# ---------------------------------------------------------------------------
# sharding-tree construction
# ---------------------------------------------------------------------------


def _rules_to_shardings(rules, shapes_tree, mesh):
    """Nested dict of rule-tuples + matching ShapeDtypeStruct tree →
    NamedSharding tree (divisibility-sanitized).

    jit argument shardings MUST be evenly divisible (unlike constraints),
    so uneven-sharding mode is suspended here.
    """
    from repro.dist import _UNEVEN

    def walk(rule, shp):
        if isinstance(rule, tuple):
            tok = _UNEVEN.set(False)
            try:
                with jax.set_mesh(mesh):
                    spec = dist.sanitize_spec(shp.shape, rule)
            finally:
                _UNEVEN.reset(tok)
            return NamedSharding(mesh, spec if spec is not None else P())
        return {k: walk(rule[k], shp[k]) for k in rule}

    return walk(rules, shapes_tree)


def _batch_shardings(batch_specs, mesh):
    names = (("pod", "data", "model")
             if dist.current_layout() == "fsdp" else ("pod", "data"))
    ba = tuple(a for a in names if a in mesh.shape)

    def one(sds):
        spec = (ba,) + (None,) * (len(sds.shape) - 1)
        with jax.set_mesh(mesh):
            s = dist.sanitize_spec(sds.shape, spec)
        return NamedSharding(mesh, s if s is not None else P())

    return jax.tree.map(one, batch_specs)


def pick_microbatches(cfg: ModelConfig, global_batch: int, dp: int) -> int:
    """1 sequence per device per microbatch for ≥4B-param models."""
    local = global_batch // dp
    if cfg.param_count() >= 4e9:
        return local
    if cfg.param_count() >= 1e9:
        return max(1, local // 4)
    return max(1, local // 8)


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------


def lower_lm_cell(arch: str, shape_name: str, mesh, attn_impl: str,
                  microbatches: int | None = None,
                  accum_mode: str = "scan_grads",
                  shard_grads: bool = False,
                  cfg_overrides: dict | None = None):
    cfg = get_config(arch)
    if cfg_overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **cfg_overrides)
    spec = LM_SHAPES[shape_name]
    dp_names = (("pod", "data", "model")
                if dist.current_layout() == "fsdp" else ("pod", "data"))
    dp = 1
    for a in dp_names:
        if a in mesh.shape:
            dp *= mesh.shape[a]

    p_rules = param_sharding_rules(cfg)
    with jax.set_mesh(mesh):
        if spec.kind == "train":
            n_mb = microbatches or pick_microbatches(cfg, spec.global_batch,
                                                     dp)
            opt_cfg = OptimizerConfig()
            state_shape = jax.eval_shape(
                functools.partial(init_train_state, jax.random.PRNGKey(0),
                                  cfg))
            o_rules = opt_state_sharding_rules(
                p_rules, jax.tree.map(lambda s: s.shape, state_shape.params,
                                      is_leaf=lambda x: hasattr(x, "shape")))
            state_sh = TrainState(
                params=_rules_to_shardings(p_rules, state_shape.params, mesh),
                opt={
                    "master": _rules_to_shardings(
                        o_rules["master"], state_shape.opt["master"], mesh),
                    "m": _rules_to_shardings(o_rules["m"],
                                             state_shape.opt["m"], mesh),
                    "v": _rules_to_shardings(o_rules["v"],
                                             state_shape.opt["v"], mesh),
                    "step": NamedSharding(mesh, P()),
                },
                step=NamedSharding(mesh, P()))
            batch = input_specs(cfg, shape_name)
            batch_sh = _batch_shardings(batch, mesh)
            step = make_train_step(cfg, opt_cfg, n_microbatches=n_mb,
                                   attn_impl=attn_impl,
                                   accum_mode=accum_mode,
                                   shard_grads_like_opt=shard_grads)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_shape, batch)
            extra = {"microbatches": n_mb}
        elif spec.kind == "prefill":
            params_shape = _param_struct(cfg)
            params_sh = _rules_to_shardings(p_rules, params_shape, mesh)
            batch = input_specs(cfg, shape_name)
            batch_sh = _batch_shardings(batch, mesh)
            fn = functools.partial(prefill, cfg=cfg, impl=attn_impl)
            jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(params_shape, batch)
            extra = {}
        else:  # decode
            params_shape = _param_struct(cfg)
            params_sh = _rules_to_shardings(p_rules, params_shape, mesh)
            specs = input_specs(cfg, shape_name)
            cache_shape = specs["cache"]
            c_rules = cache_sharding_rules(cfg)
            cache_sh = _rules_to_shardings(c_rules, cache_shape, mesh)
            tok_sh = _batch_shardings({"tokens": specs["tokens"]},
                                      mesh)["tokens"]
            fn = functools.partial(decode_step, cfg=cfg)
            jitted = jax.jit(lambda p, c, t: fn(p, c, t),
                             in_shardings=(params_sh, cache_sh, tok_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_shape, cache_shape,
                                   specs["tokens"])
            extra = {}
    return lowered, cfg, spec, extra


def _param_struct(cfg: ModelConfig):
    from repro.models import init_params
    return jax.eval_shape(
        functools.partial(init_params, jax.random.PRNGKey(0), cfg))


def lower_detect_cell(shape_name: str, mesh, use_shard_map: bool = True,
                      occ_limit: int = 0):
    """Lower the fixed-shape detection cell (now a wrapper over the shared
    streaming core) with production shardings. The per-chunk in-trace
    index is sized like the paper-scale streaming config; ``occ_limit``
    > 0 lowers the cell with the in-dispatch §6.5 occurrence limiter on,
    so its cost shows up in the dry-run HLO/memory stats before anyone
    pays for a TPU."""
    from repro.configs import fast_seismic as fs
    from repro.core.detect import detect_step, detect_step_sharded
    from repro.stream.index import StreamIndexConfig
    dcfg = fs.config()
    specs = fs.input_specs(shape_name)
    n_chunk_fp = dcfg.fingerprint.n_fingerprints(
        specs["waveforms"].shape[1])
    icfg = StreamIndexConfig(
        n_buckets=16384, bucket_cap=dcfg.lsh.bucket_cap,
        occ_slots=n_chunk_fp if occ_limit > 0 else 0)
    knobs = dict(icfg=icfg, occ_limit=occ_limit)
    all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    wf_sh = NamedSharding(mesh, P(all_axes, None))
    stat_sh = NamedSharding(mesh, P())
    if use_shard_map:
        step = functools.partial(detect_step_sharded, cfg=dcfg, mesh=mesh,
                                 **knobs)
    else:  # SPMD-partitioner baseline (kept for §Perf comparison)
        step = jax.vmap(functools.partial(detect_step, cfg=dcfg, **knobs),
                        in_axes=(0, None, None))
    with jax.set_mesh(mesh):
        jitted = jax.jit(step, in_shardings=(wf_sh, stat_sh, stat_sh))
        lowered = jitted.lower(specs["waveforms"], specs["med"],
                               specs["mad"])
    return lowered, dcfg


# ---------------------------------------------------------------------------
# model-flops accounting (MFU numerator)
# ---------------------------------------------------------------------------


def model_flops(cfg, spec_kind: str, global_batch: int, seq: int) -> float:
    if not isinstance(cfg, MC):
        return 0.0
    n_active = cfg.active_param_count()
    tokens = global_batch * (seq if spec_kind in ("train", "prefill") else 1)
    mult = 6.0 if spec_kind == "train" else 2.0
    return mult * n_active * tokens


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             attn_impl: str = "masked", save_hlo: bool = False,
             microbatches: int | None = None, tag: str = "",
             accum_mode: str = "scan_grads", shard_grads: bool = False,
             cfg_overrides: dict | None = None,
             uneven: bool = False, layout: str = "tp") -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = mesh.size
    pod_boundary = (n_dev // mesh.shape["pod"]) if multi else None
    t0 = time.perf_counter()
    import contextlib
    uneven_ctx = (dist.allow_uneven_sharding() if uneven
                  else contextlib.nullcontext())
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                    "devices": n_dev, "attn_impl": attn_impl, "tag": tag,
                    "accum_mode": accum_mode, "shard_grads": shard_grads,
                    "uneven": uneven,
                    "cfg_overrides": cfg_overrides or {}}
    if uneven:
        from repro.dist import _UNEVEN
        _uneven_tok = _UNEVEN.set(True)
    else:
        _uneven_tok = None
    from repro.dist import _LAYOUT
    _layout_tok = _LAYOUT.set(layout)
    record["layout"] = layout
    try:
        if arch == "fast_seismic":
            lowered, dcfg = lower_detect_cell(
                shape_name, mesh,
                use_shard_map=(cfg_overrides or {}).get("shard_map", 1) == 1)
            from repro.configs import fast_seismic as fs
            mf = fs.model_flops(shape_name)
            record["kind"] = "detect"
        else:
            lowered, cfg, spec, extra = lower_lm_cell(
                arch, shape_name, mesh, attn_impl, microbatches,
                accum_mode=accum_mode, shard_grads=shard_grads,
                cfg_overrides=cfg_overrides)
            mf = model_flops(cfg, spec.kind, spec.global_batch, spec.seq_len)
            record["kind"] = spec.kind
            record.update(extra)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        record["lower_s"] = round(t1 - t0, 2)
        record["compile_s"] = round(t2 - t1, 2)
        record["memory"] = hlo_stats.extract_memory(compiled)
        record["xla_cost_raw"] = hlo_stats.extract_cost(compiled)
        hlo = compiled.as_text()
        stats = hlo_stats.analyze_hlo(hlo, pod_boundary=pod_boundary)
        record["collectives"] = {
            "counts": stats.coll_counts,
            "bytes_by_kind": stats.coll_bytes,
            "link_bytes_ici": stats.link_bytes_ici,
            "link_bytes_dcn": stats.link_bytes_dcn,
        }
        record["roofline"] = hlo_stats.roofline_terms(stats, n_dev, mf)
        record["status"] = "ok"
        if save_hlo:
            import gzip
            hp = pathlib.Path(out_dir) / f"{_cell_name(record)}.hlo.gz"
            hp.parent.mkdir(parents=True, exist_ok=True)
            with gzip.open(hp, "wt") as f:
                f.write(hlo)
        # The two artifacts the spec asks to print:
        print(f"--- {arch} × {shape_name} × {mesh_kind} ---")
        print("memory_analysis:", json.dumps(record["memory"]))
        print("cost_analysis(raw):", json.dumps(record["xla_cost_raw"]))
        print("collectives:", json.dumps(record["collectives"]["counts"]))
        rf = record["roofline"]
        print(f"roofline: compute={rf['compute_s']:.4f}s "
              f"memory={rf['memory_s']:.4f}s "
              f"collective={rf['collective_s']:.4f}s "
              f"dominant={rf['dominant']} "
              f"useful_ratio={rf['useful_flops_ratio']:.3f}")
    except Exception as e:
        record["status"] = "fail"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"--- {arch} × {shape_name} × {mesh_kind} FAILED: "
              f"{record['error']}")
    if _uneven_tok is not None:
        from repro.dist import _UNEVEN
        _UNEVEN.reset(_uneven_tok)
    _LAYOUT.reset(_layout_tok)
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{_cell_name(record)}.json").write_text(
        json.dumps(record, indent=1, default=str))
    return record


def _cell_name(record: dict) -> str:
    tag = f"__{record['tag']}" if record.get("tag") else ""
    return (f"{record['arch']}__{record['shape']}__{record['mesh']}"
            f"{tag}".replace("/", "_").replace(".", "p"))


def iter_cells(archs, shapes_arg, meshes):
    for arch in archs:
        if arch == "fast_seismic":
            from repro.configs import fast_seismic as fs
            names = list(fs.SHAPES) if shapes_arg == ["all"] else shapes_arg
        else:
            cfg = get_config(arch)
            names = shapes_for(cfg) if shapes_arg == ["all"] else shapes_arg
        for shp in names:
            for mk in meshes:
                yield arch, shp, mk


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, comma list, or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--attn-impl", default="masked",
                    choices=["masked", "triangular"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip cells whose ok-status JSON already exists")
    ap.add_argument("--accum-mode", default="scan_grads",
                    choices=["scan_grads", "grad_of_scan"])
    ap.add_argument("--shard-grads", action="store_true")
    ap.add_argument("--cfg-override", default="",
                    help="comma k=v model-config overrides (ints/floats/str)")
    ap.add_argument("--uneven-sharding", action="store_true",
                    help="allow non-divisible dims to shard (XLA pads)")
    ap.add_argument("--layout", default="tp", choices=["tp", "fsdp"])
    args = ap.parse_args()

    archs = ALL_ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = ["all"] if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    cells = list(iter_cells(archs, shapes, meshes))
    if args.list:
        for c in cells:
            print(*c)
        return

    failures = 0
    for arch, shp, mk in cells:
        if args.skip_existing:
            name = _cell_name({"arch": arch, "shape": shp, "mesh": mk,
                               "tag": args.tag})
            p = pathlib.Path(args.out) / f"{name}.json"
            if p.exists() and json.loads(p.read_text()).get("status") \
                    == "ok":
                print(f"skip {arch} × {shp} × {mk} (exists)")
                continue
        overrides = {}
        for kv in args.cfg_override.split(","):
            if not kv:
                continue
            k, v = kv.split("=")
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
            overrides[k] = v
        rec = run_cell(arch, shp, mk, args.out, attn_impl=args.attn_impl,
                       save_hlo=args.save_hlo,
                       microbatches=args.microbatches, tag=args.tag,
                       accum_mode=args.accum_mode,
                       shard_grads=args.shard_grads,
                       cfg_overrides=overrides or None,
                       uneven=args.uneven_sharding, layout=args.layout)
        failures += rec["status"] != "ok"
    print(f"\n{len(cells) - failures}/{len(cells)} cells OK")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
