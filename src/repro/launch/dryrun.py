import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without real hardware: sharding
mismatches, compile-time OOM and unsupported collectives all fail here.
Records memory_analysis / cost_analysis / per-collective byte counts for the
roofline (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
    python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k [--multipod]
    python -m repro.launch.dryrun --all --out dryrun_results.json
"""

import argparse
import json
import re
import sys
import time
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models import get_config, build_model
from repro.models.config import ArchConfig
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train.step import make_train_step
from repro.serve.step import make_prefill_step, make_decode_step
from repro.parallel import sharding as SH
from repro.parallel.api import logical_rules
from repro.launch.mesh import make_production_mesh
from repro.launch import shapes as SHP

from repro.launch.hlo_analysis import analyze as analyze_hlo


def build_sig_cell(shape, multi_pod: bool):
    """Dry-run cells for the paper's own workload: pod-scale sig-kernel Gram
    (forward) and exact-gradient MMD (train).  Rows shard over data, columns
    over model — the Gram tiling from DESIGN.md §6."""
    import functools
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    from repro.core.gram import sigkernel_gram
    from repro.configs.sigkernel_workload import GRAM_ENGINE_DEFAULTS

    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    L, B = shape.seq, shape.batch
    d = 8

    if shape.kind == "sig_fwd":
        # forward Gram, embarrassingly parallel: local blocked solves only
        def gram(X, Y):
            def local(Xl, Yl):
                return sigkernel_gram(Xl, Yl, **GRAM_ENGINE_DEFAULTS)
            fn = shard_map(local, mesh=mesh,
                           in_specs=(P(data_axes), P("model")),
                           out_specs=P(data_axes, "model"), check_rep=False)
            return fn(X, Y)

        X = jax.ShapeDtypeStruct((B, L, d), jnp.float32)
        Y = jax.ShapeDtypeStruct((B, L, d), jnp.float32)
        jitted = jax.jit(gram,
                         in_shardings=(NamedSharding(mesh, P(data_axes)),
                                       NamedSharding(mesh, P("model"))),
                         out_shardings=NamedSharding(mesh, P(data_axes, "model")))
        args = (X, Y)
    else:
        # differentiated MMD via the exact one-pass backward (paper §3.4)
        def mmd_grad(X, Y):
            def loss(X):
                K = sigkernel_gram(X, Y, backend="reference")
                return K.mean()
            return jax.value_and_grad(loss)(X)

        X = jax.ShapeDtypeStruct((B, L, d), jnp.float32)
        Y = jax.ShapeDtypeStruct((B, L, d), jnp.float32)
        jitted = jax.jit(mmd_grad,
                         in_shardings=(NamedSharding(mesh, P(data_axes)),
                                       NamedSharding(mesh, P("model"))),
                         out_shardings=(NamedSharding(mesh, P()),
                                        NamedSharding(mesh, P(data_axes))))
        args = (X, Y)
    return mesh, jitted, args, {}


def build_cell(arch: str, shape_name: str, multi_pod: bool):
    cfg = get_config(arch)
    shape = SHP.SHAPES.get(shape_name) or SHP.SIG_SHAPES[shape_name]
    skip = SHP.cell_supported(cfg, shape)
    if skip:
        return None, skip
    if cfg.family == "sigkernel":
        mesh, jitted, args, meta = build_sig_cell(shape, multi_pod)
        rules = SH.rules_for(None, multi_pod)
        return _make_runner(arch, shape_name, multi_pod, mesh, rules, jitted,
                            args, meta), None
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = SH.rules_for(cfg, multi_pod)
    model = build_model(cfg)
    key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shape = jax.eval_shape(model.init, key_struct)
    if shape.kind == "train":
        from repro.train.step import apply_param_dtype
        params_shape = apply_param_dtype(params_shape, cfg)
    p_shard = SH.param_shardings(params_shape, cfg, mesh, multi_pod)

    if shape.kind == "train":
        opt = AdamW(lr=cosine_schedule(3e-4, 100, 10000),
                    moment_dtype=cfg.moment_dtype)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        o_shard = SH.param_shardings(opt_shape, cfg, mesh, multi_pod)
        batch_spec = SHP.train_input_specs(cfg, shape)
        b_shard = SH.batch_shardings(batch_spec, cfg, mesh, multi_pod)
        bsz = shape.batch
        # batch shard size for the microbatch policy
        bspec = SH.physical_spec(("batch",), (bsz,), mesh, rules)
        import math as _math
        ax = bspec[0]
        axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
        bshard = _math.prod(mesh.shape[a] for a in axes) if axes else 1
        n_mb = SHP.microbatch_policy(cfg, bsz, bshard)
        p_pspecs = jax.tree.map(lambda s: s.spec, p_shard)
        # bf16-master models also accumulate gradients in bf16 (§Perf)
        accum = "bfloat16" if cfg.param_dtype == "bfloat16" else "float32"
        step = make_train_step(model, opt, num_microbatches=n_mb,
                               param_pspecs=p_pspecs, accum_dtype=accum)
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
        args = (params_shape, opt_shape, batch_spec)
        meta = {"num_microbatches": n_mb, "batch_shard": bshard}
    elif shape.kind == "prefill":
        step = make_prefill_step(model)
        batch_spec = SHP.prefill_input_specs(cfg, shape)
        b_shard = SH.batch_shardings(batch_spec, cfg, mesh, multi_pod)
        cache_shape = jax.eval_shape(lambda p, b: step(p, b)[1],
                                     params_shape, batch_spec)
        c_shard = SH.cache_shardings(cache_shape, cfg, mesh, multi_pod)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                         out_shardings=(None, c_shard))
        args = (params_shape, batch_spec)
        meta = {}
    else:  # decode
        step = make_decode_step(model)
        cache_shape = SHP.cache_shape_for(model, cfg, shape)
        c_shard = SH.cache_shardings(cache_shape, cfg, mesh, multi_pod)
        spec = SHP.decode_input_specs(cfg, shape, cache_shape)
        tok_shard = SH.batch_shardings({"tokens": spec["tokens"]},
                                       cfg, mesh, multi_pod)["tokens"]
        jitted = jax.jit(step, in_shardings=(p_shard, c_shard, tok_shard,
                                             SH.replicated(mesh)),
                         out_shardings=(tok_shard, None, c_shard),
                         donate_argnums=(1,))
        args = (params_shape, cache_shape, spec["tokens"], spec["cur_len"])
        meta = {}

    return _make_runner(arch, shape_name, multi_pod, mesh, rules, jitted,
                        args, meta), None


def _make_runner(arch, shape_name, multi_pod, mesh, rules, jitted, args, meta):
    def run():
        t0 = time.time()
        with mesh:
            with logical_rules(rules):
                lowered = jitted.lower(*args)
                compiled = lowered.compile()
        t1 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):  # older jax returns [dict] per device
            cost = cost[0] if cost else {}
        hlo = analyze_hlo(compiled.as_text())
        coll = hlo.collective
        n_chips = 512 if multi_pod else 256
        result = {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "compile_s": round(t1 - t0, 1),
            "flops": float(cost.get("flops", -1)),
            "hlo_dot_flops": float(hlo.flops),
            "hlo_bytes": float(cost.get("bytes accessed", -1)),
            "peak_bytes_per_device": int(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
            "collectives": coll,
            "n_chips": n_chips,
            **meta,
        }
        return result

    return run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from repro.configs import ASSIGNED
    cells = []
    if args.all:
        for arch in ASSIGNED:
            for shape in SHP.SHAPES:
                for mp in (False, True):
                    cells.append((arch, shape, mp))
        for shape in SHP.SIG_SHAPES:           # the paper's own workload
            for mp in (False, True):
                cells.append(("sigkernel-workload", shape, mp))
    else:
        cells.append((args.arch, args.shape, args.multipod))

    results = []
    if args.out and os.path.exists(args.out):  # resume partial sweeps
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    def flush():
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    for arch, shape, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        if (arch, shape, mesh_name) in done:
            continue
        tag = f"{arch} x {shape} x {mesh_name}"
        try:
            run, skip = build_cell(arch, shape, mp)
            if skip:
                print(f"SKIP {tag}: {skip}", flush=True)
                results.append({"arch": arch, "shape": shape,
                                "mesh": mesh_name, "skipped": skip})
                flush()
                continue
            print(f"RUN  {tag} ...", flush=True)
            res = run()
            gb = 1 << 30
            print(f"  ok in {res['compile_s']}s  dot_flops={res['hlo_dot_flops']:.3e}  "
                  f"peak/device={res['peak_bytes_per_device']/gb:.2f}GiB  "
                  f"coll={sum(c['traffic'] for c in res['collectives'].values())/gb:.3f}GiB",
                  flush=True)
            results.append(res)
        except Exception as e:  # record failures, keep sweeping
            import traceback
            print(f"FAIL {tag}: {e}", flush=True)
            traceback.print_exc()
            results.append({"arch": arch, "shape": shape, "mesh": mesh_name,
                            "error": str(e)[:2000]})
        flush()

    if args.out:
        print(f"wrote {args.out}")
    return results


if __name__ == "__main__":
    main()
