import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
on the production meshes, with zero real allocation (ShapeDtypeStruct inputs).

For each combination this script:
  1. builds the model + step function (train / prefill / serve per shape),
  2. jit-lowers with explicit in/out shardings on the requested mesh,
  3. compiles, records memory_analysis() (proves fit) and cost_analysis()
     (FLOPs / bytes for the roofline),
  4. parses the optimized HLO for collective traffic,
  5. appends the record to an incremental JSON artifact
     (benchmarks/artifacts/dryrun_<mesh>.json).

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
  python -m repro.launch.dryrun --dml            # the paper's own configs
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from functools import partial  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config, list_configs, get_shape, SHAPES  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch import hlo_analysis, mesh as mesh_lib, steps  # noqa: E402
from repro.models.transformer import build_model  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts")


def _artifact_path(multi_pod: bool) -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    name = "dryrun_pod2x16x16.json" if multi_pod else "dryrun_16x16.json"
    return os.path.join(ARTIFACT_DIR, name)


def _load(path):
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def _store(path, records):
    with open(path, "w") as f:
        json.dump(records, f, indent=1, sort_keys=True)


def _cost_number(cost, key):
    try:
        v = cost.get(key)
        return float(v) if v is not None else 0.0
    except Exception:
        return 0.0


def dryrun_one(arch: str, shape_name: str, multi_pod: bool,
               collect_hlo: bool = True, loss_chunks: int = 8,
               overrides: dict = None):
    """Lower+compile one combination; returns the result record.

    ``overrides``: ArchConfig.replace(**overrides) knobs — used by the §Perf
    hillclimb to lower candidate variants (chunk sizes, tile dtypes, ...).
    """
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    shape = get_shape(shape_name)
    base_cfg = get_config(arch)
    skip = steps.skip_reason(base_cfg, shape)
    if skip:
        return {"status": "skipped", "reason": skip, "arch": arch,
                "shape": shape_name, "mesh": str(dict(mesh.shape))}
    cfg = steps.effective_config(base_cfg, shape)
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)
    run = RunConfig(arch=arch, shape=shape_name)

    t0 = time.time()
    rng = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(model.init, rng)
    pshard = steps.param_shardings(model, params_shape, mesh)
    specs = steps.input_specs(cfg, shape)
    in_shard = steps.input_shardings(specs, mesh)

    with mesh:
        if shape.mode == "train":
            opt = steps.make_optimizer(run)
            state_shape = jax.eval_shape(
                lambda p: steps.TrainState(p, opt.init(p),
                                           jnp.zeros((), jnp.int32)),
                params_shape)
            sshard = steps.make_state_shardings(state_shape, params_shape,
                                                pshard, mesh)
            step_fn = steps.make_train_step(model, opt, run, mesh=mesh,
                                            loss_chunks=loss_chunks)
            jitted = jax.jit(step_fn,
                             in_shardings=(sshard, in_shard),
                             out_shardings=(sshard, None))
            lowered = jitted.lower(state_shape, specs)
        elif shape.mode == "prefill":
            step_fn = steps.make_prefill_step(model, run, mesh=mesh)
            jitted = jax.jit(step_fn, in_shardings=(pshard, in_shard),
                             out_shardings=None)
            lowered = jitted.lower(params_shape, specs)
        else:  # decode
            cache_shape = steps.cache_shape_structs(model, shape)
            cshard = steps.cache_shardings(model, cfg, shape, mesh)
            step_fn = steps.make_serve_step(model, run, mesh=mesh)
            jitted = jax.jit(step_fn,
                             in_shardings=(pshard, cshard, in_shard),
                             out_shardings=(None, cshard))
            lowered = jitted.lower(params_shape, cache_shape, specs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    record = {
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "mode": shape.mode,
        "mesh": dict(mesh.shape),
        "n_chips": n_chips,
        "attn_variant": cfg.attention,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # raw cost_analysis (NOTE: while bodies counted once — see
        # hlo_analysis; the loop-corrected parse below is authoritative)
        "cost_analysis_flops": _cost_number(cost, "flops"),
        "cost_analysis_bytes": _cost_number(cost, "bytes accessed"),
        "memory": {
            "argument_size": getattr(mem, "argument_size_in_bytes", 0),
            "output_size": getattr(mem, "output_size_in_bytes", 0),
            "temp_size": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_size": getattr(mem, "generated_code_size_in_bytes", 0),
        },
    }
    flops_per_chip = record["cost_analysis_flops"]
    bytes_per_chip = record["cost_analysis_bytes"]
    if collect_hlo:
        try:
            hlo = compiled.as_text()
            csum = hlo_analysis.collective_summary(hlo)
            record["collectives"] = {
                "bytes": csum["bytes"], "counts": csum["counts"],
                "total_bytes": csum["total_bytes"],
            }
            # loop-corrected per-chip FLOPs / HBM bytes from the HLO parse
            record["hlo_dot_flops_per_chip"] = csum["dot_flops"]
            record["hlo_op_bytes_per_chip"] = csum["op_bytes"]
            flops_per_chip = max(flops_per_chip, csum["dot_flops"])
            bytes_per_chip = max(bytes_per_chip, csum["op_bytes"])
        except Exception as e:  # pragma: no cover
            record["collectives"] = {"error": str(e)}
    record["flops_per_chip"] = flops_per_chip
    record["hbm_bytes_per_chip"] = bytes_per_chip
    # the SPMD module is per-partition, so parsed collective bytes are
    # already per-chip traffic — no further division by n_chips
    terms = hlo_analysis.roofline_terms(
        flops_per_chip, bytes_per_chip,
        record.get("collectives", {}).get("total_bytes", 0.0),
        n_chips, mesh_lib.PEAK_FLOPS_BF16, mesh_lib.HBM_BW, mesh_lib.ICI_BW)
    record["roofline"] = terms
    return record


def dryrun_dml(multi_pod: bool):
    """Dry-run the paper's own DML configs (train step over pair batches)."""
    from repro.configs import dml_paper
    from repro.core import dml as dml_core, losses as losses_mod
    from repro.optim import sgd
    from repro.sharding.partition import logical_to_physical

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    out = {}
    for name, exp in dml_paper.EXPERIMENTS.items():
        t0 = time.time()
        dcfg = exp.dml
        L_shape = jax.ShapeDtypeStruct((dcfg.proj_dim, dcfg.feat_dim),
                                       jnp.float32)
        # pairs per global step: paper minibatch per worker x data-parallel
        B = exp.batch_size * mesh.shape["data"] * mesh.shape.get("pod", 1)
        batch = {
            "xs": jax.ShapeDtypeStruct((B, dcfg.feat_dim), jnp.float32),
            "ys": jax.ShapeDtypeStruct((B, dcfg.feat_dim), jnp.float32),
            "sim": jax.ShapeDtypeStruct((B,), jnp.int32),
        }
        Lsh = NamedSharding(mesh, logical_to_physical(
            ("proj", "feat"), mesh, shape=(dcfg.proj_dim, dcfg.feat_dim)))
        bsh = {
            "xs": NamedSharding(mesh, logical_to_physical(
                ("pairs", None), mesh, shape=(B, dcfg.feat_dim))),
            "ys": NamedSharding(mesh, logical_to_physical(
                ("pairs", None), mesh, shape=(B, dcfg.feat_dim))),
            "sim": NamedSharding(mesh, logical_to_physical(
                ("pairs",), mesh, shape=(B,))),
        }

        def train_step(L, b):
            (loss, aux), g = jax.value_and_grad(
                lambda p, bb: losses_mod.dml_pair_loss(
                    p, bb, lam=dcfg.lam, margin=dcfg.margin),
                has_aux=True)(L, b)
            return L - 0.01 * g, loss

        with mesh:
            jitted = jax.jit(train_step, in_shardings=(Lsh, bsh),
                             out_shardings=(Lsh, None))
            lowered = jitted.lower(L_shape, batch)
            compiled = lowered.compile()
        cost = compiled.cost_analysis()
        csum = hlo_analysis.collective_summary(compiled.as_text())
        mem = compiled.memory_analysis()
        terms = hlo_analysis.roofline_terms(
            max(_cost_number(cost, "flops"), csum["dot_flops"]),
            max(_cost_number(cost, "bytes accessed"), csum["op_bytes"]),
            csum["total_bytes"],
            n_chips, mesh_lib.PEAK_FLOPS_BF16, mesh_lib.HBM_BW,
            mesh_lib.ICI_BW)
        out[name] = {
            "status": "ok", "arch": name, "shape": "paper_batch",
            "mesh": dict(mesh.shape), "n_chips": n_chips,
            "global_pair_batch": B,
            "compile_s": round(time.time() - t0, 1),
            "flops_per_chip": _cost_number(cost, "flops"),
            "hbm_bytes_per_chip": _cost_number(cost, "bytes accessed"),
            "collectives": {"bytes": csum["bytes"],
                            "total_bytes": csum["total_bytes"]},
            "memory": {"temp_size": getattr(mem, "temp_size_in_bytes", 0),
                       "argument_size": getattr(mem, "argument_size_in_bytes", 0)},
            "roofline": terms,
        }
        print(f"[dml dryrun] {name}: ok compile={out[name]['compile_s']}s "
              f"dominant={terms['dominant']}", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dml", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    path = _artifact_path(args.multi_pod)
    records = _load(path)

    if args.dml:
        dml_records = dryrun_dml(args.multi_pod)
        for k, v in dml_records.items():
            records[f"{k}|paper_batch"] = v
        _store(path, records)
        return

    combos = []
    if args.all:
        for arch in list_configs():
            for shape in SHAPES:
                combos.append((arch, shape))
    else:
        combos.append((args.arch, args.shape))

    for arch, shape in combos:
        key = f"{arch}|{shape}"
        if args.skip_done and records.get(key, {}).get("status") in ("ok", "skipped"):
            print(f"[dryrun] {key}: cached, skipping", flush=True)
            continue
        print(f"[dryrun] {key}: lowering...", flush=True)
        try:
            rec = dryrun_one(arch, shape, args.multi_pod,
                             collect_hlo=not args.no_hlo)
        except Exception as e:
            rec = {"status": "error", "arch": arch, "shape": shape,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-3000:]}
        records[key] = rec
        _store(path, records)
        if rec["status"] == "ok":
            t = rec["roofline"]
            print(f"[dryrun] {key}: OK compile={rec['compile_s']}s "
                  f"temp={rec['memory']['temp_size']/2**30:.2f}GiB "
                  f"compute={t['compute_s']*1e3:.2f}ms "
                  f"memory={t['memory_s']*1e3:.2f}ms "
                  f"coll={t['collective_s']*1e3:.2f}ms "
                  f"dominant={t['dominant']}", flush=True)
        elif rec["status"] == "skipped":
            print(f"[dryrun] {key}: SKIPPED ({rec['reason']})", flush=True)
        else:
            print(f"[dryrun] {key}: ERROR {rec['error']}", flush=True)


if __name__ == "__main__":
    main()
