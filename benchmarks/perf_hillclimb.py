import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""§Perf hillclimb driver: lowers candidate variants of the three selected
(arch x shape) pairs, re-derives the roofline terms, and appends
hypothesis -> change -> before -> after records to
benchmarks/artifacts/perf_hillclimb.json.

Pairs (see EXPERIMENTS.md §Roofline):
  A. zamba2-2.7b x train_4k      — worst memory term (SSD chunk tiles)
  B. dml-imnet63k (paper config) — collective-bound, paper-representative
  C. smollm-135m x prefill_32k   — worst useful-compute (head replication)
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch import hlo_analysis, mesh as mesh_lib  # noqa: E402
from repro.launch.dryrun import dryrun_one  # noqa: E402

ART = os.path.join(os.path.dirname(__file__), "artifacts")
LOG = os.path.join(ART, "perf_hillclimb.json")


def _load():
    if os.path.exists(LOG):
        with open(LOG) as f:
            return json.load(f)
    return {}


def _store(log):
    os.makedirs(ART, exist_ok=True)
    with open(LOG, "w") as f:
        json.dump(log, f, indent=1, sort_keys=True)


def _summ(rec):
    t = rec["roofline"]
    return {
        "compute_s": t["compute_s"], "memory_s": t["memory_s"],
        "collective_s": t["collective_s"], "dominant": t["dominant"],
        "temp_gib": rec["memory"]["temp_size"] / 2**30,
        "flops_per_chip": rec["flops_per_chip"],
        "hbm_bytes_per_chip": rec["hbm_bytes_per_chip"],
        "collective_bytes_per_chip": rec.get("collectives", {}).get(
            "total_bytes", 0.0),
    }


def run_variant(log, exp: str, name: str, hypothesis: str, arch: str,
                shape: str, overrides: dict, force=False):
    key = f"{exp}:{name}"
    if key in log and not force:
        print(f"[perf] {key}: cached")
        return log[key]
    print(f"[perf] {key}: lowering ({hypothesis[:60]}...)")
    t0 = time.time()
    rec = dryrun_one(arch, shape, multi_pod=False, overrides=overrides or None)
    entry = {"experiment": exp, "variant": name, "hypothesis": hypothesis,
             "overrides": overrides, "elapsed_s": round(time.time() - t0, 1),
             **_summ(rec)}
    log[key] = entry
    _store(log)
    print(f"[perf] {key}: mem={entry['memory_s']:.2f}s "
          f"comp={entry['compute_s']:.2f}s coll={entry['collective_s']:.2f}s "
          f"temp={entry['temp_gib']:.2f}GiB")
    return entry


# ---------------------------------------------------------------------------
# Experiment B: the paper's DML config under communication-efficient
# local-SGD (model-sharded L + per-tau parameter averaging over data).
# ---------------------------------------------------------------------------

def dml_tau_variant(log, tau: int, comm_dtype: str, force=False):
    key = f"B:dml63k_tau{tau}_{comm_dtype}"
    if key in log and not force:
        print(f"[perf] {key}: cached")
        return log[key]
    from repro.configs import dml_paper
    exp = dml_paper.IMNET_63K
    dcfg = exp.dml
    mesh = mesh_lib.make_production_mesh()
    n_data, n_model = mesh.shape["data"], mesh.shape["model"]
    k_loc = dcfg.proj_dim // n_model
    d = dcfg.feat_dim
    B = exp.batch_size            # per-worker pairs per local step
    cdt = jnp.dtype(comm_dtype)

    def dist_loss(L_loc, batch):
        """Eq. 4 with L sharded over 'model' (k/16 rows per rank): the
        squared distance needs one tiny psum of per-pair partials."""
        z = (batch["xs"] - batch["ys"]).astype(jnp.float32)
        proj = z @ L_loc.astype(jnp.float32).T
        d2 = jax.lax.psum(jnp.sum(jnp.square(proj), axis=-1), "model")
        simf = batch["sim"].astype(jnp.float32)
        hinge = jnp.maximum(0.0, dcfg.margin - d2)
        return jnp.mean(simf * d2 + (1 - simf) * dcfg.lam * hinge), {}

    def chunk_fn(L_loc, batches):
        def local_step(Lc, b):
            (loss, _), g = jax.value_and_grad(dist_loss, has_aux=True)(Lc, b)
            return Lc - 0.01 * g, loss

        L_new, losses = jax.lax.scan(local_step, L_loc, batches)
        # server merge once per tau steps, in comm_dtype
        L_new = jax.lax.pmean(L_new.astype(cdt), "data").astype(L_new.dtype)
        return L_new, jnp.mean(losses)

    L_spec = jax.ShapeDtypeStruct((k_loc, d), jnp.float32)
    batches_spec = {
        "xs": jax.ShapeDtypeStruct((tau, B, d), jnp.float32),
        "ys": jax.ShapeDtypeStruct((tau, B, d), jnp.float32),
        "sim": jax.ShapeDtypeStruct((tau, B), jnp.int32),
    }
    fn = jax.shard_map(chunk_fn, mesh=mesh,
                       in_specs=(P("model", None), P("data")),
                       out_specs=(P("model", None), P()),
                       check_vma=False)
    # global views for lowering: L (k, d), batches (data*tau, B, ...)
    L_g = jax.ShapeDtypeStruct((dcfg.proj_dim, d), jnp.float32)
    b_g = {
        "xs": jax.ShapeDtypeStruct((n_data * tau, B, d), jnp.float32),
        "ys": jax.ShapeDtypeStruct((n_data * tau, B, d), jnp.float32),
        "sim": jax.ShapeDtypeStruct((n_data * tau, B), jnp.int32),
    }
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn).lower(L_g, b_g)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    csum = hlo_analysis.collective_summary(compiled.as_text())
    mem = compiled.memory_analysis()
    n_chips = 256
    # per-STEP terms (divide the chunk program by tau)
    flops = max(float(cost.get("flops") or 0.0), csum["dot_flops"]) / tau
    obytes = max(float(cost.get("bytes accessed") or 0.0),
                 csum["op_bytes"]) / tau
    cbytes = csum["total_bytes"] / tau
    terms = hlo_analysis.roofline_terms(
        flops, obytes, cbytes, n_chips, mesh_lib.PEAK_FLOPS_BF16,
        mesh_lib.HBM_BW, mesh_lib.ICI_BW)
    entry = {
        "experiment": "B", "variant": f"tau{tau}_{comm_dtype}",
        "hypothesis": (f"local-SGD tau={tau} divides the parameter-average "
                       f"traffic by {tau}; {comm_dtype} comm halves bytes"),
        "per_step": True, "tau": tau, "comm_dtype": comm_dtype,
        "compute_s": terms["compute_s"], "memory_s": terms["memory_s"],
        "collective_s": terms["collective_s"], "dominant": terms["dominant"],
        "collective_bytes_per_chip": cbytes,
        "temp_gib": getattr(mem, "temp_size_in_bytes", 0) / 2**30,
        "elapsed_s": round(time.time() - t0, 1),
    }
    log[key] = entry
    _store(log)
    print(f"[perf] {key}: coll={terms['collective_s']*1e6:.1f}us/step "
          f"mem={terms['memory_s']*1e3:.2f}ms comp={terms['compute_s']*1e3:.2f}ms "
          f"dominant={terms['dominant']}")
    return entry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", type=str, default="all",
                    choices=["A", "B", "C", "D", "all"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    log = _load()

    if args.exp in ("A", "all"):
        run_variant(log, "A", "it1_chunk128",
                    "halving the SSD chunk halves live (B,Q,Q,H) tile bytes "
                    "(total tile traffic ~ T*Q per layer)",
                    "zamba2-2.7b", "train_4k", {"ssm_chunk": 128},
                    args.force)
        run_variant(log, "A", "it2_tile_bf16",
                    "bf16 decay/G tiles halve intra-chunk HBM traffic; "
                    "f32 accumulation keeps accuracy (validated vs ref)",
                    "zamba2-2.7b", "train_4k", {"ssm_tile_dtype": "bfloat16"},
                    args.force)
        run_variant(log, "A", "it3_chunk128_bf16",
                    "compose it1+it2",
                    "zamba2-2.7b", "train_4k",
                    {"ssm_chunk": 128, "ssm_tile_dtype": "bfloat16"},
                    args.force)
        run_variant(log, "A", "it5_allbf16_chunk128",
                    "end-to-end bf16 tile math (xs/B/C/decays/outputs, f32 "
                    "accumulation) removes the f32 converts that defeated "
                    "it2 and halves every chunk tensor",
                    "zamba2-2.7b", "train_4k",
                    {"ssm_chunk": 128, "ssm_tile_dtype": "bfloat16"},
                    True)
        run_variant(log, "A", "it6_einsum_order",
                    "explicit 2-operand contraction order stops XLA from "
                    "materializing a (B,Q,S,H,p) 5.4GB intermediate per "
                    "chunk einsum; plus group-level remat frees the 9 "
                    "shared-attn residual sets",
                    "zamba2-2.7b", "train_4k",
                    {"ssm_chunk": 128, "ssm_tile_dtype": "bfloat16"},
                    args.force or None is None and False)
        run_variant(log, "A", "it4_chunk64_bf16",
                    "chunk 64: tile bytes keep shrinking but state-passing "
                    "matmuls (T/Q chunks) grow — expect diminishing returns",
                    "zamba2-2.7b", "train_4k",
                    {"ssm_chunk": 64, "ssm_tile_dtype": "bfloat16"},
                    args.force)

    if args.exp in ("B", "all"):
        dml_tau_variant(log, 1, "float32", args.force)    # paper-PS baseline
        dml_tau_variant(log, 4, "float32", args.force)
        dml_tau_variant(log, 16, "float32", args.force)
        dml_tau_variant(log, 16, "bfloat16", args.force)
        dml_tau_variant(log, 64, "bfloat16", args.force)

    if args.exp in ("D", "all"):
        run_variant(log, "D", "qwen3_cap125",
                    "capacity factor 2.0->1.25 shrinks the (E_loc, C, d) "
                    "dispatch buffers ~37% to bring qwen3 train under HBM",
                    "qwen3-moe-30b-a3b", "train_4k",
                    {"moe_capacity_factor": 1.25}, args.force)

    if args.exp in ("C", "all"):
        # seq-parallel attention is auto-applied when heads % model != 0 —
        # this lowers the NEW code; the pre-change artifact is the baseline
        run_variant(log, "C", "it1_seq_parallel",
                    "9 heads don't divide model=16 so every rank repeats the "
                    "full 32k attention; sharding q chunks over 'model' "
                    "divides attention tiles and FLOPs by 16",
                    "smollm-135m", "prefill_32k", {}, args.force)
        run_variant(log, "C", "it2_seqpar_qchunk512",
                    "smaller q chunks shrink live tiles further (512x1024 "
                    "vs 1024x1024) at unchanged FLOPs",
                    "smollm-135m", "prefill_32k",
                    {"attn_q_chunk": 512}, args.force)


if __name__ == "__main__":
    main()
