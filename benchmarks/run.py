"""Benchmark suite runner — one entry per paper table/figure + the roofline
report. Prints ``name,status,seconds`` CSV summary lines (machine-parseable)
after each section's own output.

  table1  -> dataset statistics (paper Table 1)
  fig2    -> async-PS convergence vs worker count (paper Fig. 2)
  fig3    -> speedup factors (paper Fig. 3)
  fig4    -> metric quality: ours vs Xing2002/ITML/KISS/Euclidean (Fig. 4)
  roofline-> per (arch x shape x mesh) roofline terms from the dry-run
  retrieval_qps -> serving: fused metric top-k vs per-pair XLA reference
  retrieval_recall -> serving: IVF + IVF-PQ recall@10-vs-QPS frontiers
             vs the exact scan (PQ: uint8 residual codes, ADC tables,
             exact rerank)
  gallery_churn -> serving: QPS + recall@10 under sustained upsert/delete
             churn with periodic compaction (MutableIndex)
  serving_load -> serving: SLO attainment under a calibrated overload
             burst — adaptive degradation vs the non-degrading baseline
             (RequestScheduler; emits BENCH_serving.json)
  mining_convergence -> closed loop: mined+curriculum training matches
             uniform sampling's final kNN accuracy in <= 0.5x the steps
             at equal batch size (HardPairMiner -> MinedPairSource ->
             ClosedLoopTrainer over the serving index)
"""

from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    results = []

    def section(name, fn):
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            fn()
            results.append((name, "ok", time.time() - t0))
        except Exception as e:
            traceback.print_exc()
            results.append((name, f"FAIL:{type(e).__name__}",
                            time.time() - t0))

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (ablation_sync, fig2_convergence, fig3_speedup,
                            fig4_quality, gallery_churn,
                            mining_convergence, retrieval_qps,
                            retrieval_recall, roofline, serving_load,
                            table1_datasets)

    section("table1_datasets", table1_datasets.main)
    section("retrieval_qps", retrieval_qps.main)
    section("retrieval_recall", retrieval_recall.main)
    section("gallery_churn", gallery_churn.main)
    section("serving_load", serving_load.main)
    section("mining_convergence", mining_convergence.main)
    section("fig4_quality", fig4_quality.main)
    section("fig2_convergence", fig2_convergence.main)
    section("fig3_speedup", fig3_speedup.main)
    section("ablation_sync", ablation_sync.main)
    section("roofline", roofline.main)

    print("\nname,status,seconds")
    failed = False
    for name, status, secs in results:
        print(f"{name},{status},{secs:.1f}")
        failed |= status != "ok"
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
