"""Retrieval serving benchmark: fused metric_topk path vs pure-XLA reference.

Two things, on one default shape (gallery M=16384 x d=128, proj k=64,
query batches of 64, top-10):

  1. **Correctness** — the fused Pallas kernel (kernels/metric_topk,
     interpret mode off-TPU) must match the XLA reference exactly on
     indices and to 1e-4 rtol on distances.
  2. **Throughput** — QPS/latency of the production serving path (gallery
     pre-projected once at index build; factored distances; jitted XLA —
     the Pallas kernel itself is correctness-checked in interpret mode
     and only meaningfully timeable on TPU) vs the pure-XLA per-pair
     reference (metric_topk_naive: apply L to every query-gallery
     difference — the textbook formulation the index amortizes away).
     The serving path must win.

Prints ``retrieval,<name>,<qps>,<ms/batch>`` CSV lines like the other
benchmark sections.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

# default shape (paper §5-style retrieval, scaled to a benchmark budget)
M, D, KPROJ, NQ, KTOP = 16384, 128, 64, 64, 10


def _time(fn, *args, iters: int = 5):
    jax.block_until_ready(fn(*args))            # warmup / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    from repro.kernels.metric_topk import (metric_topk, metric_topk_naive,
                                           metric_topk_ref, metric_topk_xla,
                                           project_gallery,
                                           tile_gallery)

    rng = np.random.RandomState(0)
    L = jnp.asarray(0.2 * rng.randn(KPROJ, D), jnp.float32)
    gallery = jnp.asarray(rng.randn(M, D), jnp.float32)
    queries = jnp.asarray(rng.randn(NQ, D), jnp.float32)

    t0 = time.perf_counter()
    gp, gn = project_gallery(L, gallery)
    gpt, gnt = jax.block_until_ready(tile_gallery(gp, gn))
    print(f"index build (one-time projection and layout of {M} rows): "
          f"{(time.perf_counter() - t0) * 1e3:.1f} ms")

    # --- 1. fused kernel correctness vs the XLA reference ---------------
    qp = queries @ L.T
    d_ref, i_ref = metric_topk_ref(qp, gp, KTOP, gn)
    d_ker, i_ker = metric_topk(L, queries, gpt, gnt, k_top=KTOP)
    assert (np.asarray(i_ker) == np.asarray(i_ref)).all(), \
        "fused kernel indices != XLA reference"
    np.testing.assert_allclose(np.asarray(d_ker), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-4)
    print(f"fused kernel vs XLA reference on ({NQ}x{M}, d={D}, k={KPROJ}): "
          f"indices exact, distances rtol<=1e-4  [OK]")

    # --- 2. serving throughput: amortized factored path vs per-pair XLA -
    def factored(q):
        return metric_topk_xla(L, q, gpt, gnt, KTOP)

    def naive(q):
        return metric_topk_naive(L, q, gallery, KTOP)

    t_fused = _time(factored, queries, iters=10)
    t_naive = _time(naive, queries, iters=1)
    rows = [
        ("factored_preprojected", t_fused),
        ("xla_per_pair_reference", t_naive),
    ]
    print("\nsection,name,qps,ms_per_batch64")
    for name, t in rows:
        print(f"retrieval,{name},{NQ / t:.0f},{t * 1e3:.2f}")
    speedup = t_naive / t_fused
    print(f"speedup (factored serving path vs per-pair reference): "
          f"{speedup:.1f}x")
    assert speedup > 1.0, \
        f"serving path did not beat the reference ({speedup})"


if __name__ == "__main__":
    main()
