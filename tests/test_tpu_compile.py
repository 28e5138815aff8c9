"""Compile the main-path Pallas kernels for a TPU v5e, without a chip.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: blocks whose last two dims break the (8, 128) tiling,
1-D vectors against the chip's T(1024) layout, more VMEM than a kernel
may use. Each test here lowers one kernel's ops-layer entry point at
the widths ``chip_smoke.py`` runs (the paper's ImageNet-1M: d_in 21,504,
d_out 1,000, 1,000 pairs per step) for one chip of a described v5e
topology, compiles it, and checks that the kernel is in the program as
a ``tpu_custom_call``; the exact search runs at the serving cells' 1M
rows, and nothing in it may copy the gallery. Nothing runs.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
imports every test file in every worker.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.dml_pair import dml_pair_loss_fused
from repro.kernels.ivf_scan import ivf_scan_topk
from repro.kernels.metric_topk import metric_topk, tile_gallery
from repro.kernels.pairwise_dist import metric_sqdist_matrix
from repro.kernels.pq_adc import pq_adc_topk

D_IN, D_OUT, PAIRS = 21_504, 1_000, 1_000       # configs/dml_paper IMNET_1M
NQ = 64                                          # chip_smoke's serving batch
C, CAP, NPROBE = 1_024, 256, 16                  # its IVF layout
S, BITS, RERANK = 20, 8, 256                     # its PQ codes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes, kernel):
    """Compile ``fn`` and check that the kernel is in the program as a
    ``tpu_custom_call`` under its stable ``name=``, which the trace's op
    events carry (``%<kernel>.<n> = ... custom-call``)."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert re.search(rf"%{kernel}(\.\d+)? = .* custom-call\(.*"
                     rf"custom_call_target=\"tpu_custom_call\"", hlo), kernel
    return hlo


F32, I32, U8 = jnp.float32, jnp.int32, jnp.uint8


def _gallery_moves(hlo: str, elements: int):
    """The copies, pads and transposes in ``hlo`` (fused or not) whose
    result holds at least ``elements`` elements."""
    moves = []
    for m in re.finditer(r"%\S+ = \w+\[([\d,]*)\]\S* "
                         r"(copy|copy-start|pad|transpose)\(", hlo):
        n = 1
        for dim in filter(None, m.group(1).split(",")):
            n *= int(dim)
        if n >= elements:
            moves.append(m.group(0))
    return moves


def _exact_search(one_chip, k_top, monkeypatch):
    # the operand the single-device ExactIndex holds for the serving
    # cells' 1M-row gallery, as its build lays it out on a TPU
    rows = 1_000_000
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        gp, gn = jax.eval_shape(tile_gallery,
                                jax.ShapeDtypeStruct((rows, D_OUT), F32),
                                jax.ShapeDtypeStruct((rows,), F32))
    hlo = _compile(lambda L, q, gp, gn: metric_topk(
                       L, q, gp, gn, k_top=k_top, interpret=False),
                   one_chip, ((D_OUT, D_IN), F32), ((NQ, D_IN), F32),
                   (gp.shape, F32), (gn.shape, F32), kernel="metric_topk")
    # the search reads the stored gallery as it stands: nothing beside
    # the kernel copies, pads or re-lays it on a call
    assert _gallery_moves(hlo, rows * D_OUT) == []


def test_metric_topk(one_chip, monkeypatch):
    _exact_search(one_chip, 10, monkeypatch)


def test_metric_topk_at_the_miner_k(one_chip, monkeypatch):
    _exact_search(one_chip, 21, monkeypatch)


def test_ivf_scan(one_chip):
    _compile(lambda qp, pr, g, gn, ids: ivf_scan_topk(
                 qp, pr, g, gn, ids, kk=10, interpret=False),
             one_chip, ((NQ, D_OUT), F32), ((NQ, NPROBE), I32),
             ((C, CAP, D_OUT), F32), ((C, CAP), F32), ((C, CAP), I32),
             kernel="ivf_scan")


def test_ivf_search_at_the_ivf_cell_widths(one_chip):
    # the benchmark's imnet1m.serve-ivf: IVF4096 over 1M rows, segments of
    # 312 (no 512-row tile divides them: one tile a segment), nprobe 32,
    # k 21, one batch of 64; the whole jitted search with its counts
    from repro.serve import ivf
    c, cap, nprobe, kk = 4_096, 312, 32, 21

    def search(q, live, L, cent, seg_rows, gp, gn, ids):
        qp = ivf.scan.project_queries(L, q)
        probes = ivf._probe(qp, cent, nprobe)
        d, i = ivf_scan_topk(qp, probes, gp.reshape(c, cap, D_OUT),
                             gn.reshape(c, cap), ids.reshape(c, cap), kk=kk,
                             interpret=False)
        return d, i, ivf._scan_counts(probes, seg_rows, i, live, cap)

    _compile(search, one_chip, ((NQ, D_IN), F32), ((), I32),
             ((D_OUT, D_IN), F32), ((c, D_OUT), F32), ((c,), I32),
             ((c * cap, D_OUT), F32), ((c * cap,), F32), ((c * cap,), I32),
             kernel="ivf_scan")


def test_pq_adc(one_chip):
    _compile(lambda tab, dc, pr, codes, t, ids: pq_adc_topk(
                 tab, dc, pr, codes, t, ids, kk=RERANK, interpret=False),
             one_chip, ((NQ, S << BITS), F32), ((NQ, NPROBE), F32),
             ((NQ, NPROBE), I32), ((C, CAP, S), U8), ((C, CAP), F32),
             ((C, CAP), I32), kernel="pq_adc")


_PAIRS = (((D_OUT, D_IN), F32), ((PAIRS, D_IN), F32), ((PAIRS, D_IN), F32),
          ((PAIRS,), I32))


def test_dml_pair_forward(one_chip):
    _compile(lambda L, xs, ys, sim: dml_pair_loss_fused(
                 L, xs, ys, sim, 1.0, 1.0, False), one_chip, *_PAIRS,
             kernel="dml_pair")


def test_dml_pair_grad(one_chip):
    _compile(jax.grad(lambda L, xs, ys, sim: dml_pair_loss_fused(
                 L, xs, ys, sim, 1.0, 1.0, False)), one_chip, *_PAIRS,
             kernel="dml_pair")


def test_pairwise_dist(one_chip):
    # the kNN eval's all-pairs matrix: N and M off every tile, d_out
    # off the lane width — ops.py pads all three
    _compile(lambda L, x, y: metric_sqdist_matrix(L, x, y, interpret=False),
             one_chip, ((D_OUT, D_IN), F32), ((500, D_IN), F32),
             ((1_000, D_IN), F32), kernel="pairwise_dist")
