"""The IVF mining cell at a tiny size on the CPU, and its counting.

The cell's run, its control and its faults (``altered``, ``half`` and the
driver's own ``FAULTS``) run with every other one-chip cell in
``test_cells.py`` and ``test_faults.py``. Here: a run reads the
program's IVF counters and build stages into its per-layer metrics; a
program without them (the stack before they existed) still runs, and
those metrics are left out; the check's pieces (list membership and its
rule, the tie rule at the ``nprobe``-th list, a left-out row) and the
counting functions give the values worked out by hand.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from harness import counts, ivf_counts, ivf_reference, spec
from tiny import TINY_PEAKS, tiny_cell

CELL = "imnet1m.serve-ivf"


def _run(seed=2 ** 31 + 17):
    """(ctx, numbers) of one tiny run of the cell through its driver."""
    import time

    import jax
    work, cfg, traffic, _ = tiny_cell(CELL)
    ctx, nums, _ = spec.driver(traffic["kind"]).drive(
        cfg, traffic, seed=seed, seconds=1.0, prof=None,
        t_start=time.perf_counter(), devices=jax.devices()[:1])
    ctx.update(cfg=cfg, traffic=traffic, peaks=TINY_PEAKS, chips=1)
    return ctx, nums


def _layer(ctx):
    entries = [m for m in spec.benchmark()["per_layer"]
               if m["name"] in ("mfu.ivf", "ivf_pad_share.serve",
                                "ivf_build_s.serve", "ivf_scan_roofline")]
    return spec.read_metrics(entries, ctx)


def test_a_run_reads_the_scan_counters_and_the_build():
    ctx, nums = _run()
    work, cfg = ctx["ivf"], ctx["cfg"]
    assert work["calls"] > 0 and work["probes"] > 0
    # every live query probes nprobe lists, and a list read once a batch
    assert work["probes"] % cfg["nprobe"] == 0
    assert 0 < work["rows_read"] <= work["rows_probed"]
    assert work["underfilled"] == 0
    assert set(ctx["ivf_build_s"]) == {"kmeans", "balance", "layout",
                                       "build"}
    got = _layer(ctx)
    # no device trace on the CPU: the roofline is left out
    assert set(got) == {"mfu.ivf", "ivf_pad_share.serve",
                        "ivf_build_s.serve"}
    assert 0 <= got["ivf_pad_share.serve"]["value"] < 100
    assert got["ivf_build_s.serve"]["value"] == ctx["ivf_build_s"]["build"]
    assert nums["layout_wrong"] == 0 and nums["ids_invalid"] == 0
    assert nums["assign_excess"] < 1e-5 and nums["rank_missed"] < 1e-5
    assert 1 <= nums["probe_lists_min"] <= nums["probe_lists_mean"] <= (
        cfg["nprobe"])

    # with a trace of the kernel: 2 ms a call
    ctx["trace"] = {"devices": {0: {"ops_ns": {"ivf_scan.3": 2e6 * 10},
                                    "op_counts": {"ivf_scan.3": 10}}}}
    call = ivf_counts.per_call(work, cfg["nprobe"])
    least, _ = counts.least_seconds(
        ivf_counts.scan_flops(call["rows_probed"], cfg["proj_dim"]),
        ivf_counts.scan_bytes(call["rows_read"], call["queries"],
                              cfg["proj_dim"]),
        TINY_PEAKS["bf16_flops"], TINY_PEAKS["hbm_bytes_per_s"])
    assert _layer(ctx)["ivf_scan_roofline"]["value"] == pytest.approx(
        100 * least / 2e-3)


def test_a_program_without_counters_runs_and_leaves_them_out(monkeypatch):
    from repro.serve import engine as engine_mod
    from repro.serve.ivf import IVFIndex
    monkeypatch.delattr(IVFIndex, "topk_counted")
    monkeypatch.setattr(engine_mod.RetrievalEngine, "_collect_gauges",
                        lambda self: None)
    ctx, nums = _run(seed=2 ** 31 + 19)
    assert ctx["ivf"] is None and ctx["ivf_build_s"] is None
    assert _layer(ctx) == {}
    _, _, _, limits = tiny_cell(CELL)
    assert spec.judge(nums, limits)[0], nums


def test_membership_counts_missing_and_doubled_rows():
    # segments of 3: lists 0 and 1; row 2 held twice, rows 3 and 4
    # missing, 9 out of range
    ids = np.array([0, 2, -1, 1, 2, 9], np.int32)
    assign, counts, wrong = ivf_reference.membership(ids, 3, 5, 3)
    assert list(assign) == [0, 1, -1, -1, -1]
    assert list(counts) == [2, 2]
    assert wrong == 4
    ok, counts, none = ivf_reference.membership(np.array([1, 0, -1, 2]), 2,
                                                3, 2)
    assert list(ok) == [0, 0, 1] and list(counts) == [2, 1] and none == 0
    # the same segments under a rule's cap of 1: list 0 holds one over it
    assert ivf_reference.membership(np.array([1, 0, -1, 2]), 2, 3, 1)[2] == 1


def test_the_rules_cap_is_the_cells():
    # ceil(1.25 x 1M / 4,096) = 306, rounded up to 8
    assert ivf_reference.cap(1_000_000, 4096, 1.25) == 312
    assert ivf_reference.cap(4096, 64, 1.0) == 64
    assert ivf_reference.cap(100, 8, 0.5) == 16        # never under M / C


def test_the_tie_rule_drops_lists_at_the_nprobe_th():
    qp = jnp.zeros((1, 2), jnp.float32)
    # centroid distances 1, 4, 4.001, 9 from the origin
    cent = jnp.array([[1, 0], [2, 0], [0, 2.00025], [3, 0]], jnp.float32)
    exact = ivf_reference.probed(qp, cent, nprobe=2, tie=0.0)
    assert list(np.asarray(exact)[0]) == [True, True, False, False]
    # a band of 1e-3 x (|q|^2 + max |c|^2) = 0.009 below the 3rd (4.001):
    # the 2nd (4.0) ties with it and is not required
    surely = ivf_reference.probed(qp, cent, nprobe=2, tie=1e-3)
    assert list(np.asarray(surely)[0]) == [True, False, False, False]
    # with no tie at the 2nd, both nearest lists are required
    apart = ivf_reference.probed(qp, cent, nprobe=1, tie=1e-3)
    assert list(np.asarray(apart)[0]) == [True, False, False, False]
    wide = ivf_reference.probed(qp, cent, nprobe=3, tie=1e-3)
    assert list(np.asarray(wide)[0]) == [True, True, True, False]
    every = ivf_reference.probed(qp, cent, nprobe=4, tie=1e-3)
    assert np.asarray(every).all()


def _rows_and_centroids():
    # centroids on a line at 0, 10, 20; the rule's cap 2
    cent = jnp.array([[0, 0], [10, 0], [20, 0]], jnp.float32)
    # rows 0-2 nearest list 0 (at 1, 2, 3), row 3 nearest list 1 (at 9),
    # row 4 nearest list 2
    gp = jnp.array([[1, 0], [2, 0], [3, 0], [9, 0], [19, 0], [0, 0]],
                   jnp.float32)
    return cent, gp, jnp.sum(gp * gp, axis=1)


def _excess(own, counts, cap=2):
    cent, gp, gn = _rows_and_centroids()
    own = np.array(own, np.int32)
    d_own, d_near, near, d_room = map(np.asarray, ivf_reference.assignment(
        gp, gn, cent, jnp.asarray(own), jnp.asarray(np.array(counts) < cap),
        blocks=2))
    return ivf_reference.assign_excess(own, d_own, d_near, near, d_room,
                                       np.ones(len(own)))


def test_the_membership_rule_by_hand():
    # the rule: list 0 keeps rows 0 (at 1) and 5 (at 0), its two nearest;
    # row 1 (at 2) and row 2 (at 3) spill to list 1, the nearest with
    # room, which then holds rows 1 and 3 and is full: row 2 goes on to
    # list 2
    assert _excess([0, 1, 2, 1, 2, 0], [2, 2, 2]) == 0.0
    # rows 2 and 3 sent to list 2 though list 1 has room (list 2 over
    # the cap is membership's): (3-20)^2 - (3-10)^2 = 240 for row 2
    assert _excess([0, 1, 2, 2, 2, 0], [2, 1, 3]) == 289 - 49
    # cap 3: list 0 keeps rows 5, 0 and 2 (at 0, 1, 3) and spills row 1
    # (at 2) to list 1: row 1 is nearer list 0 than row 2 by 9 - 4 = 5
    # (its own list lies (2-10)^2 - 4 = 60 farther)
    assert _excess([0, 1, 0, 1, 2, 0], [3, 2, 1], cap=3) == 5.0
    # a row out of its nearest list while that list has room: (9-0)^2 -
    # (9-10)^2 = 80
    assert _excess([0, 0, 0, 0, 2, 0], [5, 0, 1], cap=6) == 80.0
    # rows no list holds are left to membership
    assert _excess([0, 1, 2, 1, 2, -1], [2, 2, 2]) == 0.0


def test_a_left_out_required_row_reads_as_missed():
    from harness import mine_ivf
    # one query at the origin on a line of rows at 1..5; the reference's
    # top 3 within the lists is rows 0, 1, 2
    gp = jnp.array([[1, 0], [2, 0], [3, 0], [4, 0], [5, 0]], jnp.float32)
    gn = np.asarray(jnp.sum(gp * gp, axis=1), np.float64)
    truth = (np.array([[1.0, 4, 9]]), np.array([[0, 1, 2]]),
             np.array([[0, 1, 2]]), np.zeros(1), gn)
    qp = jnp.zeros((1, 2), jnp.float32)

    def numbers(ids):
        d = np.array([[float((i + 1) ** 2) for i in ids]])
        return mine_ivf._numbers((d, np.array([ids])), truth, qp, gp, 5)

    right = numbers([0, 1, 2])
    assert right["rank_missed"] == 0 and right["rank_excess"] == 0
    # row 0 left out, row 3 served: 16 - 1 over |qp|^2 + max |gp|^2 (16)
    wrong = numbers([1, 2, 3])
    assert wrong["rank_missed"] == pytest.approx(15 / 16)
    # rank_excess sees only the sorted gaps: (4-1, 9-4, 16-9) / 16
    assert wrong["rank_excess"] == pytest.approx(7 / 16)


def test_the_reference_search_keeps_to_the_lists():
    # rows 0, 1 in list 0 (near the origin), rows 2, 3 in list 1
    gp = jnp.array([[0, 1], [0, 2], [5, 0], [0.5, 0]], jnp.float32)
    gn = jnp.sum(gp * gp, axis=1)
    assign = jnp.array([0, 0, 1, 1], jnp.int32)
    qp = jnp.zeros((1, 2), jnp.float32)
    allowed = jnp.array([[True, False]])
    (d_in, i_in), (d_all, i_all) = ivf_reference.knn(
        qp, gp, gn, assign, allowed, k=3, blocks=2)
    assert list(np.asarray(i_in)[0]) == [0, 1, -1]
    assert list(np.asarray(d_in)[0][:2]) == [1, 4]
    assert list(np.asarray(i_all)[0]) == [3, 0, 1]


def test_counting_at_the_cells_widths():
    # IVF4096 over 1M rows at cap 312: about 244 real rows a list; a
    # query probing 32 lists scores 32 x 244 = 7,808 rows
    assert ivf_counts.scan_flops(7808, 1000) == 15_616_000
    # 64 queries sharing no list read 64 x 7,808 rows, each with its
    # norm and id, and the 64 projected queries
    assert ivf_counts.scan_bytes(64 * 7808, 64, 1000) == 4 * (
        499_712 * 1002 + 64_000) == 2_003_101_696
    # projection 43.0 MFLOP, probe of 4,096 centroids 8.2 MFLOP
    assert ivf_counts.query_flops(21504, 1000, 4096, 7808) == (
        43_008_000 + 8_192_000 + 15_616_000)
    assert ivf_counts.per_call({"calls": 2, "rows_probed": 100,
                                "rows_read": 60, "probes": 16}, 4) == {
        "rows_probed": 50, "rows_read": 30, "queries": 2}
    assert ivf_counts.per_call({"calls": 0}, 4) is None


def test_the_driver_names_its_fault():
    _, _, traffic, limits = tiny_cell(CELL)
    driver = spec.driver(traffic["kind"])
    assert set(driver.FAULTS) == {"probe_next", "fewer_probes",
                                  "smaller_cap", "spill_second"}
    assert set(limits["faults"]) == {"altered", "half"} | set(driver.FAULTS)
