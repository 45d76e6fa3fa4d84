"""The program's spans as the benchmark reads them (bench/spans.py): self
time and nesting per host line on hand-made spans, idle time charged to the
innermost of the ``bench.*`` and ``aquifer.*`` spans, and a recorded CPU
trace of one small restore through ``Orchestrator`` → ``restore_checkpoint``."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import spans  # noqa: E402

tracing = harness.tracing


def _lines():
    # line A: restore [0, 1000] > hot [100, 600] > two reads and an install;
    # line B (another thread) overlaps A's spans but is not their child
    a = [("aquifer.restore", 0, 1000), ("aquifer.restore.hot", 100, 600),
         ("aquifer.restore.cxl_read", 100, 200),
         ("aquifer.restore.install", 200, 500),
         ("aquifer.scatter.launch", 250, 300),
         ("aquifer.restore.cxl_read", 500, 550),
         ("aquifer.restore.extract", 700, 950)]
    b = [("aquifer.restore.install", 150, 450),
         ("aquifer.scatter.verify", 400, 450)]
    return {"/host:CPU#0 python": a, "/host:CPU#1 python": b}


def test_self_time_is_duration_less_children_on_the_same_line():
    s = spans.self_times(_lines(), 0, 1000)
    ns = {k: round(v * 1e9) for k, v in s.items()}
    assert ns["aquifer.restore"] == 1000 - 500 - 250
    assert ns["aquifer.restore.hot"] == 500 - 100 - 300 - 50
    assert ns["aquifer.restore.cxl_read"] == 100 + 50
    # A's install less its launch, plus B's install less its verify: B's
    # spans are not children of anything on A
    assert ns["aquifer.restore.install"] == (300 - 50) + (300 - 50)
    assert ns["aquifer.scatter.launch"] == 50 and ns["aquifer.scatter.verify"] == 50
    assert ns["aquifer.restore.extract"] == 250
    # self times of one line add up to its outermost span
    one = spans.self_times({"a": _lines()["/host:CPU#0 python"]}, 0, 1000)
    assert sum(one.values()) == pytest.approx(1000e-9)


def test_self_time_is_clipped_to_the_window():
    s = spans.self_times(_lines(), 250, 700)
    ns = {k: round(v * 1e9) for k, v in s.items()}
    assert ns["aquifer.restore"] == 100                   # 600..700
    assert ns["aquifer.restore.hot"] == 350 - 250 - 50    # 250..600 less its children
    assert ns["aquifer.restore.install"] == (250 - 50) + (200 - 50)
    assert ns["aquifer.restore.cxl_read"] == 50
    assert "aquifer.restore.extract" not in ns


def test_idle_inside_the_restore_is_charged_to_its_phases():
    """As restore_spans.py reads a traced cold start: the gaps clipped to
    ``bench.restore``, split across ``bench.*`` and ``aquifer.*`` spans."""
    bench = [("bench.traced", -100, 2000), ("bench.restore", -50, 1000),
             ("bench.first_token", 1000, 1900)]
    every = bench + spans.flatten(_lines())
    ops = [("fusion", 120, 250), ("restore", 550, 560), ("fusion", 1200, 1800)]
    gaps = spans.idle_gaps(ops, -100, 2000)
    assert gaps == [(-100, 120), (250, 550), (560, 1200), (1800, 2000)]
    ra, rb = -50, 1000
    got = spans.idle_by_span(
        every, [(max(a, ra), min(b, rb)) for a, b in gaps if b > ra and a < rb])
    ns = {k: round(v * 1e9) for k, v in got.items()}
    assert ns == {"bench.restore": 50,                      # -50..0
                  "aquifer.restore": 100 + 100 + 50,        # 0..100, 600..700, 950..1000
                  "aquifer.restore.cxl_read": 20 + 50,      # 100..120, 500..550
                  "aquifer.scatter.launch": 50,             # 250..300
                  "aquifer.restore.install": 100 + 50,      # 300..400 (both lines), 450..500
                  "aquifer.scatter.verify": 50,             # 400..450 (line B)
                  "aquifer.restore.hot": 40,                # 560..600
                  "aquifer.restore.extract": 250}           # 700..950
    assert sum(ns.values()) == sum(min(b, rb) - max(a, ra) for a, b in gaps[:3])
    assert spans.OUTSIDE not in ns


def test_idle_time_is_split_between_the_phases_a_gap_spans():
    # one gap [120, 460] crosses a read, installs, a launch and B's verify
    every = [("bench.restore", 0, 1000)] + spans.flatten(_lines())
    got = spans.idle_by_span(every, [(120, 460), (960, 1100)])
    ns = {k: round(v * 1e9) for k, v in got.items()}
    assert ns == {"aquifer.restore.cxl_read": 80,        # 120..200
                  # 200..250, 300..400, 450..460 (A's install is shorter than B's)
                  "aquifer.restore.install": 50 + 100 + 10,
                  "aquifer.scatter.launch": 50,          # 250..300
                  "aquifer.scatter.verify": 50,          # 400..450 (line B)
                  "aquifer.restore": 40,                 # 960..1000
                  spans.OUTSIDE: 100}                    # 1000..1100


@pytest.mark.parametrize("seed", [0, 1])
def test_idle_split_agrees_with_a_label_per_nanosecond(seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, 400, 60)
    sp = [(f"s{i % 5}", int(a), int(a + d))
          for i, (a, d) in enumerate(zip(starts, rng.integers(0, 120, 60)))]
    cuts = np.sort(rng.choice(np.arange(0, 520), 16, replace=False))
    gaps = [(int(a), int(b)) for a, b in zip(cuts[::2], cuts[1::2])]
    want: dict = {}
    for a, b in gaps:
        for t in range(a, b):
            held = [(e - s, n) for n, s, e in sp if s <= t < e]
            label = min(held)[1] if held else spans.OUTSIDE
            want[label] = want.get(label, 0) + 1
    got = spans.idle_by_span(sp, gaps)
    assert {k: round(v * 1e9) for k, v in got.items()} == want


def test_the_benchmarks_reduction_is_unchanged_without_program_spans():
    """A hand-made trace with no aquifer.* spans reduces as it always has."""
    ops = {"/device:TPU:0": [("fusion.1", 100, 300), ("fusion.2", 200, 400),
                             ("jit_fused_restore_pallas", 700, 900)]}
    mods = {"/device:TPU:0": [("jit_decode_step(1)", 100, 400),
                              ("jit_fused_restore_pallas(2)", 700, 900)]}
    bench = [("bench.traced", 0, 1000), ("bench.restore", 0, 600),
             ("bench.first_token", 600, 1000)]
    red = tracing.reduce(tracing.Events(ops, mods, bench), 0, 1000)
    assert red.idle_share == pytest.approx(0.5)
    assert red.module_s("jit_decode_step") == pytest.approx(300e-9)
    assert [s for _, s in red.gaps] == pytest.approx([300e-9, 100e-9, 100e-9])
    assert sorted(g for g, _ in red.gaps) == [
        "bench.first_token", "bench.restore", "bench.restore"]
    # the reader's gaps are the reduction's, and charging their time span by
    # span keeps its sum
    gaps = spans.idle_gaps(ops["/device:TPU:0"], 0, 1000)
    assert gaps == [(0, 100), (400, 700), (900, 1000)]
    split = spans.idle_by_span(bench, gaps)
    assert {k: round(v * 1e9) for k, v in split.items()} == {
        "bench.restore": 100 + 200, "bench.first_token": 100 + 100}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_idle_gaps_are_the_benchmarks_gaps(seed):
    """The stretches the reader splits are those trace.reduce measures."""
    rng = np.random.default_rng(seed)
    starts = rng.integers(-200, 5_000, 80)
    ops = [(f"op{i}", int(a), int(a + d))
           for i, (a, d) in enumerate(zip(starts, rng.integers(1, 300, 80)))]
    t0, t1 = 0, 5_000
    red = tracing.reduce(tracing.Events({"/device:TPU:0": ops}, {}, []), t0, t1)
    gaps = spans.idle_gaps(ops, t0, t1)
    assert all(t0 <= a < b <= t1 for a, b in gaps)
    assert all(b1 < a2 for (_, b1), (a2, _) in zip(gaps, gaps[1:]))
    assert sorted((b - a) / 1e9 for a, b in gaps) == pytest.approx(
        sorted(s for _, s in red.gaps))
    assert sum(b - a for a, b in gaps) / 1e9 == pytest.approx(red.window_s - red.busy_s)


def _inside(outer, sps):
    _, a, b = outer
    return [s for s in sps if a <= s[1] and s[2] <= b and s is not outer]


def test_a_recorded_restore_holds_its_phase_spans(tmp_path):
    """One small restore through Orchestrator → restore_checkpoint, with the
    fused kernel (in the interpreter) over a device page array: the whole
    restore holds each phase, and every hot chunk is one CXL read and one
    install, each install one staged, launched and verified batch."""
    import jax.numpy as jnp

    from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
    from repro.core import Catalog, HierarchicalPool, Orchestrator, PoolMaster
    from repro.kernels.snapshot_fuse.ops import FusedScatter

    rng = np.random.default_rng(3)
    state = {"params": {"w": jnp.asarray(rng.standard_normal((300, 1024)),
                                         jnp.float32)},
             "opt": {"m": rng.standard_normal((20, 1024)).astype(np.float32),
                     "v": np.zeros((16, 1024), np.float32)}}
    pool = HierarchicalPool(cxl_capacity=64 << 20, rdma_capacity=64 << 20)
    master = PoolMaster(pool, Catalog())
    _, pub = save_checkpoint(master, "ck", state, step=1)
    assert pub["hot"] > 256 and pub["cold"] > 0 and pub["zero"] > 0
    orch = Orchestrator("h", pool, master.catalog,
                        scatter_fn=FusedScatter(use_pallas=True, interpret=True))
    with tracing.record(tmp_path):
        restored, _ = restore_checkpoint(orch, "ck", state)
    orch.close()
    np.testing.assert_array_equal(np.asarray(restored["opt"]["m"]), state["opt"]["m"])

    lines = spans.load(tmp_path)
    sps = spans.flatten(lines)
    restore = [s for s in sps if s[0] == "aquifer.restore"]
    assert len(restore) == 1
    inner = _inside(restore[0], sps)
    names = {s[0] for s in inner}
    for phase in ("borrow", "hot", "zero", "cold", "extract"):
        assert f"aquifer.restore.{phase}" in names
    assert {"aquifer.restore.cxl_read", "aquifer.restore.rdma_read",
            "aquifer.restore.install", "aquifer.scatter.stage",
            "aquifer.scatter.launch", "aquifer.scatter.verify"} <= names

    # the first hot pass (in Orchestrator.restore) installs every chunk
    hot = [s for s in inner if s[0] == "aquifer.restore.hot"]
    assert len(hot) == 2                                   # both passes
    first = _inside(hot[0], sps)
    chunks = math.ceil(pub["hot"] / 256)
    for name in ("aquifer.restore.cxl_read", "aquifer.restore.install",
                 "aquifer.scatter.launch", "aquifer.scatter.verify"):
        assert sum(s[0] == name for s in first) == chunks, name
    assert not [s for s in _inside(hot[1], sps) if s[0] == "aquifer.restore.install"]
    (cold,) = [s for s in inner if s[0] == "aquifer.restore.cold"]
    in_cold = [s[0] for s in _inside(cold, sps)]
    assert in_cold.count("aquifer.restore.rdma_read") == in_cold.count(
        "aquifer.restore.install") >= 1
    # every span of the restore is on the caller's line, and the leaf phases
    # (self times) add up to the whole restore
    (line,) = [k for k, v in lines.items() if restore[0] in v]
    assert all(s in lines[line] for s in inner)
    selfs = spans.self_times(lines, restore[0][1], restore[0][2])
    assert sum(selfs.values()) == pytest.approx((restore[0][2] - restore[0][1]) / 1e9)
