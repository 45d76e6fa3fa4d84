"""A burst of cold starts on a four-chip cell, driven on the CPU at a tiny
size: four instances restore one snapshot together, each onto its own
device, through one node page server whose hot-chunk cache reads each chunk
once and hands it to the others.

The harness's look for a chip is skipped.  A child process with four host
devices (``--xla_force_host_platform_device_count=4``) runs this file as a
script: ``harness.run_cell`` on the ``mistral-coldstart-x4`` cell with the
tiny configuration, a sound run and then a run with each fault planted
under the timed path, and prints what each run recorded as one JSON line.
The restore kernel runs in the interpreter over device page arrays, as the
chip runs it compiled."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

import arrivals  # noqa: E402
import control  # noqa: E402
import harness  # noqa: E402
import work  # noqa: E402

CELL = "mistral-coldstart-x4"
SEED = 2**31 + 5151
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
VOCAB = 16384          # 8 hot chunks a restore, so that a burst has some to share


def _tiny() -> harness.Cell:
    cell = harness.load_cell(CELL)
    cfg = json.loads((HERE / "tiny-dense.json").read_text())
    cfg["vocab_size"] = VOCAB
    cfg["program"] = dict(cfg["program"], overrides={"vocab": VOCAB})
    cell.cfg = cfg
    cell.mix = dict(cell.mix, max_len=128,
                    prompt_len={"median": 24, "sigma": 0.6, "min": 4, "max": 64})
    return cell


# --------------------------------------------------------------------------
# faults planted under the timed path (applied in the child process)
# --------------------------------------------------------------------------

def _state_unchanged(mp):
    """A restore that installs nothing: every instance keeps the zeroed
    memory it was given."""
    from repro.core.serving import RestoreEngine
    mp.setattr(RestoreEngine, "pre_install_hot", lambda self, *a, **k: 0)
    mp.setattr(RestoreEngine, "install_all_sync", lambda self, use_batch=True: None)


def _half_the_burst(mp):
    """Half of a burst's invocations left out, the rest served."""
    orig = harness.Program.invoke
    mp.setattr(harness.Program, "invoke",
               lambda self, prompts: orig(self, prompts[: max(1, len(prompts) // 2)]))


def _fanout_left_out(mp):
    """The hot chunks reach the first restore of a burst alone: the cache
    hands nothing to the others, which install no hot page."""
    from repro.core.serving import RestoreEngine
    orig = RestoreEngine.pre_install_hot

    def first_only(self, *a, **k):
        group = self._group
        if group is not None and next(iter(group.sessions.values())) is not self:
            return 0
        return orig(self, *a, **k)
    mp.setattr(RestoreEngine, "pre_install_hot", first_only)


def _alter_a_token(mp):
    """The token served on the third chip altered where its prefill makes it."""
    import jax

    from repro.serve.engine import ServerInstance
    orig = ServerInstance.prefill
    third = jax.devices()[2]

    def altered(self, tokens):
        logits = orig(self, tokens)
        if third in logits.devices():
            return logits.at[:, 5].set(logits.max() + 1.0)
        return logits
    mp.setattr(ServerInstance, "prefill", altered)


# the cell's per-layer metrics read from counters, not from a trace
COUNTED = ("chunk_cache_reads.burst", "fanout_hits.burst", "install_batches.coldstart",
           "verify_syncs.coldstart")

FAULTS = {"state_unchanged": _state_unchanged, "half_the_burst": _half_the_burst,
          "fanout_left_out": _fanout_left_out, "alter_a_token": _alter_a_token}


def _record(mp) -> dict:
    """What the run does that its result line does not say: the devices of
    each released instance's weights, the hot-chunk reads from CXL inside
    the window (through the fan-out cache or beside it), the hot chunks a
    restore asks for, and the run's record."""
    from repro.core.pool import HostView
    from repro.core.serving import RestoreEngine

    seen = {"placed": [], "reads": 0, "window_reads": [], "runs": [], "hot_chunks": 0}
    read, release = HostView.read_charged, harness.Program.release
    counters, run_cls = harness.Program.counters, harness.Run

    def counted(self, offset, nbytes):
        if nbytes > 4096 and nbytes % 4096 == 0:      # a hot chunk (1 MiB or the tail)
            seen["reads"] += 1
        return read(self, offset, nbytes)

    def placed(self, server):
        import jax
        seen["placed"].append(sorted({d.id for x in jax.tree.leaves(server.params)
                                      for d in x.devices()}))
        return release(self, server)

    def at_window_edge(self):
        seen["window_reads"].append(seen["reads"])
        seen["hot_chunks"] = -(-int(self.pub["hot"]) // RestoreEngine.HOT_CHUNK_PAGES)
        return counters(self)

    def kept(*a, **k):
        run = run_cls(*a, **k)
        seen["runs"].append(run)
        return run

    mp.setattr(HostView, "read_charged", counted)
    mp.setattr(harness.Program, "release", placed)
    mp.setattr(harness.Program, "counters", at_window_edge)
    mp.setattr(harness, "Run", kept)
    return seen


def _child(scenarios) -> None:
    from repro.kernels.snapshot_fuse import ops

    ops.default_scatter_fn = lambda: ops.FusedScatter(use_pallas=True, interpret=True)
    for name in scenarios:
        with pytest.MonkeyPatch.context() as mp:
            seen = _record(mp)
            if name != "sound":
                FAULTS[name](mp)
            res = harness.run_cell(_tiny(), SEED, 0.3, False, time.perf_counter(),
                                   PEAK, log=lambda *_: None)
        run = seen["runs"][-1]
        a, b = seen["window_reads"][-2:]
        print(json.dumps({
            "scenario": name, "result": res, "window_s": run.window_s,
            "invocations": [[v.latency_s, v.restore_s, v.first_token_s]
                            for v in run.invocations],
            "counters": run.counters, "placed": seen["placed"],
            "cxl_chunk_reads_seen": b - a, "hot_chunks": seen["hot_chunks"],
            "per_layer": {m: harness.reader(m)(run) for m in COUNTED}}), flush=True)


# --------------------------------------------------------------------------
# the tests
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, __file__, "sound", *FAULTS], cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines() if x.startswith("{")]
    return {r["scenario"]: r for r in lines}


def test_a_sound_burst_is_correct(runs):
    r = runs["sound"]["result"]
    assert r["correct"], r["checks"]
    assert r["attempted"] == 4 and r["failed"] == 0
    assert r["checks"]["params_differ"]["value"] == 0
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {m["name"] for m in _tiny().end_to_end
                                 if m["name"] != "peak_hbm_gib"}


def test_each_instance_is_restored_onto_its_own_device(runs):
    placed = runs["sound"]["placed"]
    assert len(placed) == 8                          # the warm-up burst, the window's
    for burst in (placed[:4], placed[4:]):
        assert sorted(burst) == [[0], [1], [2], [3]]


def test_the_latencies_run_from_the_bursts_due_time(runs):
    """All four are due at t = 0: each latency holds its own restore and
    first token, and the last to finish ends the window."""
    s = runs["sound"]
    invs = s["invocations"]
    assert len(invs) == 4
    for latency, restore_s, first_s in invs:
        assert restore_s + first_s <= latency <= s["window_s"]
    assert s["window_s"] - max(v[0] for v in invs) < 0.5


def test_the_burst_shares_its_hot_chunk_reads(runs):
    """The cache's reads and hand-overs account for every hot chunk the
    four restores asked for: what HostView read beside the cache was read
    by a restore that no other had joined yet."""
    s = runs["sound"]
    c, hot_chunks, seen = s["counters"], s["hot_chunks"], s["cxl_chunk_reads_seen"]
    assert hot_chunks >= 8
    assert c["bursts"] == 1 and c["restores"] == 4
    assert hot_chunks <= seen < 4 * hot_chunks
    assert 0 < c["chunk_cache_reads"] <= seen
    assert c["fanout_hits"] > 0
    assert seen + c["fanout_hits"] == 4 * hot_chunks
    per = s["per_layer"]
    assert per["chunk_cache_reads.burst"] == c["chunk_cache_reads"]
    assert per["fanout_hits.burst"] == c["fanout_hits"]


def test_the_restore_counters_read_per_restore_in_a_burst(runs):
    """Four restores update the one scatter's counters, and each makes the
    same scatter calls, one a hot chunk plus one a cold run, and the same
    checksum waits (here one a call: the tiny snapshot has no checksum
    table to bind)."""
    s = runs["sound"]
    c, per = s["counters"], s["per_layer"]
    assert c["batches"] % 4 == 0 and c["verify_syncs"] % 4 == 0
    assert s["hot_chunks"] < per["install_batches.coldstart"] == c["batches"] / 4
    assert per["verify_syncs.coldstart"] == c["verify_syncs"] / 4 > 0


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_fault_under_the_burst_is_not_correct(runs, fault):
    r = runs[fault]["result"]
    assert not r["correct"], r["checks"]


def test_the_control_is_not_correct():
    """The reference with float8 weights in the program's place, over the
    burst's four invocations, on three seeds."""
    cell = _tiny()
    prog = harness.Program(cell.cfg, cell.mix)
    template = prog.template
    prog.close()
    for seed in (SEED, SEED + 1, SEED + 2):
        r = control.run(cell, seed, template, invocations=4)
        assert not r["correct"], r["checks"]


def test_a_burst_is_as_wide_as_the_cells_chips():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and "size" not in cell.mix["arrivals"]
    for chips in (1, 2, 4):
        assert arrivals.arrivals(cell.mix, 9, 30.0, chips).tolist() == [0.0] * chips


def test_burst_due_times_are_bit_identical_per_seed():
    mix = arrivals.load_mix(harness.BENCH / "traffic" / "coldstart-burst4.json")
    a = arrivals.arrivals(mix, 2**31 + 77, 30.0, 4)
    assert a.tobytes() == arrivals.arrivals(mix, 2**31 + 77, 30.0, 4).tobytes()
    assert a.tobytes() == arrivals.arrivals(mix, 12, 30.0, 4).tobytes()
    assert a.tolist() == [0.0] * 4
    assert arrivals.arrivals(mix, 5, 130.0, 4).tolist() == [0.0] * 4 + [60.0] * 4 + [120.0] * 4
    assert [arrivals.prompt_length(mix, i) for i in range(4)] == [1020, 501, 1490, 815]


def test_the_burst_cell_reports_its_metrics_and_not_the_one_restore_readers():
    cell = harness.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"chunk_cache_reads.burst", "fanout_hits.burst", "restore_s.coldstart",
            "install_batches.coldstart", "verify_syncs.coldstart",
            "restore_kernel_roofline.burst", "first_token_s.coldstart",
            "device_idle_share.coldstart", "coldstart_mfu"} == names
    assert {m["name"] for m in cell.end_to_end} == {"coldstart_s", "peak_hbm_gib",
                                                    "setup_s"}
    one = harness.load_cell("mistral-coldstart")
    assert not {"chunk_cache_reads.burst", "fanout_hits.burst",
                "restore_kernel_roofline.burst"} & {m["name"] for m in one.per_layer}
    # the roofline over one restore's bytes reads 4x too high on four chips
    assert "restore_kernel_roofline.coldstart" not in names


def test_the_per_burst_readers_read_nothing_without_a_burst():
    invs = [harness.Invocation(16, 1.0, 0.9, 0.1, True)]
    run = harness.Run(harness.load_cell("mistral-coldstart"), PEAK, 30.0, 6.0, invs,
                      {"batches": 10, "restores": 1, "bursts": 0, "fanout_hits": 0,
                       "chunk_cache_reads": 0}, 1000, work.StepWork(2e9, 1e9), 8 << 30)
    for name in ("chunk_cache_reads.burst", "fanout_hits.burst"):
        assert harness.reader(name)(run) is None
    run.counters.update(bursts=2, fanout_hits=30, chunk_cache_reads=10)
    assert harness.reader("chunk_cache_reads.burst")(run) == 5
    assert harness.reader("fanout_hits.burst")(run) == 15


def test_a_bursts_roofline_reads_what_one_restore_reads_on_its_chip():
    """Four traced restores, one a chip, and the kernel's device time
    averaged over the four chips: each chip moved one restore's bytes."""
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    red = harness.tracing.Reduction(40.0, 5.0, [], [("jit_fused_restore_pallas(7)", 0.284)],
                                    [])

    def run(cell, n):
        invs = [harness.Invocation(16, 30.0, 25.0, 5.0, True)] * n
        return harness.Run(cell, peak, 90.0, 40.0, invs, {"restores": n}, 1077266,
                           work.StepWork(2e9, 1e9), 8 << 30, red)

    one = harness.reader("restore_kernel_roofline.coldstart")(
        run(harness.load_cell("mistral-coldstart"), 1))
    assert one == pytest.approx(100 * 2 * 4096 * 1077266 / 819e9 / 0.284)
    burst = harness.reader("restore_kernel_roofline.burst")(run(harness.load_cell(CELL), 4))
    assert burst == pytest.approx(one)
    assert harness.reader("restore_kernel_roofline.burst")(
        harness.Run(harness.load_cell(CELL), peak, 90.0, 40.0, [], {}, 1077266,
                    work.StepWork(2e9, 1e9), 8 << 30, None)) is None


if __name__ == "__main__":
    _child(sys.argv[1:])
