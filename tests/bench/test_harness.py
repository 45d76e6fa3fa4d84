"""The benchmark harness (bench/): everything is found by name from
BENCHMARK.json, a new cell is data files alone, the command refuses a CPU,
the traffic is the same for a seed, and the metric readers read a run."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import arrivals  # noqa: E402
import harness  # noqa: E402
import work  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = harness.load_cell(cell)
    assert c.cfg["name"] == next(w["config"] for w in SPEC["workloads"]
                                 if w["name"] == cell)
    ref = harness.reference(c)
    ref.Arch.from_config(c.cfg)
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.reader(m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    assert {"token_gap", "logit_err_median"} <= set(c.limits)


def test_benchmark_json_keeps_to_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert any(SPEC["command"][1].startswith(p + "/") for p in SPEC["paths"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert all(k in cfg for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    every = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    assert all(NAME.match(x["name"]) for x in every)
    assert len({x["name"] for x in every}) == len(every)


def test_a_new_cell_is_data_files_alone(tmp_path):
    """A mix, a configuration, a metric and a cell added as new files and
    new entries: the harness finds them, and no file already there changes."""
    shutil.copytree(BENCH, tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    b = tmp_path / "bench"
    mix = json.loads((b / "traffic" / "coldstart.json").read_text())
    mix.update(name="coldstart-poisson", loop="open",
               arrivals={"process": "poisson", "rate_rps": 0.05})
    (b / "traffic" / "coldstart-poisson.json").write_text(json.dumps(mix))
    cfg = json.loads((b / "configs" / "mistral-large-2407-1L.json").read_text())
    cfg["name"] = "mistral-large-2407-2L"
    cfg["num_hidden_layers"] = cfg["program"]["layers"] = 2
    (b / "configs" / "mistral-large-2407-2L.json").write_text(json.dumps(cfg))
    (b / "limits" / "mistral-2L-poisson.json").write_text(
        (b / "limits" / "mistral-coldstart.json").read_text())
    (b / "metrics" / "invocations.coldstart.py").write_text(
        "def read(run):\n    return float(len(run.invocations))\n")
    spec["configs"].append({"name": "mistral-large-2407-2L", "source": cfg["source"],
                            "file": "bench/configs/mistral-large-2407-2L.json",
                            "reduced": cfg["reduced"], "why": "deeper"})
    spec["workloads"].append({"name": "mistral-2L-poisson", "config": "mistral-large-2407-2L",
                              "traffic": "coldstart-poisson", "chips": 1,
                              "why": "open-loop cold starts"})
    spec["end_to_end"][0]["workloads"].append("mistral-2L-poisson")
    spec["per_layer"].append({"name": "invocations.coldstart", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "restore", "moves": "coldstart_s",
                              "workloads": ["mistral-2L-poisson"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("mistral-2L-poisson", root=tmp_path)
    assert cell.cfg["num_hidden_layers"] == 2 and cell.mix["loop"] == "open"
    assert [m["name"] for m in cell.per_layer] == ["invocations.coldstart"]
    assert "coldstart_s" in [m["name"] for m in cell.end_to_end]
    run = _run(cell, "coldstart")
    assert harness.reader("invocations.coldstart", tmp_path)(run) == 3.0
    assert harness.reader("coldstart_s", tmp_path)(run) == pytest.approx(2.0)
    due = arrivals.arrivals(cell.mix, 5, 600.0)
    assert due.size > 0 and np.all(np.diff(due) >= 0)
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} changed"


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_the_command_refuses_a_cpu_backend():
    out = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                          "--seed", "3000000007", "--seconds", "1", "--trace", "0"],
                         cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_the_command_needs_the_program_beside_it(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    out = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                          "--seed", "1", "--seconds", "1"], cwd=tmp_path,
                         env=_cpu_env(), capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.parametrize("mix_name", ["coldstart", "warm"])
def test_traffic_is_bit_identical_per_seed(mix_name):
    mix = arrivals.load_mix(BENCH / "traffic" / f"{mix_name}.json")
    seed = 2**31 + 77
    lengths = [arrivals.prompt_length(mix, i) for i in range(200)]
    assert lengths == [arrivals.prompt_length(mix, i) for i in range(200)]
    spec = mix["prompt_len"]
    assert min(lengths) >= spec["min"] and max(lengths) <= spec["max"]
    assert abs(np.median(lengths) - spec["median"]) <= 0.05 * spec["median"]
    # every prefix spreads over the distribution: a low, a middle and a high one
    first = sorted(lengths[:3])
    assert first[0] < spec["median"] <= first[1] < first[2]
    p1 = arrivals.prompts(mix, seed, 3, 17, 32768)
    assert np.array_equal(p1, arrivals.prompts(mix, seed, 3, 17, 32768))
    assert p1.shape == (mix["batch"], 17) and p1.dtype == np.int32
    assert not np.array_equal(p1, arrivals.prompts(mix, seed, 4, 17, 32768))
    assert not np.array_equal(p1, arrivals.prompts(mix, seed + 1, 3, 17, 32768))
    assert arrivals.arrivals(mix, seed, 10.0) is None       # closed loop


def test_a_mix_key_the_harness_does_not_read_is_refused(tmp_path):
    mix = json.loads((BENCH / "traffic" / "warm.json").read_text())
    mix["clients"] = 4
    (tmp_path / "warm4.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="clients"):
        arrivals.load_mix(tmp_path / "warm4.json")


def test_open_loop_arrivals_are_bit_identical_per_seed():
    mix = {"loop": "open", "arrivals": {"process": "onoff", "rate_rps": 2.0}}
    a = arrivals.arrivals(mix, 9, 60.0)
    assert np.array_equal(a, arrivals.arrivals(mix, 9, 60.0))
    assert not np.array_equal(a, arrivals.arrivals(mix, 10, 60.0))


def _run(cell, kind, reduction=None):
    invs = [harness.Invocation(16, 1.0, 0.9 if kind == "coldstart" else None, 0.1, True),
            harness.Invocation(32, 3.0, 2.8 if kind == "coldstart" else None, 0.2, False),
            harness.Invocation(8, 2.0, 1.9 if kind == "coldstart" else None, 0.1, False)]
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    return harness.Run(cell, peak, 30.0, 6.0, invs,
                       {"batches": 3000, "restores": 3}, 1000,
                       work.StepWork(2e9, 1e9), 8 << 30, reduction)


def test_metric_readers_read_a_coldstart_run():
    cell = harness.load_cell("mistral-coldstart")
    red = harness.tracing.Reduction(
        1.0, 0.1, [], [("jit_fused_restore_pallas(7)", 0.001)], [])
    run = _run(cell, "coldstart", red)
    r = {m: harness.reader(m)(run) for m in
         ["coldstart_s", "peak_hbm_gib", "setup_s", "restore_s.coldstart",
          "install_batches.coldstart", "first_token_s.coldstart",
          "restore_kernel_roofline.coldstart", "device_idle_share.coldstart",
          "coldstart_mfu", "warm_first_token_s", "decode_step_ms.warm"]}
    assert r["coldstart_s"] == pytest.approx(2.0)
    assert r["peak_hbm_gib"] == pytest.approx(8.0) and r["setup_s"] == 30.0
    assert r["restore_s.coldstart"] == pytest.approx(1.8667, rel=1e-4)
    assert r["install_batches.coldstart"] == 1000
    assert r["first_token_s.coldstart"] == pytest.approx(0.4 / 3)
    # one traced restore of 1000 pages: 2 x 4 KiB a page over 819 GB/s in 1 ms
    assert r["restore_kernel_roofline.coldstart"] == pytest.approx(
        100 * 2 * 4096 * 1000 / 819e9 / 1e-3)
    assert r["device_idle_share.coldstart"] == pytest.approx(90.0)
    least = 3 * 1000 * 4096 / 819e9 + 56 * (1e9 / 819e9)
    assert r["coldstart_mfu"] == pytest.approx(100 * least / 6.0)
    assert r["warm_first_token_s"] is None and r["decode_step_ms.warm"] is None


def test_metric_readers_read_a_warm_run():
    cell = harness.load_cell("mistral-warm")
    run = _run(cell, "warm", harness.tracing.Reduction(1.0, 0.8, [], [], []))
    assert harness.reader("warm_first_token_s")(run) == pytest.approx(2.0)
    assert harness.reader("decode_step_ms.warm")(run) == pytest.approx(1000 * 0.4 / 56)
    assert harness.reader("device_idle_share.warm")(run) == pytest.approx(20.0)
    assert harness.reader("warm_mfu")(run) == pytest.approx(
        100 * (1e9 / 819e9) / (0.4 / 56))
    for name in ("coldstart_s", "restore_s.coldstart", "restore_kernel_roofline.coldstart",
                 "install_batches.coldstart", "coldstart_mfu"):
        assert harness.reader(name)(run) is None


def test_least_work_is_counted_from_shapes():
    import jax
    import jax.numpy as jnp

    sd = jax.ShapeDtypeStruct
    tree = {"embed": {"table": sd((100, 8), jnp.bfloat16), "head": sd((8, 100), jnp.bfloat16)},
            "layers": {"mlp": {"wi": sd((2, 8, 16), jnp.bfloat16)},
                       "ln1": {"scale": sd((2, 8), jnp.bfloat16)}}}
    st = work.step(tree, batch=3)
    assert st.flops == pytest.approx(2 * 3 * 800 + 2 * 3 * (2 * 8 * 16))
    assert st.bytes == pytest.approx(3 * 16 + 1600 + 2 * 8 * 16 * 2 + 32)
    assert work.image_pages(tree) == 1 + 1 + 1 + 1


def test_the_kept_sample_holds_the_longest_and_is_fixed_by_the_seed():
    def keep(seed, lengths):
        s = harness.Sample(4, seed)
        for i, n in enumerate(lengths):
            s.offer(i, (np.zeros((2, n)), i, None))
        return [a[1] for a in s.items()]

    lengths = [5, 9, 3, 56, 7, 8, 56, 4, 6, 2, 11, 12]
    a = keep(7, lengths)
    assert a == keep(7, lengths) and len(a) == 4 and 3 in a    # first longest
    assert a == sorted(a)
    assert keep(8, lengths) != a or keep(9, lengths) != a
    assert keep(7, lengths[:3]) == [0, 1, 2]
