"""What decides a run's ``correct``, driven end to end on the CPU at a tiny
size: a sound run passes, each fault planted under the timed path fails it,
and the control (the reference in float8) fails the cell's limits.

The harness's look for a chip is skipped: these call ``harness.run_cell``
directly with the tiny configuration beside this file, the real cells'
traffic at prompt lengths a test run can hold, and the real cells' limits."""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "bench"))

import control  # noqa: E402
import harness  # noqa: E402

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
SEED = 2**31 + 4242
SECONDS = 0.3


def _tiny(cell_name: str) -> harness.Cell:
    cell = harness.load_cell(cell_name)
    cell.cfg = json.loads((HERE / "tiny-dense.json").read_text())
    cell.mix = dict(cell.mix, max_len=128,
                    prompt_len={"median": 24, "sigma": 0.6, "min": 4, "max": 64})
    return cell


def _run(cell):
    return harness.run_cell(cell, SEED, SECONDS, False, time.perf_counter(), PEAK,
                            log=lambda *_: None)


@pytest.mark.parametrize("cell_name", ["mistral-coldstart", "mistral-warm"])
def test_a_sound_run_is_correct(cell_name):
    res = _run(_tiny(cell_name))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in _tiny(cell_name).end_to_end
                                   if m["name"] != "peak_hbm_gib"}


def _install_nothing(mp):
    """A restore step that returns the instance's memory unchanged."""
    from repro.core.serving import RestoreEngine
    mp.setattr(RestoreEngine, "install_all_sync", lambda self, use_batch=True: None)


def _install_half(mp):
    """Half of each batch of pages left out."""
    from repro.core.serving import Instance
    orig = Instance.uffd_copy_batch

    def half(self, pages, mat):
        k = max(1, len(pages) // 2)
        return orig(self, pages[:k], mat[:k])
    mp.setattr(Instance, "uffd_copy_batch", half)


def _alter_a_restored_page(mp):
    """One restored weight altered where the restore produces it."""
    from repro.core.pagestore import StateImage
    orig = StateImage.read_array

    def altered(self, name):
        a = orig(self, name).copy()
        if name.endswith("wo"):
            a.reshape(-1).view(np.uint8)[7] ^= 0x10
        return a
    mp.setattr(StateImage, "read_array", altered)


def _alter_the_token(mp):
    """A served token altered where the prefill produces it."""
    from repro.serve.engine import ServerInstance
    orig = ServerInstance.prefill

    def altered(self, tokens):
        logits = orig(self, tokens)
        return logits.at[:, 5].set(logits.max() + 1.0)
    mp.setattr(ServerInstance, "prefill", altered)


def _step_keeps_its_state(mp):
    """Each decode step returns the caches it was given."""
    from repro.serve import engine
    orig = engine._decode_jit

    def frozen(model):
        step = orig(model)
        return lambda params, tokens, caches, pos: (step(params, tokens, caches, pos)[0],
                                                    caches)
    mp.setattr(engine, "_decode_jit", frozen)


def _weights_change_while_served(mp):
    """The resident server's weights written over in place as it serves."""
    from repro.serve.engine import ServerInstance
    orig = ServerInstance.prefill

    def writes(self, tokens):
        logits = orig(self, tokens)
        norm = self.params["final_norm"]
        norm["scale"] = norm["scale"] + 2.0 ** -5
        return logits
    mp.setattr(ServerInstance, "prefill", writes)


@pytest.mark.parametrize("cell_name,fault", [
    ("mistral-coldstart", _install_nothing),
    ("mistral-coldstart", _install_half),
    ("mistral-coldstart", _alter_a_restored_page),
    ("mistral-coldstart", _alter_the_token),
    ("mistral-warm", _step_keeps_its_state),
    ("mistral-warm", _weights_change_while_served),
    ("mistral-warm", _alter_the_token),
])
def test_a_fault_under_the_timed_path_is_not_correct(cell_name, fault, monkeypatch):
    fault(monkeypatch)
    res = _run(_tiny(cell_name))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell_name", ["mistral-coldstart", "mistral-warm"])
def test_the_control_is_not_correct(cell_name):
    """The reference with float8 weights in the program's place, judged by
    ``harness.check`` as a run is, comes out not correct on three seeds."""
    cell = _tiny(cell_name)
    prog = harness.Program(cell.cfg, cell.mix)
    template = prog.template
    prog.close()
    for seed in (SEED, SEED + 1, SEED + 2):
        r = control.run(cell, seed, template, invocations=3)
        assert not r["correct"], r["checks"]
