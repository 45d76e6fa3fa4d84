"""The one traffic generator: a mix file (``traffic/<mix>.json``) and a seed
give the schedule and the prompts of a run.

Every seed serves the same sequence of prompt lengths: invocation ``i``
takes the lognormal's (``median``, ``sigma``, clipped to ``min``..``max``)
quantile at the ``i``-th point of the golden-ratio sequence, so that every
prefix of the sequence spreads over the whole distribution, and a window
that holds three invocations serves a low, a middle and a high length.  Two
seeds do the same work and differ only in their token ids.  A closed loop (``"loop": "closed"``) sends the next
invocation when the previous one has answered.  An open loop names an
arrival process under ``"arrivals"``; :func:`poisson_arrivals` and
:func:`onoff_arrivals` are copied from ``repro.fleet.arrivals`` so that the
yardstick does not move when that module changes.  A ``"burst"`` process
makes one invocation per chip of the cell due together every ``period_s``
seconds, from t = 0, the same for every seed.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np


MIX_KEYS = {"name", "invocation", "loop", "batch", "max_len", "prompt_len",
            "arrivals", "trace_invocations", "check_invocations", "source", "why"}
GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"{path}: the harness reads no {sorted(unknown)}")
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    if mix["loop"] == "open" and "arrivals" not in mix:
        raise ValueError(f"{path}: an open loop needs 'arrivals'")
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence((int(seed), *stream)))


def prompt_length(mix: dict, i: int) -> int:
    """Invocation ``i``'s prompt length."""
    spec = mix["prompt_len"]
    u = min(max((0.5 + i * GOLDEN) % 1.0, 1e-9), 1.0 - 1e-9)
    n = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
    return int(min(max(round(n), spec["min"]), spec["max"]))


def prompts(mix: dict, seed: int, i: int, length: int, vocab: int) -> np.ndarray:
    """Invocation ``i``'s prompts: ``(batch, length)`` int32 token ids."""
    rng = _rng(seed, 0x70, i)
    return rng.integers(0, vocab, (mix["batch"], int(length)), dtype=np.int32)


def arrivals(mix: dict, seed: int, t_end: float,
             chips: int = 1) -> Optional[np.ndarray]:
    """Due times (seconds from the window's start) of an open loop, None for
    a closed loop, in a cell on ``chips`` chips."""
    if mix["loop"] == "closed":
        return None
    spec = mix["arrivals"]
    rng = _rng(seed, 0xA7)
    if spec["process"] == "poisson":
        return poisson_arrivals(rng, spec["rate_rps"], t_end)
    if spec["process"] == "burst":
        return burst_arrivals(chips, spec["period_s"], t_end)
    if spec["process"] == "onoff":
        return onoff_arrivals(rng, spec["rate_rps"], t_end,
                              spec.get("mean_on_s", 2.0), spec.get("mean_off_s", 8.0))
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def burst_arrivals(size: int, period_s: float, t_end: float) -> np.ndarray:
    """``size`` due times at each of 0, ``period_s``, 2 ``period_s``, ...
    before ``t_end``."""
    starts = np.arange(0.0, t_end, float(period_s))
    return np.repeat(starts, int(size))


def poisson_arrivals(rng: np.random.Generator, rate_rps: float,
                     t_end: float, t_start: float = 0.0) -> np.ndarray:
    """Homogeneous Poisson: N ~ Poisson(rate * window), times uniform."""
    window = max(0.0, t_end - t_start)
    n = int(rng.poisson(rate_rps * window))
    if n == 0:
        return np.zeros(0, np.float64)
    return np.sort(rng.uniform(t_start, t_end, n))


def onoff_arrivals(rng: np.random.Generator, rate_rps: float, t_end: float,
                   mean_on_s: float = 2.0, mean_off_s: float = 8.0,
                   t_start: float = 0.0) -> np.ndarray:
    """Markov-modulated ON/OFF bursts whose long-run mean rate is
    ``rate_rps``: exponential ON windows at ``rate / duty`` separated by
    exponential OFF silences."""
    duty = mean_on_s / (mean_on_s + mean_off_s)
    on_rate = rate_rps / max(duty, 1e-9)
    window = max(0.0, t_end - t_start)
    n_pairs = max(4, int(window / (mean_on_s + mean_off_s) * 3) + 4)
    on_len = rng.exponential(mean_on_s, n_pairs)
    off_len = rng.exponential(mean_off_s, n_pairs)
    start_on = bool(rng.uniform() < duty)
    durations = np.empty(2 * n_pairs)
    durations[0::2], durations[1::2] = (on_len, off_len) if start_on else (off_len, on_len)
    edges = t_start + np.concatenate(([0.0], np.cumsum(durations)))
    out: List[np.ndarray] = []
    on_slots = range(0, 2 * n_pairs, 2) if start_on else range(1, 2 * n_pairs, 2)
    for i in on_slots:
        a, b = edges[i], min(edges[i + 1], t_end)
        if a >= t_end:
            break
        if b > a:
            out.append(poisson_arrivals(rng, on_rate, b, a))
    if not out:
        return np.zeros(0, np.float64)
    return np.sort(np.concatenate(out))
