"""One run of one cell: load, warm up, measure for ``seconds``, check the
answers against the plain reference, and build the result line.

Everything is found by name from ``BENCHMARK.json``: the configuration file,
the traffic mix (``traffic/<mix>.json``), the reader of each metric
(``metrics/<metric>.py``, a function ``read(run)``), the reference named by
the configuration (``references/<name>.py``) and the cell's limits
(``limits/<cell>.json``).

The served path is the program's: ``repro.launch.serve.publish`` (with its
serving hotness) → ``Orchestrator`` → ``serve.coldstart.restore_server``
→ ``ServerInstance.prefill`` → the first token.  A ``coldstart``
invocation claims a skeleton, restores the published snapshot into HBM,
prefills the batch and reads the first tokens back, then frees the
instance.  A ``warm`` invocation prefills the same batch on a resident
server, whose weights are checked against the seed's after the window.  The benchmark's own spans (``bench.*``) wrap each step, so a trace
can label the device's idle gaps.

A cell on several chips runs cold starts that are due together as one
burst: one thread per chip, started together, each restoring its instance
onto its own chip through the one orchestrator (so through one node page
server and its hot-chunk cache) and serving its prompt there.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import arrivals
import weights as wts
import work

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACED_SPAN = "bench.traced"


def load_module(path: Path, name: Optional[str] = None):
    name = name or path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# bench/trace.py, by path: ``import trace`` would find the standard library's
tracing = load_module(BENCH / "trace.py", "bench_trace")


# --------------------------------------------------------------------------
# what the cell is
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    root: Path                 # the checkout: BENCHMARK.json and bench/
    chips: int
    cfg: dict                  # the configuration file
    mix: dict                  # the traffic mix file
    end_to_end: List[dict]     # the cell's metric entries
    per_layer: List[dict]
    limits: Dict[str, float]


def _load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


def _reports(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = [m["name"] for m in e2e]
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    here = root / "bench"
    return Cell(name, root, w["chips"], _load_json(root / cfg_entry["file"]),
                arrivals.load_mix(here / "traffic" / f"{w['traffic']}.json"),
                e2e, layer, _load_json(here / "limits" / f"{name}.json"))


def reader(metric: str, root: Path = ROOT) -> Callable:
    return load_module(Path(root) / "bench" / "metrics" / f"{metric}.py").read


def peaks_for(kind: str) -> dict:
    table = _load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# --------------------------------------------------------------------------
# what a run records; the metric readers read this
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Invocation:
    length: int                # prompt tokens (prefill steps)
    latency_s: float           # from due to the first tokens read back
    restore_s: Optional[float]
    first_token_s: float
    traced: bool


@dataclasses.dataclass
class Run:
    cell: Cell
    peak: dict
    setup_s: float
    window_s: float
    invocations: List[Invocation]
    counters: Dict[str, float]
    image_pages: int
    step: work.StepWork
    memory_peak_bytes: Optional[int]
    reduction: Optional[tracing.Reduction] = None

    @property
    def kind(self) -> str:
        return self.cell.mix["invocation"]


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

def on_device(device):
    """JAX's default device for this thread is ``device`` inside the block;
    ``None`` leaves it as it is."""
    import jax

    return contextlib.nullcontext() if device is None else jax.default_device(device)


class Program:
    """The program's served path for one configuration, on ``devices``: a
    skeleton pool built on each (so that the instances restored from it, and
    their decode steps, live there) and one orchestrator for all of them.
    Without ``devices``, one pool on the default device."""

    def __init__(self, cfg: dict, mix: dict, devices: Optional[list] = None):
        import jax

        from repro.launch import serve
        from repro.serve.coldstart import SkeletonPool

        p = cfg["program"]
        mc = serve.model_config(p["arch"], published_widths=p["published_widths"],
                                layers=p["layers"], dtype=p["dtype"])
        mc = dataclasses.replace(mc, **p["overrides"])
        _check_widths(mc, cfg)
        self.mc = mc
        self.serve = serve
        self.kind = mix["invocation"]
        self.devices = list(devices) if devices else [None]
        self.pools = []
        for d in self.devices:
            with on_device(d):
                self.pools.append(SkeletonPool(mc, batch=mix["batch"],
                                               max_len=mix["max_len"],
                                               target_size=1, background=False))
        self.sp = self.pools[0]
        self.template = jax.eval_shape(self.sp.model.init, jax.random.PRNGKey(0))
        self.orch = self.node = None
        self.master = None
        self.server = None
        self.pub: Dict[str, int] = {}
        self.dispatch_s = 0.0      # a warm prefill's return, before its read-back

    # -- set-up ------------------------------------------------------------
    def publish(self, params) -> None:
        from repro.core import Orchestrator
        from repro.core.nodeserver import NodePageServer
        from repro.kernels.snapshot_fuse.ops import default_publish_fn

        self.master, image, self.pub = self.serve.publish(
            self.mc, params, self.sp.claim().caches, default_publish_fn())
        del image
        # the host's one node page server, as the orchestrator would build it
        self.node = NodePageServer("bench-host", self.master.pool, buffer_pool_pages=256)
        self.orch = Orchestrator("bench-host", self.master.pool, self.master.catalog,
                                 node_server=self.node)

    def make_resident(self, params) -> None:
        from repro.serve.engine import ServerInstance

        sk = self.sp.claim()
        self.server = ServerInstance(sk.model, params, sk.caches, sk.max_len)

    def counters(self) -> Dict[str, int]:
        """The restore's counters so far: the scatter's ``stats``, and the
        node page server's hot-chunk cache reads, fan-out hits and fan-out
        installs."""
        if self.orch is None:
            return {}
        out = dict(getattr(self.orch.scatter_fn, "stats", {}))
        out["chunk_cache_reads"] = self.node.chunks.stats["reads"]
        out["fanout_hits"] = self.node.chunks.stats["fanout_hits"]
        out["fanout_installs"] = self.node.stats["fanout_installs"]
        return out

    def invoke(self, prompts: List[np.ndarray]) -> List[tuple]:
        """Serve ``prompts[j]`` as one invocation each, the j-th on device
        j.  One runs in the calling thread; several run as a burst, a thread
        each, started together.  Returns per invocation what it returned, or
        the exception it raised, and the host clock when it ended."""
        call = self.coldstart if self.kind == "coldstart" else self.warm

        def one(j: int) -> tuple:
            try:
                out = call(prompts[j], j)
            except Exception as e:   # an invocation that fails counts as failed
                out = e
            return out, time.perf_counter()

        if len(prompts) == 1:
            return [one(0)]
        done: List[Optional[tuple]] = [None] * len(prompts)
        start = threading.Barrier(len(prompts))

        def run(j: int) -> None:
            with on_device(self.devices[j]):
                start.wait()
                done[j] = one(j)

        threads = [threading.Thread(target=run, args=(j,), name=f"bench-burst-{j}")
                   for j in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return done

    # -- one invocation -------------------------------------------------------
    # Prompts arrive as host token ids, as a server receives them; each
    # prefill step takes its column from the host array.
    def coldstart(self, prompts: np.ndarray, j: int = 0):
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        from repro.serve.coldstart import restore_server

        sk = self.pools[j].claim()
        t0 = time.perf_counter()
        with TraceAnnotation("bench.restore"):
            out = restore_server(self.orch, self.mc.name, sk, self.template)
            server = out["instance"]
            jax.block_until_ready(server.params)
        t1 = time.perf_counter()
        with TraceAnnotation("bench.first_token"):
            logits = server.prefill(prompts)
            tokens = np.asarray(jnp.argmax(logits, axis=-1))
        t2 = time.perf_counter()
        return server, logits, tokens, t1 - t0, t2 - t1

    def warm(self, prompts: np.ndarray, j: int = 0):
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        self.server.pos = 0
        t0 = time.perf_counter()
        with TraceAnnotation("bench.first_token"):
            logits = self.server.prefill(prompts)
            self.dispatch_s = time.perf_counter() - t0
            tokens = np.asarray(jnp.argmax(logits, axis=-1))
        return self.server, logits, tokens, None, time.perf_counter() - t0

    def release(self, server) -> None:
        """Free a cold-started instance, as a platform reclaims one it does
        not keep warm."""
        import jax

        for x in jax.tree.leaves((server.params, server.caches)):
            x.delete()

    def close(self) -> None:
        if self.orch is not None:
            self.orch.close()
            self.node.close()
        for sp in self.pools:
            sp.close()
        self.orch = self.node = self.master = self.server = None


def _check_widths(mc, cfg: dict) -> None:
    """The program's model has the configuration file's shapes."""
    want = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "vocab": "vocab_size", "n_layers": "num_hidden_layers",
            "d_ff": "intermediate_size"}
    bad = {k: (getattr(mc, k), cfg[v]) for k, v in want.items()
           if getattr(mc, k) != cfg[v]}
    if mc.tie_embeddings != cfg["tie_word_embeddings"]:
        bad["tie_embeddings"] = (mc.tie_embeddings, cfg["tie_word_embeddings"])
    if float(mc.rope_theta) != float(cfg["rope_theta"]):
        bad["rope_theta"] = (mc.rope_theta, cfg["rope_theta"])
    if bad:
        raise ValueError(f"the program's model differs from {cfg['name']}: {bad}")


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

class CompileCounter:
    """Counts programs compiled (persistent-cache misses) and programs
    loaded from the persistent cache."""

    def __init__(self):
        import jax

        self.n = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, name, **_):
        if name == "/jax/compilation_cache/cache_misses":
            self.n += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peak: dict, log=print) -> dict:
    """Set up, measure, check.  Returns the result line as a dict."""
    import jax

    devices = jax.devices()[:cell.chips]
    dev = devices[0]
    compiles = CompileCounter()
    cfg, mix = cell.cfg, cell.mix
    kind = mix["invocation"]
    if kind not in ("coldstart", "warm"):
        raise ValueError(f"unknown invocation kind {kind!r}")
    if kind == "warm" and cell.chips > 1:
        raise ValueError("a warm cell serves from one resident server, on one chip")
    prog = Program(cfg, mix, devices if cell.chips > 1 else None)
    vocab = cfg["vocab_size"]

    # -- set-up: weights from the seed, publish or make resident, warm up ----
    params = wts.make(prog.template, seed)
    ref_digest = wts.digest(params)
    if kind == "coldstart":
        prog.publish(params)
        jax.tree.map(lambda x: x.delete(), params)
        # one cold start on each chip, together, as the window runs them
        warmup = [_warmup_prompts(mix, vocab)] * len(prog.devices)
        for j, (out, _) in enumerate(prog.invoke(warmup)):
            if isinstance(out, Exception):
                raise out
            if len(prog.devices) > 1:
                with on_device(prog.devices[j]):    # the window's digest there
                    wts.digest(out[0].params)
            prog.release(out[0])
        del out
    else:
        prog.make_resident(params)
        prog.warm(_warmup_prompts(mix, vocab))
    del params
    gc.collect()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s ({compiles.n} compiled, {compiles.hits} from the "
        f"persistent cache) on {len(prog.devices)} chip(s); published "
        f"{prog.pub or 'nothing'}")

    # -- the window -----------------------------------------------------------
    before = prog.counters()
    compiles_before = compiles.n + compiles.hits
    due = arrivals.arrivals(mix, seed, seconds, cell.chips)
    invs: List[Invocation] = []
    kept = Sample(int(mix["check_invocations"]), seed)
    digests: List[np.ndarray] = []
    failed = 0
    bursts = 0
    tdir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace else None
    n_trace = int(mix["trace_invocations"]) if trace else 0
    traced = _Tracer(tdir) if trace else None
    w0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - w0
        k = 1                      # invocations due together, one per chip
        if due is None:
            if now >= seconds:
                break
            t_due = now
        else:
            if i >= due.size:
                break
            t_due = float(due[i])
            while (k < len(prog.devices) and i + k < due.size
                   and due[i + k] == due[i]):
                k += 1
            if t_due > now:
                time.sleep(t_due - now)
        lengths = [arrivals.prompt_length(mix, i + j) for j in range(k)]
        ps = [arrivals.prompts(mix, seed, i + j, n, vocab) for j, n in enumerate(lengths)]
        is_traced = i < n_trace
        if is_traced and i == 0:
            traced.start()
        done = prog.invoke(ps)
        if is_traced and i + k >= n_trace:
            traced.stop()
        bursts += k > 1
        if len(done) < k:          # due, and never answered: failed
            failed += k - len(done)
            log(f"invocations {i + len(done)}..{i + k - 1} were never served")
        for j, (out, t_done) in enumerate(done):
            n = i + j
            if isinstance(out, Exception):
                failed += 1
                log(f"invocation {n} failed: {type(out).__name__}: {out}")
                continue
            server, logits, tokens, restore_s, first_s = out
            # a closed loop times the invocation itself, an open loop from its
            # due time, so that a stall also counts against the requests
            # behind it
            latency = ((restore_s or 0.0) + first_s if due is None
                       else t_done - w0 - t_due)
            invs.append(Invocation(lengths[j], latency, restore_s, first_s, is_traced))
            kept.offer(n, (ps[j], tokens, logits))
            if kind == "coldstart":
                with on_device(prog.devices[j]):
                    digests.append(wts.digest(server.params))
                prog.release(server)
                log(f"invocation {n}: {lengths[j]} tokens, restore {restore_s:.4f} s, "
                    f"first token {first_s:.4f} s, latency {latency:.4f} s")
            else:
                log(f"invocation {n}: {lengths[j]} tokens, dispatched in "
                    f"{prog.dispatch_s:.4f} s, first token {first_s:.4f} s")
            del server, logits, out
        del done
        i += k
    window_s = time.perf_counter() - w0
    if traced is not None and traced.on:
        traced.stop()
    after = prog.counters()
    if kind == "warm":
        digests.append(wts.digest(prog.server.params))
    n_compiles = compiles.n + compiles.hits - compiles_before
    log(f"window {window_s:.3f} s: {len(invs)} invocations, {failed} failed, "
        f"{n_compiles} programs compiled or loaded inside the window")

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    mem_peak = max((b for b in peaks if b is not None), default=None)
    counters = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    counters["restores"] = sum(1 for v in invs if v.restore_s is not None)
    if kind == "coldstart":
        counters["bursts"] = bursts
        log(f"hot chunks: {counters['chunk_cache_reads']} read through the cache, "
            f"{counters['fanout_hits']} handed over, by {counters['restores']} "
            f"restores in {bursts} bursts; peak HBM per chip {peaks}")
    run = Run(cell, peak, setup_s, window_s, invs, counters,
              work.image_pages(prog.template),
              work.step(prog.template, mix["batch"]), mem_peak)
    if traced is not None:
        ev = tracing.load(tdir)
        log(f"trace: {tracing.summary(ev)}")
        run.reduction = tracing.reduce(ev, *tracing.window(ev, TRACED_SPAN))
        shutil.rmtree(tdir, ignore_errors=True)

    # -- free the program's state, then check against the reference ----------
    template = prog.template
    prog.close()
    del prog
    gc.collect()
    t_check = time.perf_counter()
    outs = [(p, t, np.asarray(lg)[:, :vocab]) for p, t, lg in kept.items()]
    del kept
    checks = check(cell, wts.make(template, seed), ref_digest, digests, outs)
    log(f"check against the reference {time.perf_counter() - t_check:.3f} s")
    correct = failed == 0 and len(invs) > 0 and passes(checks)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": len(invs) + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if run.reduction is not None:
        device["busy_s"] = run.reduction.busy_s
        device["window_s"] = run.reduction.window_s
        result["breakdown"] = tracing.breakdown(run.reduction)
    result["checks"] = checks
    log(f"peak host RSS {_max_rss_kib()} KiB")
    return result


def _max_rss_kib() -> int:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss


class _Tracer:
    def __init__(self, directory: Path):
        self.directory = directory
        self.on = False
        self._cm = None
        self._span = None

    def start(self):
        from jax.profiler import TraceAnnotation

        self._cm = tracing.record(self.directory)
        self._cm.__enter__()
        self._span = TraceAnnotation(TRACED_SPAN)
        self._span.__enter__()
        self.on = True

    def stop(self):
        self._span.__exit__(None, None, None)
        self._cm.__exit__(None, None, None)
        self.on = False


def _warmup_prompts(mix: dict, vocab: int) -> np.ndarray:
    """Set-up's invocation, from a fixed seed: the mix's shortest prompt,
    since the prefill runs one program of one token whatever the length."""
    return arrivals.prompts(mix, 0, 0, int(mix["prompt_len"]["min"]), vocab)


# --------------------------------------------------------------------------
# correct: the plain reference against what the window served
# --------------------------------------------------------------------------

def reference(cell: Cell):
    return _module(cell.root / "bench" / "references" / f"{cell.cfg['reference']}.py")


@functools.lru_cache(maxsize=None)
def _module(path: Path):
    """Loaded once, so that its jitted functions keep their compiled programs."""
    return load_module(path)


class Sample:
    """The window's answers kept for the check: the first invocation with the
    longest prompt, and a reservoir of ``k - 1`` others drawn from the seed.
    Their logits stay on the device until the window has closed, so the
    window holds no downloads between invocations."""

    def __init__(self, k: int, seed: int):
        self.k = max(0, k - 1)
        self.rng = np.random.default_rng(np.random.SeedSequence((int(seed), 0xC4)))
        self.longest: Optional[tuple] = None
        self.pool: List[tuple] = []
        self.seen = 0

    def offer(self, i: int, answer: tuple) -> None:
        if self.longest is None or answer[0].shape[1] > self.longest[1][0].shape[1]:
            if self.longest is not None:
                self._reservoir(*self.longest)
            self.longest = (i, answer)
            return
        self._reservoir(i, answer)

    def _reservoir(self, i: int, answer: tuple) -> None:
        self.seen += 1
        if len(self.pool) < self.k:
            self.pool.append((i, answer))
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            self.pool[j] = (i, answer)

    def items(self) -> List[tuple]:
        both = self.pool + ([self.longest] if self.longest is not None else [])
        return [a for _, a in sorted(both, key=lambda ia: ia[0])]


def reference_logits(cell: Cell, params, prompts: np.ndarray) -> np.ndarray:
    """The reference's logits ``(B, vocab)`` after each row of ``prompts``:
    padded to a power of two of at least one block, so that a run compiles
    the reference for a few lengths only."""
    import jax.numpy as jnp

    ref = reference(cell)
    length = prompts.shape[1]
    size = max(ref.BLOCK, 1 << (length - 1).bit_length())
    padded = np.zeros((prompts.shape[0], size), np.int32)
    padded[:, :length] = prompts
    return np.asarray(ref.last_logits(ref.Arch.from_config(cell.cfg), params,
                                      jnp.asarray(padded), jnp.int32(length)))


def passes(checks: Dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def check(cell: Cell, params, ref_digest: np.ndarray,
          digests: List[np.ndarray], outs: List[tuple]) -> Dict[str, dict]:
    """Each number compared, beside its limit.

    ``params_differ``: weight arrays, restored (coldstart) or served through
    the window (warm), whose bits differ from the benchmark's (exact).
    ``token_gap``: the widest gap by which a served token's reference logit
    lies below the reference's best.
    ``logit_err``: the largest ``max|served - reference| / max|reference|``
    over the served rows' logits; ``logit_err_median``: its median over the
    rows.  A number is compared where the cell's limits file gives it a
    limit."""
    checks: Dict[str, dict] = {}
    if digests:
        differ = int(sum(int(np.count_nonzero(d != ref_digest)) for d in digests))
        checks["params_differ"] = {"value": differ,
                                   "limit": cell.limits["params_differ"]}
    gap = 0.0
    errs: List[float] = []
    for prompts, tokens, served in outs:
        want = reference_logits(cell, params, prompts)
        rows = np.arange(want.shape[0])
        gap = max(gap, float((want.max(-1) - want[rows, tokens]).max()))
        errs.extend((np.abs(served - want).max(-1) / np.abs(want).max(-1)).tolist())
    readings = {"token_gap": gap, "logit_err": max(errs, default=0.0),
                "logit_err_median": float(np.median(errs)) if errs else 0.0}
    for name, value in readings.items():
        if name in cell.limits:
            checks[name] = {"value": value, "limit": cell.limits[name]}
    return checks


def log_stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
