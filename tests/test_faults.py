"""Fault-tolerant page serving (DESIGN.md §15).

Covers the production fault seam end to end: the deterministic
``FaultInjector`` schedules (and their parity with the sim's reference
``FlakyTier``), ``call_with_retries`` backoff behaviour, the ``TierHealth``
circuit breaker, checksum repair with dedup-store quarantine, CXL-brownout
degradation, and the fleet scheduler's health de-scoring.

Two property guarantees (hypothesis; the conftest fallback keeps them
running without it):

* a fixed seed + fault schedule yields an IDENTICAL retry/sleep trace and
  backoff ledger under ``VirtualClock`` — fault handling is replayable;
* a zero-fault schedule (injector armed but empty) leaves every cost
  ledger byte-identical to running with no injector at all — the
  fault-free overhead of the seam is exactly zero modeled seconds.
"""
import random

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    FaultInjector,
    HierarchicalPool,
    Instance,
    PoolMaster,
    RestoreEngine,
    RetryPolicy,
    SnapshotReader,
    StateImage,
    TierFaultError,
    TierHealth,
    TimeLedger,
    call_with_retries,
)
from repro.core.pagestore import PAGE_SIZE
from repro.core.serving import AsyncRDMAEngine
from repro.fleet.arrivals import FunctionType
from repro.fleet.model import RestoreProfile
from repro.fleet.placement import HostState, PlacementScheduler
from repro.kernels.snapshot_fuse import FusedScatter, make_fused_publish_fn
from repro.kernels.snapshot_fuse.ops import ChecksumMismatchError
from repro.sim import FlakyTier, VirtualClock

CLASSES = ("hot",) * 4 + ("cold",) * 4 + ("zero",) * 2


def build_layout(classes=CLASSES, fill_seed=0):
    n = len(classes)
    rng = np.random.default_rng(fill_seed + 1000 * n)
    buf = np.zeros(n * PAGE_SIZE, dtype=np.uint8)
    for i, cls in enumerate(classes):
        if cls == "zero":
            continue
        page = rng.integers(0, 256, size=PAGE_SIZE, dtype=np.uint8)
        page[0] = max(1, int(page[0]))
        buf[i * PAGE_SIZE : (i + 1) * PAGE_SIZE] = page
    img = StateImage.build({"blob": buf})
    ws = [i for i, cls in enumerate(classes) if cls == "hot"]
    return img, ws


def publish_stack(classes=CLASSES, fused=False, fill_seed=0):
    img, ws = build_layout(classes, fill_seed)
    pool = HierarchicalPool(64 << 20, 64 << 20)
    master = PoolMaster(pool)
    pf = make_fused_publish_fn(use_pallas=False) if fused else None
    master.publish("snap", img, ws, publish_fn=pf)
    borrow = master.catalog.borrow("snap")
    assert borrow is not None
    return img, pool, borrow


def run_restore(img, pool, borrow, host="h", scatter_fn=None, clock=None):
    view = pool.host_view(host)
    reader = SnapshotReader(borrow.regions, view, pool.rdma)
    reader.invalidate_cxl()
    inst = Instance(StateImage.empty_like(img.manifest), clock=clock)
    engine = RestoreEngine(reader, inst, None, scatter_fn=scatter_fn,
                           clock=clock)
    engine.install_all_sync(use_batch=True)
    return view, reader, inst, engine


# -- FaultInjector schedules --------------------------------------------------

class TestFaultInjector:
    def test_read_windows_count_and_bound(self):
        inj = FaultInjector(seed=1).fail_reads("rdma", 2, lo=PAGE_SIZE,
                                               hi=3 * PAGE_SIZE)
        # outside the byte window: clean
        inj.check_read("rdma", 0, PAGE_SIZE)
        # wrong tier: clean even inside the window
        inj.check_read("cxl", PAGE_SIZE, PAGE_SIZE)
        for _ in range(2):
            with pytest.raises(TierFaultError) as ei:
                inj.check_read("rdma", PAGE_SIZE, PAGE_SIZE)
            assert ei.value.kind == "timeout" and ei.value.tier == "rdma"
        inj.check_read("rdma", PAGE_SIZE, PAGE_SIZE)   # window drained
        assert inj.stats["injected_timeouts"] == 2
        assert inj.stats["reads"] == 5

    def test_write_faults_symmetric_to_reads(self):
        inj = FaultInjector(seed=1).fail_writes("cxl", 1)
        with pytest.raises(TierFaultError) as ei:
            inj.check_write("cxl", 0, PAGE_SIZE)
        assert ei.value.kind == "write"
        inj.check_write("cxl", 0, PAGE_SIZE)
        assert inj.stats["injected_write_faults"] == 1
        assert inj.stats["writes"] == 2

    def test_poison_corrupts_only_window_page_of_returned_copy(self):
        inj = FaultInjector(seed=1).poison_reads(
            "cxl", 1, lo=PAGE_SIZE, hi=2 * PAGE_SIZE)
        data = np.zeros(3 * PAGE_SIZE, dtype=np.uint8)
        hit = inj.filter_read("cxl", 0, data.nbytes, data)
        assert hit
        # exactly the page overlapping [lo, hi) was flipped, in place
        assert data[PAGE_SIZE] == 0xFF
        assert data[0] == 0 and data[2 * PAGE_SIZE] == 0
        assert int(np.count_nonzero(data)) == 1
        assert inj.stats["injected_poison"] == 1
        # window consumed: the re-read comes back clean (repairable)
        clean = np.zeros(3 * PAGE_SIZE, dtype=np.uint8)
        assert not inj.filter_read("cxl", 0, clean.nbytes, clean)

    def test_completion_errors(self):
        inj = FaultInjector(seed=1).fail_completions("rdma", 1)
        with pytest.raises(TierFaultError) as ei:
            inj.check_completion("rdma")
        assert ei.value.kind == "completion"
        inj.check_completion("rdma")
        assert inj.stats["injected_completion_errors"] == 1

    def test_brownout_hits_host_link_reads_only(self):
        clock = VirtualClock()
        inj = FaultInjector(clock=clock, seed=0).brownout(
            "cxl", start_s=1.0, duration_s=2.0)
        assert not inj.in_brownout("cxl")
        inj.check_read("cxl", 0, PAGE_SIZE, host_link=True)   # before window
        clock.advance(1.5)
        assert inj.in_brownout("cxl")
        with pytest.raises(TierFaultError) as ei:
            inj.check_read("cxl", 0, PAGE_SIZE, host_link=True)
        assert ei.value.kind == "brownout"
        # the owner-side pool-fabric path is NOT browned out
        inj.check_read("cxl", 0, PAGE_SIZE, host_link=False)
        clock.advance(2.0)
        assert not inj.in_brownout("cxl")
        inj.check_read("cxl", 0, PAGE_SIZE, host_link=True)   # after window
        assert inj.stats["brownout_rejections"] == 1


# -- FlakyTier is the reference implementation (satellite: parity) ------------

class TestFlakyTierParity:
    @staticmethod
    def _access_seq(rng, n=24):
        return [(int(rng.integers(0, 8)) * PAGE_SIZE,
                 int(rng.integers(1, 3)) * PAGE_SIZE) for _ in range(n)]

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=25, deadline=None)
    def test_read_fault_pattern_matches_reference(self, seed):
        rng = np.random.default_rng(seed)
        windows = [(int(rng.integers(1, 4)),
                    int(rng.integers(0, 4)) * PAGE_SIZE,
                    int(rng.integers(4, 9)) * PAGE_SIZE)
                   for _ in range(int(rng.integers(1, 3)))]
        seq = self._access_seq(rng)

        pool = HierarchicalPool(16 << 20, 16 << 20)
        flaky = FlakyTier(pool.rdma)
        inj = FaultInjector(seed=seed)
        for n, lo, hi in windows:
            flaky.fail_reads(n, lo, hi)
            inj.fail_reads("rdma", n, lo, hi)

        def mask(fn):
            out = []
            for off, nb in seq:
                try:
                    fn(off, nb)
                    out.append(False)
                except TierFaultError:
                    out.append(True)
            return out

        ref = mask(flaky.read)
        got = mask(lambda off, nb: inj.check_read("rdma", off, nb))
        assert got == ref
        assert inj.stats["injected_timeouts"] == flaky.stats["injected_timeouts"]
        assert inj.stats["reads"] == flaky.stats["reads"] == len(seq)

    def test_write_fault_pattern_matches_reference(self):
        pool = HierarchicalPool(16 << 20, 16 << 20)
        flaky = FlakyTier(pool.rdma).fail_writes(2, lo=PAGE_SIZE,
                                                 hi=3 * PAGE_SIZE)
        inj = FaultInjector(seed=0).fail_writes("rdma", 2, lo=PAGE_SIZE,
                                                hi=3 * PAGE_SIZE)
        page = np.ones(PAGE_SIZE, dtype=np.uint8)
        seq = [0, PAGE_SIZE, 2 * PAGE_SIZE, PAGE_SIZE, 4 * PAGE_SIZE]
        ref, got = [], []
        for off in seq:
            try:
                flaky.write(off, page)
                ref.append(False)
            except TierFaultError:
                ref.append(True)
            try:
                inj.check_write("rdma", off, page.nbytes)
                got.append(False)
            except TierFaultError:
                got.append(True)
        assert got == ref == [False, True, True, False, False]
        assert (inj.stats["injected_write_faults"]
                == flaky.stats["injected_write_faults"] == 2)
        assert inj.stats["writes"] == flaky.stats["writes"] == len(seq)


# -- retry/backoff ------------------------------------------------------------

class TestCallWithRetries:
    @staticmethod
    def _run_once(seed, n_faults):
        clock = VirtualClock()
        ledger = TimeLedger()
        trace = []
        left = [n_faults]

        def fn():
            if left[0] > 0:
                left[0] -= 1
                raise TierFaultError("injected", tier="rdma")
            return 42

        out = call_with_retries(fn, rng=random.Random(seed), ledger=ledger,
                                clock=clock, trace=trace)
        return out, tuple(trace), dict(ledger.seconds), clock.monotonic()

    @given(st.integers(0, 2**31 - 1), st.integers(0, 4))
    @settings(max_examples=30, deadline=None)
    def test_same_seed_same_schedule_identical_trace(self, seed, n_faults):
        a = self._run_once(seed, n_faults)
        b = self._run_once(seed, n_faults)
        assert a == b, "retry/sleep behaviour must replay bit-identically"
        out, trace, ledger, elapsed = a
        assert out == 42 and len(trace) == n_faults
        # every backoff is slept on the clock AND charged to the ledger
        assert elapsed == sum(trace)
        assert ledger.get("retry_backoff", 0.0) == sum(trace)

    def test_exhaustion_raises_after_max_retries(self):
        calls = [0]

        def fn():
            calls[0] += 1
            raise TierFaultError("always", tier="rdma")

        with pytest.raises(TierFaultError):
            call_with_retries(fn, rng=random.Random(0), clock=VirtualClock())
        assert calls[0] == RetryPolicy().max_retries + 1

    def test_brownout_is_never_retried(self):
        calls = [0]

        def fn():
            calls[0] += 1
            raise TierFaultError("dark", tier="cxl", kind="brownout")

        with pytest.raises(TierFaultError):
            call_with_retries(fn, rng=random.Random(0), clock=VirtualClock())
        assert calls[0] == 1, "the breaker degrades; retries must not hammer"

    def test_deadline_bounds_cumulative_backoff(self):
        policy = RetryPolicy(max_retries=100, base_backoff_s=1e-3,
                             jitter_frac=0.0, extent_deadline_s=4e-3)
        clock = VirtualClock()

        def fn():
            raise TierFaultError("slow", tier="rdma")

        with pytest.raises(TierFaultError):
            call_with_retries(fn, policy=policy, clock=clock)
        assert clock.monotonic() <= policy.extent_deadline_s

    def test_demand_faults_escalate(self):
        policy = RetryPolicy()
        assert (policy.backoff_s(0, urgent=True)
                < policy.backoff_s(0, urgent=False))
        assert policy.deadline_s(urgent=True) < policy.deadline_s(urgent=False)


class TestEngineRetry:
    def test_engine_retries_through_transient_faults(self):
        pool = HierarchicalPool(16 << 20, 16 << 20)
        want = np.arange(PAGE_SIZE, dtype=np.uint8) % 251
        pool.rdma.write(0, want)
        pool.rdma.fault_injector = FaultInjector(seed=1).fail_reads("rdma", 2)
        ledger = TimeLedger()
        eng = AsyncRDMAEngine(pool.rdma, ledger, start=False)
        buf = np.empty(PAGE_SIZE, dtype=np.uint8)
        eng._execute_read(1, 0, PAGE_SIZE, buf, ledger)
        np.testing.assert_array_equal(buf, want)
        assert eng.stats["retries"] == 2
        assert eng.stats["injected_faults"] == 2
        assert eng.stats["retry_exhausted"] == 0
        # wasted wire time and backoff are both charged to modeled time
        assert ledger.seconds.get("rdma_retry", 0.0) > 0.0
        assert ledger.seconds.get("retry_backoff", 0.0) > 0.0

    def test_engine_exhaustion_degrades_to_final_clean_read(self):
        pool = HierarchicalPool(16 << 20, 16 << 20)
        want = np.full(PAGE_SIZE, 7, dtype=np.uint8)
        pool.rdma.write(0, want)
        # more scheduled faults than the retry budget: the engine must not
        # spin forever — it finishes with one clean (uninjected) read
        pool.rdma.fault_injector = FaultInjector(seed=1).fail_reads("rdma", 99)
        eng = AsyncRDMAEngine(pool.rdma, TimeLedger(), start=False)
        buf = np.empty(PAGE_SIZE, dtype=np.uint8)
        eng._execute_read(1, 0, PAGE_SIZE, buf, eng.ledger)
        np.testing.assert_array_equal(buf, want)
        assert eng.stats["retry_exhausted"] == 1
        assert eng.stats["retries"] == eng.retry.max_retries


# -- TierHealth circuit breaker -----------------------------------------------

class TestTierHealth:
    def test_soft_failures_trip_at_threshold(self):
        ht = TierHealth("cxl", VirtualClock(), failure_threshold=3)
        for _ in range(2):
            ht.record_failure()
            assert ht.allow() and not ht.degraded
        ht.record_failure()
        assert not ht.allow() and ht.degraded
        assert ht.stats == {"failures": 3, "trips": 1, "probes": 0,
                            "recoveries": 0}

    def test_hard_failure_trips_immediately(self):
        ht = TierHealth("cxl", VirtualClock())
        ht.record_failure(hard=True)
        assert not ht.allow() and ht.state == TierHealth.OPEN

    def test_success_resets_soft_failure_count(self):
        ht = TierHealth("cxl", VirtualClock(), failure_threshold=2)
        ht.record_failure()
        ht.record_success()
        ht.record_failure()
        assert ht.allow(), "success between failures resets the count"

    def test_half_open_probe_then_recovery(self):
        clock = VirtualClock()
        ht = TierHealth("cxl", clock, cooldown_s=1e-3)
        ht.record_failure(hard=True)
        assert not ht.allow()
        clock.advance(1e-3)
        assert ht.allow() and ht.state == TierHealth.HALF_OPEN
        assert ht.stats["probes"] == 1
        ht.record_success()
        assert ht.state == TierHealth.CLOSED and not ht.degraded
        assert ht.stats["recoveries"] == 1

    def test_half_open_failure_reopens(self):
        clock = VirtualClock()
        ht = TierHealth("cxl", clock, cooldown_s=1e-3)
        ht.record_failure(hard=True)
        clock.advance(1e-3)
        assert ht.allow()                       # HALF_OPEN probe admitted
        ht.record_failure()                     # probe failed
        assert ht.state == TierHealth.OPEN and not ht.allow()
        assert ht.stats["trips"] == 2


# -- ChecksumMismatchError (satellite: structured payload + message) ----------

class TestChecksumMismatchError:
    def test_bad_pages_is_structured_int64(self):
        err = ChecksumMismatchError(np.array([5, 2], dtype=np.int32))
        assert err.bad_pages.dtype == np.int64
        assert err.bad_pages.tolist() == [5, 2]
        assert isinstance(err, RuntimeError)
        # scalar input is normalized to a 1-D array
        assert ChecksumMismatchError(3).bad_pages.tolist() == [3]
        # back-compat alias
        assert err.pages.tolist() == [5, 2]

    def test_message_is_readable_and_truncated(self):
        short = ChecksumMismatchError(np.arange(3))
        assert str(short) == "checksum mismatch on 3 restored page(s): [0, 1, 2]"
        long = ChecksumMismatchError(np.arange(100))
        msg = str(long)
        assert "100 restored page(s)" in msg
        assert str(ChecksumMismatchError.MAX_SHOWN - 1) in msg
        assert "(+92 more)" in msg
        assert "99" not in msg.split("(+")[0], "tail pages must be elided"


# -- zero-fault overhead: the armed seam charges nothing ----------------------

def _restore_ledgers(arm_injector, fill_seed=0):
    img, pool, borrow = publish_stack(fused=True, fill_seed=fill_seed)
    if arm_injector:
        # armed but EMPTY schedule: every read takes the check branches
        pool.attach_fault_injector(FaultInjector(seed=123))
    view, reader, inst, engine = run_restore(
        img, pool, borrow, scatter_fn=FusedScatter(use_pallas=False))
    assert inst.all_present()
    np.testing.assert_array_equal(inst.image.buf, img.buf)
    return (dict(inst.ledger.seconds), dict(view.ledger.seconds),
            dict(inst.stats), dict(engine.repair_stats))


@given(st.integers(0, 100))
@settings(max_examples=5, deadline=None)
def test_zero_fault_schedule_leaves_ledger_byte_identical(fill_seed):
    base = _restore_ledgers(arm_injector=False, fill_seed=fill_seed)
    armed = _restore_ledgers(arm_injector=True, fill_seed=fill_seed)
    assert armed == base, (
        "an armed-but-empty injector (and attached TierHealth breakers) "
        "must not change any modeled charge or counter")


# -- checksum repair ----------------------------------------------------------

class TestChecksumRepair:
    @pytest.mark.parametrize("memory", ["host", "device"])
    def test_poisoned_page_is_repaired_from_home_tier(self, memory):
        """A poisoned read fails the fused verify and is re-read clean —
        whether the instance's memory is a host image or a device page
        array (the kernel in the interpreter; a failed verify has already
        installed into, and donated, that array)."""
        img, pool, borrow = publish_stack(fused=True)
        probe = SnapshotReader(borrow.regions, pool.host_view("probe"),
                               pool.rdma)
        hot0 = int(probe.hot_page_indices()[0])
        _kind, off = probe.lookup(hot0)
        inj = FaultInjector(seed=3).poison_reads("cxl", 1, lo=off,
                                                 hi=off + PAGE_SIZE)
        pool.attach_fault_injector(inj)
        scatter = FusedScatter(use_pallas=memory == "device",
                               interpret=memory == "device")
        view, reader, inst, engine = run_restore(img, pool, borrow,
                                                 scatter_fn=scatter)
        assert inst.all_present()
        np.testing.assert_array_equal(inst.image_bytes(), img.buf)
        assert inj.stats["injected_poison"] == 1
        assert engine.repair_stats["checksum_mismatches"] == 1
        assert engine.repair_stats["checksum_repairs"] == 1
        assert engine.repair_stats["repair_failures"] == 0
        # the repair re-read is charged like a fresh demand read
        assert inst.ledger.seconds.get("cxl_read", 0.0) > 0.0

    @pytest.mark.parametrize("memory", ["host", "device"])
    def test_demand_fault_verifies_in_its_own_call(self, memory):
        """A hot page demand-faulted outside any bulk install is compared in
        the call that installs it: a poisoned read is caught and repaired
        before ``access`` returns, with one readback for the install and
        one for the repair."""
        img, pool, borrow = publish_stack(fused=True)
        reader = SnapshotReader(borrow.regions, pool.host_view("h"), pool.rdma)
        hot0 = int(reader.hot_page_indices()[0])
        _kind, off = reader.lookup(hot0)
        pool.attach_fault_injector(FaultInjector(seed=4).poison_reads(
            "cxl", 1, lo=off, hi=off + PAGE_SIZE))
        scatter = FusedScatter(use_pallas=memory == "device",
                               interpret=memory == "device")
        inst = Instance(StateImage.empty_like(img.manifest))
        engine = RestoreEngine(reader, inst, None, scatter_fn=scatter)
        engine.access(hot0)
        assert inst.present[hot0] and not inst.pending.any()
        assert engine.repair_stats["checksum_mismatches"] == 1
        assert engine.repair_stats["checksum_repairs"] == 1
        assert scatter.stats["verify_syncs"] == 2
        got = inst.image_bytes().reshape(-1, PAGE_SIZE)[hot0]
        np.testing.assert_array_equal(got, img.pages_matrix()[hot0])

    def test_at_rest_corruption_exhausts_repair_budget_and_surfaces(self):
        img, pool, borrow = publish_stack(fused=True)
        probe = SnapshotReader(borrow.regions, pool.host_view("probe"),
                               pool.rdma)
        hot0 = int(probe.hot_page_indices()[0])
        _kind, off = probe.lookup(hot0)
        # corrupt the pool bytes themselves: every budgeted re-read sees the
        # same bad content, so repair cannot succeed and must SURFACE
        pool.cxl.buf[off] ^= 0xFF
        view = pool.host_view("h")
        reader = SnapshotReader(borrow.regions, view, pool.rdma)
        reader.invalidate_cxl()
        inst = Instance(StateImage.empty_like(img.manifest))
        engine = RestoreEngine(reader, inst, None,
                               scatter_fn=FusedScatter(use_pallas=False))
        with pytest.raises(RuntimeError) as ei:
            engine.install_all_sync(use_batch=True)
        assert getattr(ei.value, "bad_pages", None) is not None
        assert engine.repair_stats["repair_failures"] == 1
        assert engine.repair_stats["checksum_repairs"] == 0


class TestQuarantine:
    @staticmethod
    def _dedup_stack():
        img, ws = build_layout(CLASSES, fill_seed=5)
        pool = HierarchicalPool(64 << 20, 64 << 20)
        master = PoolMaster(pool)
        master.publish("snap", img, ws, dedup=True,
                       publish_fn=make_fused_publish_fn(use_pallas=False))
        return pool, pool.dedup_cxl

    def test_quarantine_bars_sharing_without_touching_refs(self):
        pool, store = self._dedup_stack()
        off = min(store._hash_of)
        refs_before = store.refcounts()
        assert store.quarantine(off) is True
        assert store.quarantine(off) is False       # already quarantined
        assert store.quarantine(1 << 40) is False   # not a store offset
        assert store.quarantined_offsets() == [off]
        assert store.stats["quarantined"] == 1
        # I6: existing references are untouched by quarantine
        assert store.refcounts() == refs_before
        assert store.unique_pages() == len(refs_before)

    def test_rematerialize_verifies_content_hash(self):
        pool, store = self._dedup_stack()
        off = min(store._hash_of)
        clean = pool.cxl.buf[off : off + PAGE_SIZE].copy()
        store.quarantine(off)
        wrong = clean.copy()
        wrong[0] ^= 0xFF
        with pytest.raises(ValueError):
            store.rematerialize(off, wrong)
        store.rematerialize(off, clean)
        assert store.quarantined_offsets() == []
        assert store.stats["rematerialized"] == 1
        # un-quarantined offsets cannot be rematerialized
        with pytest.raises(ValueError):
            store.rematerialize(off, clean)


# -- brownout degradation -----------------------------------------------------

class TestBrownoutDegradation:
    def test_restore_degrades_to_rdma_only_and_stays_bit_identical(self):
        clock = VirtualClock()
        img, pool, borrow = publish_stack(fused=True)
        inj = FaultInjector(clock=clock, seed=0).brownout(
            "cxl", start_s=0.0, duration_s=1e9)
        pool.attach_fault_injector(inj)
        view, reader, inst, engine = run_restore(
            img, pool, borrow, scatter_fn=FusedScatter(use_pallas=False),
            clock=clock)
        assert inst.all_present()
        np.testing.assert_array_equal(inst.image.buf, img.buf)
        assert engine.degraded_cxl
        assert engine.repair_stats["degraded_preinstalls"] == 1
        assert engine.repair_stats["degraded_faults"] > 0
        assert pool.health["cxl"].degraded
        assert inj.stats["brownout_rejections"] >= 1
        # hot pages arrived over the RNIC: charged as rdma_read, and the
        # host-link ledger carries no CXL hot-chunk charges
        assert view.stats.get("degraded_reads", 0) > 0
        assert view.ledger.seconds.get("rdma_read", 0.0) > 0.0

    def test_degraded_model_upper_bounds_the_healthy_one(self):
        from repro.serve.strategies import (
            modeled_concurrent_restore_s,
            modeled_degraded_restore_s,
        )
        img, pool, borrow = publish_stack(fused=True)
        view = pool.host_view("m")
        reader = SnapshotReader(borrow.regions, view, pool.rdma)
        healthy = modeled_concurrent_restore_s(reader, 1)
        degraded = modeled_degraded_restore_s(reader, 1)
        assert degraded > healthy > 0.0, (
            "page-at-a-time RNIC hot transfer must cost more than the "
            "chunked CXL pre-install")


# -- health feeds placement ---------------------------------------------------

class _DegradedHealth:
    degraded = True


class TestPlacementHealth:
    def test_unhealthy_host_is_descored_and_avoided(self):
        prof = RestoreProfile(
            name="fn0", version=1, total_pages=3072,
            hot_bytes=4 << 20, cold_bytes=8 << 20,
            meta_terms=((4e-7 + 4096 / 50e9, 4096),),
            flush_s=1e-5, hot_serial_s=(4 << 20) / 50e9, hot_chunks=16,
            hot_install_s=3e-5, zero_install_s=1e-6,
            cold_serial_s=(8 << 20) / 12.5e9, cold_install_s=5e-5)
        fn = FunctionType(0, "fn0", 0, 10.0, "poisson", 0.5)
        sched = PlacementScheduler("locality")
        healthy, unhealthy = HostState(0), HostState(1)
        unhealthy.note_health(_DegradedHealth())
        assert not unhealthy.cxl_healthy
        assert (sched.score(unhealthy, fn, prof)
                < sched.score(healthy, fn, prof))
        assert sched.choose([healthy, unhealthy], fn, prof) is healthy
        # recovery: the breaker closing restores the score symmetrically
        unhealthy.note_health(None)
        assert unhealthy.cxl_healthy
        assert (sched.score(unhealthy, fn, prof)
                == sched.score(healthy, fn, prof))
