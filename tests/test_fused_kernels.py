"""Fused snapshot data plane (kernels/snapshot_fuse, DESIGN.md §13).

Interpret-mode bit-identity of the fused publish sweep and the fused
gather→verify→scatter restore against both the piecemeal Pallas ops and the
numpy oracles — odd page counts, tail chunks, all-zero and all-hot layouts,
the dedup ``hash_fn`` seam, the pluggable zero-scan backend, and the
publish→restore checksum-verification loop end-to-end.
"""
import dataclasses
import functools
import sys
import threading

import numpy as np
import pytest

from repro.core import FaultInjector, HierarchicalPool
from repro.core.coherence import Catalog
from repro.core.dedup import pallas_hash_fn
from repro.core.master import PoolMaster
from repro.core.orchestrator import Orchestrator
from repro.core.pagestore import (
    PAGE_SIZE,
    StateImage,
    numpy_zero_scan,
    pallas_zero_scan,
    set_zero_scan_backend,
)
from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
from repro.core.serving import Instance, RestoreEngine
from repro.core.snapshot import SnapshotReader, build_snapshot
from repro.kernels import (
    FusedScatter,
    fused_publish,
    fused_restore,
    make_fused_publish_fn,
    page_checksum,
    page_gather,
    page_scatter,
    zero_detect,
)
from repro.kernels.snapshot_fuse import ops as snapshot_fuse_ops
from repro.kernels.snapshot_fuse.ops import ChecksumMismatchError

INTERP = {"use_pallas": True, "interpret": True}


def _pages(n, seed=0, zero_every=3):
    rng = np.random.default_rng(seed)
    pages = rng.integers(0, 256, size=(n, PAGE_SIZE), dtype=np.uint8)
    if zero_every:
        pages[::zero_every] = 0
    ws = np.zeros(n, dtype=bool)
    if n:
        ws[rng.choice(n, size=max(1, n // 2), replace=False)] = True
    return pages, ws


class TestFusedPublish:
    @pytest.mark.parametrize("n", [1, 7, 8, 37, 64])
    def test_interpret_matches_oracle(self, n):
        """Odd counts and block tails (block_pages=8) vs the numpy ref."""
        pages, ws = _pages(n, seed=n)
        got = fused_publish(pages, ws, block_pages=8, **INTERP)
        want = fused_publish(pages, ws, use_pallas=False)
        np.testing.assert_array_equal(got.zero_bitmap, want.zero_bitmap)
        np.testing.assert_array_equal(got.checksums, want.checksums)
        np.testing.assert_array_equal(got.hot, want.hot)
        np.testing.assert_array_equal(got.cold, want.cold)

    @pytest.mark.parametrize("n", [37, 64])
    def test_slab_sweep_matches_oracle(self, n, monkeypatch):
        """An image swept in several slabs (the last one padded) carries its
        hot/cold counts across slabs: same outputs as the numpy ref."""
        monkeypatch.setattr(snapshot_fuse_ops, "SLAB_PAGES", 16)
        pages, ws = _pages(n, seed=n + 1)
        got = fused_publish(pages, ws, block_pages=8, **INTERP)
        want = fused_publish(pages, ws, use_pallas=False)
        np.testing.assert_array_equal(got.zero_bitmap, want.zero_bitmap)
        np.testing.assert_array_equal(got.checksums, want.checksums)
        np.testing.assert_array_equal(got.hot, want.hot)
        np.testing.assert_array_equal(got.cold, want.cold)

    def test_matches_piecemeal_ops(self):
        """The fused sweep ≡ zero_detect + page_checksum + 2× page_gather +
        dedup hash, run as separate interpret-mode kernels."""
        pages, ws = _pages(37, seed=2)
        u32 = pages.view(np.uint32).reshape(37, -1)
        fp = fused_publish(pages, ws, block_pages=8, **INTERP)
        zb = np.asarray(zero_detect(u32, block_pages=8, **INTERP)) != 0
        csum = np.asarray(page_checksum(pages, block_pages=8, **INTERP))
        hot_idx = np.flatnonzero(~zb & ws).astype(np.int32)
        cold_idx = np.flatnonzero(~zb & ~ws).astype(np.int32)
        hot = np.asarray(page_gather(u32, hot_idx, **INTERP))
        cold = np.asarray(page_gather(u32, cold_idx, **INTERP))
        np.testing.assert_array_equal(fp.zero_bitmap, zb)
        np.testing.assert_array_equal(fp.checksums, csum)
        np.testing.assert_array_equal(
            fp.hot.view(np.uint32).reshape(hot.shape), hot)
        np.testing.assert_array_equal(
            fp.cold.view(np.uint32).reshape(cold.shape), cold)

    def test_all_zero_layout(self):
        pages = np.zeros((16, PAGE_SIZE), np.uint8)
        ws = np.ones(16, bool)
        fp = fused_publish(pages, ws, block_pages=8, **INTERP)
        assert fp.zero_bitmap.all()
        assert fp.hot.shape[0] == 0 and fp.cold.shape[0] == 0

    def test_all_hot_layout(self):
        pages, _ = _pages(24, seed=3, zero_every=0)
        ws = np.ones(24, bool)
        fp = fused_publish(pages, ws, block_pages=8, **INTERP)
        assert not fp.zero_bitmap.any() and fp.cold.shape[0] == 0
        np.testing.assert_array_equal(fp.hot, pages)

    def test_empty(self):
        fp = fused_publish(np.zeros((0, PAGE_SIZE), np.uint8),
                           np.zeros(0, bool), **INTERP)
        assert fp.zero_bitmap.shape == (0,) and fp.hot.shape[0] == 0

    def test_dedup_hash_seam(self):
        """The fused checksum column IS the dedup hash: bit-equal to
        ``pallas_hash_fn`` (the store hash marked ``is_poly32``), so
        ``put_pages(..., hashes=checksums[idx])`` lands in the same buckets
        the store would compute itself."""
        pages, ws = _pages(21, seed=4)
        fp = fused_publish(pages, ws, block_pages=8, **INTERP)
        assert getattr(pallas_hash_fn, "is_poly32", False)
        np.testing.assert_array_equal(fp.checksums,
                                      np.asarray(pallas_hash_fn(pages)))


class TestFusedRestore:
    @pytest.mark.parametrize("n,m", [(16, 4), (37, 21), (64, 64)])
    def test_interpret_matches_piecemeal(self, n, m):
        """gather → checksum → scatter as three interpret-mode kernels vs
        the one fused kernel, including tail chunks and permuted sources."""
        rng = np.random.default_rng(n * m)
        chunk = rng.integers(0, 256, size=(m, PAGE_SIZE), dtype=np.uint8)
        chunk_u32 = chunk.view(np.uint32).reshape(m, -1)
        dst = np.sort(rng.choice(n, size=m, replace=False)).astype(np.int32)
        src = rng.permutation(m).astype(np.int32)
        dest = np.zeros((n, PAGE_SIZE), np.uint8)

        g = np.asarray(page_gather(chunk_u32, src, **INTERP))
        cs = np.asarray(page_checksum(g, block_pages=8, **INTERP))
        want = np.asarray(page_scatter(
            np.zeros((n, PAGE_SIZE // 4), np.uint32), g, dst, **INTERP))

        out, csums = fused_restore(dest, chunk, dst, src_indices=src, **INTERP)
        np.testing.assert_array_equal(np.asarray(out).reshape(n, -1), want)
        np.testing.assert_array_equal(csums, cs)

    @pytest.mark.parametrize("bad_page", [None, 17])
    def test_long_batch_splits_like_one(self, bad_page, monkeypatch):
        """A batch longer than MAX_BATCH_PAGES installs in pieces (the last
        padded) with the same bytes and checksums as the oracle, and a bad
        page in a later piece is still reported by its guest index."""
        monkeypatch.setattr(snapshot_fuse_ops, "MAX_BATCH_PAGES", 8)
        rng = np.random.default_rng(11)
        chunk = rng.integers(0, 256, size=(21, PAGE_SIZE), dtype=np.uint8)
        dst = np.sort(rng.choice(40, size=21, replace=False)).astype(np.int32)
        src = rng.permutation(21).astype(np.int32)
        want, want_cs = fused_restore(np.zeros((40, PAGE_SIZE), np.uint8),
                                      chunk, dst, src_indices=src,
                                      use_pallas=False)
        exp = want_cs.copy()
        if bad_page is not None:
            exp[bad_page] ^= 1
        try:
            out, csums = fused_restore(np.zeros((40, PAGE_SIZE), np.uint8),
                                       chunk, dst, src_indices=src,
                                       expected_csums=exp, **INTERP)
            assert bad_page is None
            np.testing.assert_array_equal(csums, want_cs)
        except ChecksumMismatchError as err:
            assert err.bad_pages.tolist() == [dst[bad_page]]
            out = err.dest
        np.testing.assert_array_equal(
            np.asarray(out).reshape(40, -1).view(np.uint8), want)

    def test_cpu_path_in_place(self):
        chunk, _ = _pages(6, seed=5, zero_every=0)
        dest = np.zeros((12, PAGE_SIZE), np.uint8)
        idx = np.array([1, 3, 5, 7, 9, 11], np.int32)
        out, _ = fused_restore(dest, chunk, idx, use_pallas=False)
        assert out is dest
        np.testing.assert_array_equal(dest[idx], chunk)
        assert not dest[::2].any()

    def test_checksum_verify_pass_and_fail(self):
        chunk, _ = _pages(5, seed=6, zero_every=0)
        exp = np.asarray(pallas_hash_fn(chunk))
        dest = np.zeros((8, PAGE_SIZE), np.uint8)
        idx = np.arange(5, dtype=np.int32)
        fused_restore(dest, chunk, idx, expected_csums=exp, use_pallas=False)

        bad = exp.copy()
        bad[2] ^= 1
        with pytest.raises(ChecksumMismatchError) as ei:
            fused_restore(dest, chunk, idx, expected_csums=bad,
                          use_pallas=False)
        assert ei.value.pages.tolist() == [2]


    @pytest.mark.parametrize("bad_page", [None, 13])
    def test_held_checks_span_stash_vectors(self, bad_page, monkeypatch):
        """A bulk install's device checksums fill several stash vectors (cut
        to 16 entries here), written in groups of two batches of one shape
        and singly otherwise; one settle compares them all and names the one
        bad guest page, after every batch was installed."""
        monkeypatch.setattr(snapshot_fuse_ops, "STASH_ENTRIES", 16)
        monkeypatch.setattr(snapshot_fuse_ops, "STASH_GROUP", 2)
        rng = np.random.default_rng(12)
        chunk = rng.integers(0, 256, size=(30, PAGE_SIZE), dtype=np.uint8)
        table = np.zeros(64, np.uint32)
        dst = np.sort(rng.choice(64, size=30, replace=False)).astype(np.int32)
        table[dst] = np.asarray(pallas_hash_fn(chunk))
        if bad_page is not None:
            table[dst[bad_page]] ^= 1
        sf = FusedScatter(**INTERP).bind_checksums(table)
        checks = sf.pending_checks()
        dest = sf.new_memory(64, PAGE_SIZE)
        for lo, hi in [(0, 5), (5, 6), (6, 14), (14, 22), (22, 30)]:
            dest = sf(dest, chunk[lo:hi], dst[lo:hi], checks=checks)
        assert sf.stats["verify_syncs"] == 0
        # padded 8, 1, 8, 8, 8: [8] and [1] singly, [8 8] as a group, and
        # the last [8] at settle, in a third vector
        assert len(checks._full) == 1 and checks._used == 16
        bad = checks.settle()
        assert len(checks._full) == 2 and checks._used == 8
        assert bad.tolist() == ([] if bad_page is None else [dst[bad_page]])
        assert sf.stats["verify_syncs"] == 1
        got = np.asarray(dest).reshape(64, -1).view(np.uint8)
        np.testing.assert_array_equal(got[dst], chunk)


class TestFusedScatterSeam:
    def test_scatterfn_signature_unbound(self):
        """Drop-in for the serving seam: (dest, compact, indices) -> dest,
        numerically the plain scatter when no checksum table is bound."""
        chunk, _ = _pages(4, seed=7, zero_every=0)
        dest = np.zeros((10, PAGE_SIZE), np.uint8)
        idx = np.array([0, 2, 5, 9], np.int32)
        sf = FusedScatter(use_pallas=False)
        out = sf(dest, chunk, idx)
        np.testing.assert_array_equal(out[idx], chunk)
        assert sf.stats == {"batches": 1, "pages": 4, "pages_verified": 0,
                            "verify_syncs": 1}

    def test_bound_copy_shares_stats_and_verifies(self):
        chunk, _ = _pages(4, seed=8, zero_every=0)
        table = np.zeros(10, np.uint32)
        idx = np.array([1, 4, 6, 8], np.int32)
        table[idx] = np.asarray(pallas_hash_fn(chunk))
        template = FusedScatter(use_pallas=False)
        bound = template.bind_checksums(table)
        bound(np.zeros((10, PAGE_SIZE), np.uint8), chunk, idx)
        assert template.stats["pages_verified"] == 4  # shared dict

        table[4] ^= 1
        with pytest.raises(ChecksumMismatchError):
            template.bind_checksums(table)(
                np.zeros((10, PAGE_SIZE), np.uint8), chunk, idx)


def _image(seed=11):
    rng = np.random.default_rng(seed)
    return StateImage.build({
        "w": rng.standard_normal((48, 1024)).astype(np.float32),
        "b": np.zeros((4, 1024), np.float32),
    })


def _scatter(memory):
    """The host oracle, or the kernel (in the interpreter) over a
    device-resident page array — the TPU data plane's memory layout."""
    if memory == "device":
        return FusedScatter(use_pallas=True, interpret=True)
    return FusedScatter(use_pallas=False)


class TestEndToEnd:
    @pytest.mark.parametrize("memory", ["host", "device"])
    @pytest.mark.parametrize("dedup", [False, True])
    def test_publish_restore_bit_identical_and_verified(self, dedup, memory):
        """Fused publish (master-wide publish_fn) → node-server restore with
        the fused verified scatter: bytes identical, every page checked —
        into a host image, or into one device page array."""
        img = _image()
        ws = list(range(0, img.total_pages, 3))
        pool = HierarchicalPool(
            cxl_capacity=64 << 20, rdma_capacity=128 << 20,
            dedup_hash_fn=pallas_hash_fn if dedup else None)
        catalog = Catalog()
        master = PoolMaster(pool, catalog, dedup=dedup,
                            publish_fn=make_fused_publish_fn(use_pallas=False))
        regions = master.publish("model", img, ws)
        assert getattr(regions, "page_checksums", None) is not None
        if dedup:
            assert pool.dedup_cxl.stats["unique"] > 0
        orch = Orchestrator("hostA", pool, catalog, scatter_fn=_scatter(memory))
        ri = orch.restore("model", pre_install=True)
        assert ri is not None
        ri.engine.install_all_sync()
        assert ri.instance.all_present()
        np.testing.assert_array_equal(ri.instance.image_bytes(), img.buf)
        assert (ri.instance.device_pages is not None) == (memory == "device")
        if memory == "device":
            assert not ri.instance.image.buf.any()   # the host image is unused
        stats = ri.instance.scatter_fn.stats
        assert stats["pages_verified"] == ri.instance.stats["uffd_copies"] > 0
        ri.shutdown()
        orch.close()

    @pytest.mark.parametrize("dedup", [False, True])
    def test_publish_fn_layout_identical_to_piecemeal(self, dedup):
        """The fused publish produces byte-identical snapshots (regions AND
        tier contents) to the default path — so rebuilds/re-curations that
        ride the master's publish_fn can't drift the layout."""
        img = _image(seed=12)
        ws = list(range(0, img.total_pages, 4))
        snaps = []
        for publish_fn in (None, make_fused_publish_fn(use_pallas=False)):
            pool = HierarchicalPool(
                cxl_capacity=64 << 20, rdma_capacity=128 << 20,
                dedup_hash_fn=pallas_hash_fn if dedup else None)
            regions = build_snapshot(pool, img, ws, "m", dedup=dedup,
                                     publish_fn=publish_fn)
            snaps.append((pool, regions))
        (pool_a, ra), (pool_b, rb) = snaps
        assert dataclasses.asdict(ra) == dataclasses.asdict(rb)
        np.testing.assert_array_equal(pool_a.cxl.buf, pool_b.cxl.buf)
        np.testing.assert_array_equal(pool_a.rdma.buf, pool_b.rdma.buf)
        assert SnapshotReader(rb, pool_b.host_view("h", None),
                              pool_b.rdma).page_checksums() is not None

    def test_corruption_detected_on_restore(self):
        img = _image(seed=13)
        ws = list(range(0, img.total_pages, 3))
        pool = HierarchicalPool(cxl_capacity=64 << 20, rdma_capacity=128 << 20)
        catalog = Catalog()
        master = PoolMaster(pool, catalog,
                            publish_fn=make_fused_publish_fn(use_pallas=False))
        regions = master.publish("m2", img, ws)
        pool.cxl.buf[regions.hot_off + 100] ^= 0xFF
        orch = Orchestrator("hostB", pool, catalog,
                            scatter_fn=FusedScatter(use_pallas=False))
        with pytest.raises(ChecksumMismatchError):
            orch.restore("m2", pre_install=True)
        orch.close()


class TestDeferredVerify:
    """Bulk installs read their checksums back once, when they end; every
    other install verifies in its own call (DESIGN.md §13)."""

    @staticmethod
    def _restore(memory, poison_hot=None):
        img = _image(seed=17)
        pool = HierarchicalPool(cxl_capacity=64 << 20, rdma_capacity=64 << 20)
        master = PoolMaster(pool, Catalog(),
                            publish_fn=make_fused_publish_fn(use_pallas=False))
        regions = master.publish("m", img, list(range(0, img.total_pages, 2)))
        reader = SnapshotReader(regions, pool.host_view("h"), pool.rdma)
        if poison_hot is not None:
            _kind, off = reader.lookup(int(reader.hot_page_indices()[poison_hot]))
            pool.attach_fault_injector(FaultInjector(seed=5).poison_reads(
                "cxl", 1, lo=off, hi=off + PAGE_SIZE))
        inst = Instance(StateImage.empty_like(img.manifest))
        engine = RestoreEngine(reader, inst, None, scatter_fn=_scatter(memory))
        return img, reader, inst, engine

    @pytest.mark.parametrize("memory", ["host", "device"])
    def test_bad_page_in_an_early_chunk_is_found_when_the_phase_ends(self, memory):
        """A poisoned page in the second of several hot chunks: the phase
        installs every chunk, compares once, and only then repairs the page,
        which was never present before its repair verified."""
        img, reader, inst, engine = self._restore(memory, poison_hot=5)
        target = int(reader.hot_page_indices()[5])
        n_chunks = len(list(reader.iter_hot_extents(4)))
        assert n_chunks > 2
        sf = inst.scatter_fn
        seen = []
        repair = engine._repair_page

        def spy(page):
            seen.append((page, sf.stats["batches"], sf.stats["verify_syncs"],
                         bool(inst.present[page])))
            return repair(page)

        engine._repair_page = spy
        engine.pre_install_hot(chunk_pages=4)
        assert seen == [(target, n_chunks, 1, False)]
        # the phase's one readback, then the repair's own single-page check
        assert sf.stats["verify_syncs"] == 2
        assert engine.repair_stats["checksum_mismatches"] == 1
        assert engine.repair_stats["checksum_repairs"] == 1
        hot = reader.hot_page_indices()
        assert inst.present[hot].all() and not inst.pending.any()
        assert inst.stats["uffd_copies"] == hot.size
        got = inst.image_bytes().reshape(-1, PAGE_SIZE)
        np.testing.assert_array_equal(got[hot], img.pages_matrix()[hot])

    @pytest.mark.parametrize("memory", ["host", "device"])
    def test_a_whole_restore_reads_checksums_back_twice(self, memory):
        """Hot phase, then cold phase: one readback each; the second hot
        pass finds every page present and holds nothing."""
        img, reader, inst, engine = self._restore(memory)
        engine.pre_install_hot(chunk_pages=4)
        engine.install_all_sync()
        assert inst.all_present()
        np.testing.assert_array_equal(inst.image_bytes(), img.buf)
        assert inst.scatter_fn.stats["verify_syncs"] == 2
        assert inst.scatter_fn.stats["batches"] > 2

    def test_installs_from_another_thread_verify_in_their_own_call(self):
        """While a bulk install is open, a completion thread's install is
        checked at once and its page is present when the call returns; the
        bulk install's page stays pending until it settles."""
        img, reader, inst, engine = self._restore("host")
        hot = reader.hot_page_indices()
        sf = inst.scatter_fn
        mat = img.pages_matrix()
        assert inst.defer_checks()
        assert not inst.defer_checks()           # one bulk install at a time
        inst.uffd_copy_batch(hot[:1], mat[hot[:1]])
        worker = threading.Thread(
            target=inst.uffd_copy_batch, args=(hot[1:2], mat[hot[1:2]]))
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
        assert sf.stats["verify_syncs"] == 1
        assert inst.present[hot[1]] and not inst.present[hot[0]]
        assert inst.pending[hot[0]]
        assert inst.settle_checks().size == 0
        assert inst.present[hot[:2]].all() and not inst.pending.any()
        assert sf.stats["verify_syncs"] == 2


    def test_faults_racing_a_bulk_install_see_only_verified_pages(self):
        """Guest threads fault the hot pages while the bulk install walks
        them one page a chunk: every access returns a present page with the
        image's bytes, and each page is installed exactly once."""
        img, reader, inst, engine = self._restore("host")
        hot = reader.hot_page_indices()
        want = img.pages_matrix()
        errors = []

        def guest(order):
            try:
                for p in order:
                    engine.access(int(p), timeout_s=30)
                    assert inst.present[p]
                    np.testing.assert_array_equal(inst.image.pages_matrix()[p], want[p])
            except Exception as e:          # reported by the main thread
                errors.append(e)

        rng = np.random.default_rng(0)
        guests = [threading.Thread(target=guest, args=(rng.permutation(hot),))
                  for _ in range(6)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            for t in guests:
                t.start()
            engine.pre_install_hot(chunk_pages=1)
            for t in guests:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in guests) and not errors, errors
        assert inst.present[hot].all() and not inst.pending.any()
        assert inst.stats["uffd_copies"] == hot.size


class TestZeroScanBackend:
    def test_parity_and_install(self):
        img = _image(seed=14)
        want = numpy_zero_scan(img.pages_matrix())
        scan = functools.partial(pallas_zero_scan, interpret=True)
        np.testing.assert_array_equal(img.zero_page_bitmap(backend=scan), want)
        prev = set_zero_scan_backend(scan)
        try:
            np.testing.assert_array_equal(img.zero_page_bitmap(), want)
        finally:
            set_zero_scan_backend(prev)
        np.testing.assert_array_equal(img.zero_page_bitmap(), want)


class TestDeviceResidentInstance:
    """The TPU data plane's instance memory, exercised off-TPU with the
    kernels in the interpreter: every install path writes into one device
    page array and the served state is built from it."""

    @pytest.mark.parametrize("use_batch", [True, False])
    def test_every_install_path_lands_in_device_memory(self, use_batch):
        img = _image(seed=15)
        ws = list(range(0, img.total_pages, 2))   # hot, cold and zero pages
        pool = HierarchicalPool(cxl_capacity=64 << 20, rdma_capacity=64 << 20)
        master = PoolMaster(pool, Catalog(),
                            publish_fn=make_fused_publish_fn(use_pallas=False))
        regions = master.publish("m", img, ws)
        assert regions.n_hot and regions.n_cold and regions.n_zero
        reader = SnapshotReader(regions, pool.host_view("h"), pool.rdma)
        inst = Instance(StateImage.empty_like(img.manifest))
        engine = RestoreEngine(reader, inst, None, scatter_fn=_scatter("device"))
        engine.install_all_sync(use_batch=use_batch)
        assert inst.all_present()
        np.testing.assert_array_equal(inst.image_bytes(), img.buf)
        assert inst.device_pages.shape == (img.total_pages, 8, 128)
        assert not inst.image.buf.any()

    def test_checkpoint_state_built_from_device_pages(self):
        """restore_checkpoint slices and bitcasts each page-aligned extent of
        the device page array: same leaves, dtypes and bits as the host
        path, including sub-word dtypes and ragged tails."""
        import jax.numpy as jnp

        rng = np.random.default_rng(16)
        state = {"w": jnp.asarray(rng.standard_normal((33, 70)), jnp.bfloat16),
                 "b": np.arange(7, dtype=np.int32),
                 "q": rng.integers(0, 255, (5, 3), dtype=np.uint8),
                 "step": np.int32(9)}
        out = {}
        for memory in ("host", "device"):
            pool = HierarchicalPool(cxl_capacity=64 << 20,
                                    rdma_capacity=64 << 20)
            master = PoolMaster(pool, Catalog())
            save_checkpoint(master, "ck", state, step=1)
            orch = Orchestrator("h", pool, master.catalog,
                                scatter_fn=_scatter(memory))
            out[memory], _ = restore_checkpoint(orch, "ck", state)
            orch.close()
        for name, want in state.items():
            for memory in ("host", "device"):
                got = np.asarray(out[memory][name])
                assert got.dtype == np.asarray(want).dtype, (name, memory)
                np.testing.assert_array_equal(got, np.asarray(want))
