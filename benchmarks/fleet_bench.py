"""Fleet-serving benchmark (ISSUE 7 acceptance): traffic in, tail out.

Real bytes, modeled time, end to end:

1. a REAL pod is built — every function type's snapshot is published
   through ``PoolMaster`` into the content-addressed dedup store, admission
   priced by ``DedupStore.probe_new_bytes`` (the marginal-byte probe the
   capacity manager admits on) and residency audited with
   ``exclusive_cxl_bytes`` (the store's ground truth for how many of a
   variant's hot bytes are shared with its base group);
2. each snapshot is profiled via a production ``SnapshotReader`` into a
   :class:`~repro.fleet.model.RestoreProfile`; the profile must reproduce
   ``strategies.modeled_concurrent_restore_s`` exactly (asserted here);
3. a sample of variants is restored for real through the serving path
   (borrow → flush → extent walk) and byte-compared against its image;
4. a seeded heavy-tailed trace (Zipf rates; Poisson/diurnal/ON-OFF mix)
   drives the :class:`~repro.fleet.driver.FleetDriver` under each
   placement policy — **locality vs random vs round_robin** A/B on the
   SAME trace — with keep-warm economics and queue-depth autoscaling on.

Reported per policy: p50/p99/mean modeled cold-start, modeled throughput,
warm/join fractions, peak hosts and in-flight concurrency.  Acceptance:
locality beats random by >= 1.3x on p99 modeled cold-start, the full run
covers >= 200 function types at >= 10k peak in-flight invocations, and two
identically-seeded locality runs are bit-identical.

All compared keys are modeled/deterministic (fixed default seed; CI holds
them to ±10%).  Results land in ``experiments/fleet_bench.json`` (full) or
``fleet_bench_quick.json`` (``--quick`` CI smoke).
"""
from __future__ import annotations

import argparse
import json
import math
import os
from pathlib import Path

import numpy as np

from repro.core import (
    HierarchicalPool,
    Instance,
    PoolMaster,
    RestoreEngine,
    SnapshotReader,
    StateImage,
)
from repro.core.pagestore import PAGE_SIZE
from repro.core.snapshot import exclusive_cxl_bytes
from repro.fleet import (
    FleetDriver,
    FleetTopology,
    QueueAutoscaler,
    generate_trace,
    plan_balanced,
    plan_replicated,
    plan_single,
    profile_reader,
    synthesize_fleet,
)
from repro.serve.strategies import modeled_concurrent_restore_s
from repro.sim.clock import VirtualClock

OUT = Path(__file__).resolve().parents[1] / "experiments"
SEED = int(os.environ.get("AQUIFER_SIM_SEED", "0"))


def build_pod(fleet, hot_pages, cold_pages, zero_pages, delta_pages,
              seed=SEED):
    """Publish one dedup variant snapshot per function type: variants of a
    base group share that group's base hot pages and differ in
    ``delta_pages`` private rows plus a private cold arena."""
    rng = np.random.default_rng(seed)
    n_bases = max(f.base_group for f in fleet) + 1
    bases = [rng.integers(1, 255, hot_pages * PAGE_SIZE,
                          dtype=np.int64).astype(np.uint8)
             for _ in range(n_bases)]
    pool = HierarchicalPool(cxl_capacity=1 << 30, rdma_capacity=1 << 30)
    # budget: dedup keeps a base group's shared pages once, so the pod fits
    # comfortably; the margin still makes the capacity manager account
    # every publish through probe_new_bytes-style marginal admission
    budget = (n_bases * hot_pages + len(fleet) * (delta_pages + 4)) * PAGE_SIZE * 2
    master = PoolMaster(pool, cxl_budget=budget, dedup=True)
    images, probes = {}, []
    for f in fleet:
        w = bases[f.base_group].copy()
        lo = (f.fn_id * delta_pages) % hot_pages
        for d in range(delta_pages):
            p = (lo + d) % hot_pages
            w[p * PAGE_SIZE:(p + 1) * PAGE_SIZE] = \
                rng.integers(1, 255, PAGE_SIZE).astype(np.uint8)
        img = StateImage.build({
            "w": w,
            "cold": rng.integers(1, 255, cold_pages * PAGE_SIZE).astype(np.uint8),
            "z": np.zeros(zero_pages * PAGE_SIZE, np.uint8),
        })
        ws = list(range(img.manifest.by_name()["w"].page_count))
        # marginal CXL bytes this publish will newly allocate (admission's
        # ground truth): first variant of a group pays its base, the rest
        # pay ~delta_pages
        probes.append(int(pool.dedup_cxl.probe_new_bytes(
            img.pages_matrix()[ws])))
        master.publish(f.name, img, ws)
        images[f.fn_id] = img
    return pool, master, images, probes


def profile_pod(pool, master, fleet):
    """One RestoreProfile per published snapshot, with its shared-base
    fraction taken from the dedup store's refcounts (exclusive_cxl_bytes),
    and an exactness check against the analytic restore model."""
    profiles = {}
    max_err = 0.0
    for f in fleet:
        entry = master.catalog.find(f.name)
        assert entry is not None and entry.regions is not None, \
            f"{f.name} not resident"
        r = entry.regions
        reader = SnapshotReader(r, pool.host_view(f"prof-{f.name}"), pool.rdma)
        hot_bytes = r.n_hot * PAGE_SIZE
        excl = exclusive_cxl_bytes(pool, r)
        prof = profile_reader(reader,
                              shared_base_bytes=max(0, hot_bytes - excl),
                              exclusive_bytes=excl)
        for conc in (1, 4):
            want = modeled_concurrent_restore_s(reader, conc)
            got = prof.cold_start_s(conc)
            max_err = max(max_err, abs(want - got))
            assert math.isclose(want, got, rel_tol=1e-12), \
                f"profile departs from restore model: {want} vs {got}"
        profiles[f.fn_id] = prof
    return profiles, max_err


def verify_restores(pool, master, images, fleet, n_sample):
    """Production-path restore + byte-compare for a deterministic sample."""
    idx = np.linspace(0, len(fleet) - 1, n_sample).astype(int)
    ok = []
    for i in idx:
        f = fleet[int(i)]
        borrow = master.catalog.borrow(f.name)
        assert borrow is not None
        try:
            reader = SnapshotReader(borrow.regions,
                                    pool.host_view(f"v-{f.name}"), pool.rdma)
            reader.invalidate_cxl()
            inst = Instance(StateImage.empty_like(images[f.fn_id].manifest))
            RestoreEngine(reader, inst, rdma_engine=None).install_all_sync()
            ok.append(bool(inst.all_present() and
                           np.array_equal(inst.image_bytes(),
                                          images[f.fn_id].buf)))
        finally:
            borrow.release()
    return bool(all(ok)), len(ok)


def drive(fleet, profiles, trace, policy, n_hosts, slots, max_hosts):
    d = FleetDriver(fleet, profiles, policy=policy, seed=SEED,
                    n_hosts=n_hosts, slots_per_host=slots,
                    clock=VirtualClock(),
                    autoscaler=QueueAutoscaler(min_hosts=n_hosts,
                                               max_hosts=max_hosts))
    return d.run(trace)


def run(quick: bool = False) -> dict:
    if quick:
        n_types, n_bases = 24, 6
        hot, cold, zero, delta = 48, 24, 16, 4
        total_rps, t_end, compute_mean = 500.0, 8.0, 0.25
        n_hosts, slots, max_hosts = 6, 16, 32
        n_sample = 4
        target_hot = 64 << 20
    else:
        n_types, n_bases = 200, 16
        hot, cold, zero, delta = 64, 32, 16, 6
        total_rps, t_end, compute_mean = 4000.0, 45.0, 2.0
        n_hosts, slots, max_hosts = 48, 64, 192
        n_sample = 8
        target_hot = 256 << 20

    fleet = synthesize_fleet(n_types, n_bases, total_rps, seed=SEED,
                             compute_mean_s=compute_mean)
    pool, master, images, probes = build_pod(fleet, hot, cold, zero, delta)
    profiles, model_err = profile_pod(pool, master, fleet)
    bit_identical, n_verified = verify_restores(pool, master, images, fleet,
                                                n_sample)
    # extrapolate the (exactness-checked) profiles to production-size
    # images — same layout shape, target_hot hot bytes — so the driver's
    # keep-warm economics and contention run at realistic magnitudes
    scale = target_hot / (hot * PAGE_SIZE)
    profiles = {k: p.scaled(scale) for k, p in profiles.items()}
    trace = generate_trace(fleet, t_end, seed=SEED)

    results = {p: drive(fleet, profiles, trace, p, n_hosts, slots, max_hosts)
               for p in ("locality", "random", "round_robin")}
    policies = {p: r.summary() for p, r in results.items()}
    # bit-determinism: an identically-seeded locality re-run must match
    r1 = results["locality"]
    r2 = drive(fleet, profiles, trace, "locality", n_hosts, slots, max_hosts)
    deterministic = bool(
        np.array_equal(r1.host, r2.host)
        and np.array_equal(r1.mode, r2.mode)
        and np.array_equal(r1.ready_s, r2.ready_s, equal_nan=True)
        and np.array_equal(r1.done_s, r2.done_s, equal_nan=True))

    loc, rnd = policies["locality"], policies["random"]
    p99_x = (rnd["p99_cold_start_s"] / loc["p99_cold_start_s"]
             if loc["p99_cold_start_s"] > 0 else float("inf"))
    shared_frac = float(np.mean(
        [profiles[f.fn_id].shared_base_bytes
         / max(1, profiles[f.fn_id].hot_bytes) for f in fleet]))
    criteria = {
        "locality_vs_random_p99_ge_1_3x": bool(p99_x >= 1.3),
        "bit_deterministic": deterministic,
        "restores_bit_identical": bit_identical,
        "profile_matches_restore_model": bool(model_err == 0.0),
        "all_completed": bool(all(p["completed"] == p["invocations"]
                                  for p in policies.values())),
    }
    if not quick:
        criteria["ge_200_function_types"] = bool(n_types >= 200)
        criteria["ge_10k_peak_inflight"] = bool(
            loc["inflight_peak"] >= 10_000)
    out = {
        "quick": quick, "seed": SEED,
        "fleet": {"n_types": n_types, "n_bases": n_bases,
                  "hot_pages": hot, "cold_pages": cold, "zero_pages": zero,
                  "delta_pages": delta, "total_rps": total_rps,
                  "t_end_s": t_end, "invocations": len(trace),
                  "n_hosts": n_hosts, "slots_per_host": slots,
                  "max_hosts": max_hosts},
        "pod": {"profile_scale_x": scale,
                "probe_marginal_bytes_total": int(sum(probes)),
                "probe_marginal_bytes_first": int(probes[0]),
                "probe_marginal_bytes_last": int(probes[-1]),
                "mean_shared_base_frac": shared_frac,
                "restores_verified": n_verified,
                "capacity": master.capacity.report()},
        "policies": policies,
        "locality_vs_random_p99_x": p99_x,
        "criteria": criteria,
    }
    OUT.mkdir(exist_ok=True)
    name = "fleet_bench_quick.json" if quick else "fleet_bench.json"
    (OUT / name).write_text(json.dumps(out, indent=2))
    return out


def drive_topo(fleet, profiles, trace, topo, n_hosts, slots):
    """One locality-policy run with a FleetTopology surcharging the
    scheduler's scores and the driver's restore charges.  No autoscaler:
    the tier comparison is same-hardware, same-budget — only the replica
    plan differs."""
    d = FleetDriver(fleet, profiles, policy="locality", seed=SEED,
                    n_hosts=n_hosts, slots_per_host=slots,
                    clock=VirtualClock())
    d.scheduler.topology = topo
    return d.run(trace)


def run_multipod(quick: bool = False) -> dict:
    """Multi-pod tier (ISSUE 9): replication + migration economics vs the
    single-big-pod and no-replication baselines at equal TOTAL CXL budget.

    Pods are Octopus-shaped: ``device_ports`` head ports per MHD, so the
    single big pod CXL-attaches only ``device_ports`` hosts while k pods
    attach k× as many — but must split the budget and (without
    replication) scatter each snapshot into exactly one pod.  The
    replicated tier spends the same budget's headroom on second replicas,
    gated by ``migration_economics`` priced on MEASURED demand: the
    per-pod cold-restore counts of the no-replication run (migration
    toward demand, not toward raw invocation rates — warm-served hot
    functions don't re-read their hot set)."""
    if quick:
        n_types, n_bases = 24, 6
        hot, cold, zero, delta = 48, 24, 16, 4
        total_rps, t_end, compute_mean = 500.0, 8.0, 0.25
        n_hosts, slots = 6, 64
        n_pods, device_ports = 3, 2
        target_hot = 64 << 20
    else:
        n_types, n_bases = 200, 16
        hot, cold, zero, delta = 64, 32, 16, 6
        total_rps, t_end, compute_mean = 2000.0, 45.0, 1.0
        n_hosts, slots = 48, 96
        n_pods, device_ports = 4, 12
        target_hot = 256 << 20

    fleet = synthesize_fleet(n_types, n_bases, total_rps, seed=SEED,
                             compute_mean_s=compute_mean)
    pool, master, images, _probes = build_pod(fleet, hot, cold, zero, delta)
    profiles, model_err = profile_pod(pool, master, fleet)
    bit_identical, n_verified = verify_restores(pool, master, images, fleet, 4)
    scale = target_hot / (hot * PAGE_SIZE)
    profiles = {k: p.scaled(scale) for k, p in profiles.items()}
    trace = generate_trace(fleet, t_end, seed=SEED)

    # equal TOTAL CXL budget across tiers: 1.5x the fleet's hot bytes —
    # one copy of everything fits with headroom, full k-replication would not
    budget = int(1.5 * sum(p.hot_bytes for p in profiles.values()))

    plans = {"single_pod": (1, plan_single(fleet)),
             "no_replication": (n_pods, plan_balanced(fleet, profiles,
                                                      n_pods)[0])}
    tiers, topos = {}, {}

    def run_tier(tier, k, plan):
        topo = FleetTopology(k, device_ports, plan)
        result = drive_topo(fleet, profiles, trace, topo, n_hosts, slots)
        s = result.summary()
        s["topology"] = dict(topo.stats)
        s["n_pods"] = k
        s["attached_hosts"] = sum(1 for h in range(n_hosts)
                                  if topo.attached(h))
        tiers[tier] = s
        topos[tier] = (topo, result)
        return result

    for tier, (k, plan) in plans.items():
        run_tier(tier, k, plan)

    # measured demand: a second replica serves one pod's share of the cold
    # restores actually paid without it — warm hits and joins never re-read
    # the hot set, so they contribute no replica benefit
    base = topos["no_replication"][1]
    cold_mask = base.mode == 0          # MODE_COLD
    cold_by_fn = np.bincount(base.fn[cold_mask].astype(int),
                             minlength=n_types)
    expected_reads = {f.fn_id: float(cold_by_fn[f.fn_id]) / n_pods
                      for f in fleet}
    rep_plan, rep_stats = plan_replicated(fleet, profiles, n_pods, budget,
                                          expected_reads)
    run_tier("replicated", n_pods, rep_plan)

    # bit-determinism: an identically-seeded replicated re-run must match
    r1 = topos["replicated"][1]
    r2 = drive_topo(fleet, profiles, trace,
                    FleetTopology(n_pods, device_ports, rep_plan),
                    n_hosts, slots)
    deterministic = bool(
        np.array_equal(r1.host, r2.host)
        and np.array_equal(r1.mode, r2.mode)
        and np.array_equal(r1.ready_s, r2.ready_s, equal_nan=True)
        and np.array_equal(r1.done_s, r2.done_s, equal_nan=True))

    rep, single, norep = (tiers["replicated"], tiers["single_pod"],
                          tiers["no_replication"])
    criteria = {
        "replicated_beats_single_pod_p99": bool(
            rep["p99_cold_start_s"] < single["p99_cold_start_s"]),
        "replicated_beats_no_replication_p99": bool(
            rep["p99_cold_start_s"] <= norep["p99_cold_start_s"]),
        "economics_gate_filtered": bool(
            rep_stats["replicas_added"] > 0
            and rep_stats["skipped_uneconomic"] > 0),
        "bit_deterministic": deterministic,
        "restores_bit_identical": bit_identical,
        "profile_matches_restore_model": bool(model_err == 0.0),
        "all_completed": bool(all(t["completed"] == t["invocations"]
                                  for t in tiers.values())),
    }
    out = {
        "quick": quick, "seed": SEED,
        "fleet": {"n_types": n_types, "n_bases": n_bases,
                  "invocations": len(trace), "t_end_s": t_end,
                  "n_hosts": n_hosts, "slots_per_host": slots,
                  "n_pods": n_pods, "device_ports": device_ports,
                  "total_cxl_budget_bytes": budget,
                  "restores_verified": n_verified},
        "replication_plan": rep_stats,
        "tiers": tiers,
        "single_vs_replicated_p99_x": (
            single["p99_cold_start_s"] / rep["p99_cold_start_s"]
            if rep["p99_cold_start_s"] > 0 else float("inf")),
        "criteria": criteria,
    }
    OUT.mkdir(exist_ok=True)
    name = ("fleet_bench_multipod_quick.json" if quick
            else "fleet_bench_multipod.json")
    (OUT / name).write_text(json.dumps(out, indent=2))
    return out


def main_multipod(quick: bool) -> int:
    out = run_multipod(quick=quick)
    f = out["fleet"]
    print(f"multipod: {f['n_types']} types, {f['invocations']} invocations, "
          f"{f['n_pods']} pods x {f['device_ports']} ports, "
          f"budget {f['total_cxl_budget_bytes'] >> 20} MiB total")
    print(f"replication plan: {out['replication_plan']}")
    for tier, s in out["tiers"].items():
        topo = s["topology"]
        print(f"{tier:>16}: p50 {s['p50_cold_start_s']*1e3:8.3f} ms  "
              f"p99 {s['p99_cold_start_s']*1e3:8.3f} ms  "
              f"local/remote/unattached {topo['local_placements']}/"
              f"{topo['remote_placements']}/{topo['unattached_placements']}")
    print(f"single_pod vs replicated p99: "
          f"{out['single_vs_replicated_p99_x']:.2f}x")
    ok = all(out["criteria"].values())
    print(f"criteria: {out['criteria']}  ->  {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI smoke (small fleet)")
    ap.add_argument("--multipod", action="store_true",
                    help="multi-pod replication/migration tier")
    args = ap.parse_args()
    if args.multipod:
        raise SystemExit(main_multipod(args.quick))
    out = run(quick=args.quick)
    f = out["fleet"]
    print(f"fleet: {f['n_types']} types / {f['n_bases']} bases, "
          f"{f['invocations']} invocations over {f['t_end_s']}s "
          f"({f['total_rps']:.0f} rps offered)")
    print(f"pod: shared-base frac {out['pod']['mean_shared_base_frac']:.3f}, "
          f"probe marginal first/last "
          f"{out['pod']['probe_marginal_bytes_first'] >> 10}/"
          f"{out['pod']['probe_marginal_bytes_last'] >> 10} KiB, "
          f"{out['pod']['restores_verified']} real restores verified")
    for name, p in out["policies"].items():
        print(f"{name:>12}: p50 {p['p50_cold_start_s']*1e3:8.3f} ms  "
              f"p99 {p['p99_cold_start_s']*1e3:8.3f} ms  "
              f"warm {p['warm_frac']:.3f}  join {p['join_frac']:.3f}  "
              f"hosts {p['host_peak']}  inflight {p['inflight_peak']}")
    print(f"locality vs random p99: {out['locality_vs_random_p99_x']:.2f}x")
    ok = all(out["criteria"].values())
    print(f"criteria: {out['criteria']}  ->  {'PASS' if ok else 'FAIL'}")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
