"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Where a paper artifact is a
convergence/accuracy result (Tab 4/5, Fig 1/3/4/5), the benchmark runs the
CPU-scale analogue via the event simulator and reports the decisive derived
quantity; timing-style artifacts (Tab 2/3/6) are measured or analytically
derived from the event model.

    PYTHONPATH=src python -m benchmarks.run                    # all
    PYTHONPATH=src python -m benchmarks.run --only table2
    PYTHONPATH=src python -m benchmarks.run --only topology --seed 7
    PYTHONPATH=src python -m benchmarks.run --only topology --small  # CI
    PYTHONPATH=src python -m benchmarks.run --only sweep       # batched vs serial

``--seed`` threads into every world compilation; ``--only topology`` emits
``BENCH_topology.json`` with a serialized ``World`` spec and a wall-clock
axis (bandwidth-aware LinkModel) per curve.  The sweep families
(``topology``, ``channel``) replay as batched many-worlds scans
(``Simulator.run_worlds``, DESIGN.md §11) — one jit trace + one dispatch
per family — and ``--only sweep`` emits ``BENCH_sweep.json``, the
batched-vs-serial wall-clock artifact the CI perf gate reads.  Timing
helpers block on results and report cold (compile-inclusive) and warm.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, repeats=3):
    """(cold_us, warm_us) of ``fn`` with results BLOCKED before the clock
    is read — jax dispatch is async, so timing an unblocked call measures
    enqueue latency, not work.  Cold includes compilation; warm is the
    steady-state mean over ``repeats``."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn())
    return cold, (time.perf_counter() - t0) / repeats * 1e6


def _parse_only(arg):
    return [s.strip() for s in arg.split(",") if s.strip()]


# ------------------------------------------------------- artifact emission

_MAX_CURVE_POINTS = 48  # per-curve cap in the emitted JSON artifacts


def _curve_indices(length: int, max_points: int = _MAX_CURVE_POINTS):
    """Evenly spaced sample indices keeping first and last points."""
    if length <= max_points:
        return np.arange(length)
    return np.unique(np.linspace(0, length - 1, max_points).round()
                     .astype(int))


def _downsample_entry(entry: dict, keys: tuple) -> dict:
    """Downsample a curve entry's per-round arrays on SHARED indices (the
    x-axis and every consensus curve stay aligned); scalars, world specs,
    and anything not listed pass through untouched."""
    lengths = [len(entry[k]) for k in keys if k in entry]
    if not lengths:
        return entry
    idxs = _curve_indices(max(lengths))
    out = dict(entry)
    for k in keys:
        if k in entry:
            arr = entry[k]
            out[k] = [arr[i] for i in idxs if i < len(arr)]
    return out


def _finite_or_none(x: float):
    """JSON-safe scalar: divergent (nan/inf) values become null."""
    x = float(x)
    return x if np.isfinite(x) else None


def _sanitize_json(obj):
    """Recursively null out NaN/Inf floats so every bench writer is safe
    against a diverged curve (json with allow_nan=False would otherwise
    throw away a whole completed sweep at write time)."""
    if isinstance(obj, dict):
        return {k: _sanitize_json(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize_json(v) for v in obj]
    if isinstance(obj, float):
        return _finite_or_none(obj)
    return obj


def _artifact_path(name: str) -> str:
    """Repo-root path of a BENCH_*/TRACE_* artifact."""
    import os
    return os.path.join(os.path.dirname(__file__), "..", name)


def _dump_json(path_base: str, name: str, report: dict) -> None:
    """Compact-writer for every BENCH_*.json artifact: no indentation
    whitespace (the topology artifact was ~17k lines indented) and
    NaN/Inf-free (``_sanitize_json``)."""
    import json
    with open(_artifact_path(name), "w") as f:
        json.dump(_sanitize_json(report), f, separators=(",", ":"),
                  allow_nan=False)
        f.write("\n")


# ------------------------------------------------- flight recorder (host)
# One SpanTracer per --only family (set by main()): every bench family
# writes TRACE_<name>.json beside its BENCH_<name>.json (DESIGN.md §15).
_TRACER = None


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _roofline(flops: float, write_bytes: float, coll_bytes: float) -> dict:
    """Roofline seconds and bottleneck against the LIVE chip's peaks
    (``analysis.roofline.device_peaks``; an unknown chip raises).  Off a
    TPU there is no device to bound, so the row keeps only its counts."""
    if not _on_tpu():
        return {}
    from repro.analysis.roofline import device_peaks
    pk = device_peaks()
    terms = {"compute": flops / pk.flops_bf16,
             "memory": write_bytes / pk.hbm_bw,
             "collective": coll_bytes / pk.ici_bw}
    return {"compute_s": terms["compute"], "memory_s": terms["memory"],
            "collective_s": terms["collective"],
            "bottleneck": max(terms, key=terms.get)}


def _exec_cost(tag: str, jitted, *args) -> dict:
    """Per-executable cost row: FLOPs, HBM write bytes, collective bytes
    (and, on a TPU, the roofline bottleneck) of ONE jitted callable,
    derived AOT from its compiled HLO (``lower -> compile -> as_text``;
    never executed).

    Off the chip a failure degrades to an ``{"executable", "error"}`` row
    instead of failing the bench; on a TPU it raises, so a chip run never
    hides a broken executable behind an error row.  When a family tracer
    is live, the compile is recorded as a ``jit.compile`` span annotated
    with the cost row.
    """
    try:
        from repro.analysis import cost_from_hlo
        t0 = _TRACER.now_us() if _TRACER is not None else 0.0
        compiled = jitted.lower(*args).compile()
        cost = cost_from_hlo(compiled.as_text())
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        row = {
            "executable": tag, "method": "hlo",
            "flops": cost.flops,
            "write_bytes": cost.write_bytes,
            "collective_bytes": cost.collective_bytes,
            "collective_detail": cost.collective_detail,
            "xla_flops": float(ca.get("flops", 0.0)),
            "xla_bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            **_roofline(cost.flops, cost.write_bytes,
                        cost.collective_bytes),
        }
        if _TRACER is not None:
            _TRACER.complete(
                f"jit.compile.{tag}", t0, _TRACER.now_us() - t0,
                lane="compile",
                args={k: row[k] for k in ("flops", "write_bytes",
                                          "collective_bytes")})
        return row
    except Exception as e:
        if _on_tpu():
            raise
        return {"executable": tag, "method": "hlo", "error": str(e)}


def _schedule_compiler(rounds):
    """World-schedule compiler memoized per unique (world object, seed) —
    a sweep grid replays the identical schedule across its baseline/
    accelerated (and robust/non-robust) arms, so each point compiles
    once."""
    cache = {}

    def compiled(w, s):
        key = (id(w), s)
        if key not in cache:
            cache[key] = w.compile(rounds, seed=s)
        return cache[key]

    return compiled


def _quad_grad_fn(b, noise=0.05):
    def grad_fn(x, key, wid):
        g = (x - b[wid]) + noise * jax.random.normal(key, x.shape)
        return 0.5 * jnp.sum((x - b[wid]) ** 2), g
    return grad_fn


def _sim_consensus(graph_name, n, accel, rate, rounds=250, d=64, seed=0):
    """(cold_us, warm_us, tail_consensus) of one serial world replay.

    The replay result is blocked on before the clock is read (the old
    timing measured async DISPATCH, not the replay); cold includes the
    jit trace, warm is a second identical call.
    """
    from repro.core import Simulator, World, build_graph, params_from_graph
    b = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    g = build_graph(graph_name, n)
    sim = Simulator(_quad_grad_fn(b), params_from_graph(g, accelerated=accel),
                    gamma=0.05)
    st = sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))
    # compile host-side BEFORE the timer: the us column measures the replay
    # only, comparable with pre-World artifacts
    sched = World(topology=g, comms_per_grad=rate).compile(rounds, seed=seed)
    out = {}

    def run():
        _, out["trace"] = sim.run_schedule(st, sched)
        return out["trace"]

    cold, warm = _timeit(run, repeats=1)
    return cold, warm, float(jnp.mean(out["trace"].consensus[-50:]))


# ----------------------------------------------------------- paper artifacts

def bench_table2_comm_rates(seed: int = 0) -> list[str]:
    """Tab 2: #communications per time unit for A2CiD2's rate condition
    sqrt(chi1 chi2)=O(1), per graph (analytic, from the Laplacian)."""
    from repro.core import build_graph
    rows = []
    for name in ("star", "ring", "complete"):
        n = 16
        g = build_graph(name, n)
        chi1, chi2 = g.chi1(), g.chi2()
        # scale Lambda by sqrt(chi1 chi2) => comm rate Tr(scaled)/2 (App D)
        scale = np.sqrt(chi1 * chi2)
        t0 = time.perf_counter()
        rate = scale * g.total_rate()
        us = (time.perf_counter() - t0) * 1e6
        rows.append(f"table2_comm_rate_{name},{us:.1f},{rate:.1f}")
    return rows


def bench_table3_training_time(seed: int = 0) -> list[str]:
    """Tab 3/6: async event timeline vs synchronous barriers — derived idle
    fraction of the slowest worker under jittered step durations."""
    rng = np.random.default_rng(seed)
    n, steps = 16, 200
    # per-step durations: lognormal jitter around 1 (stragglers)
    dur = rng.lognormal(mean=0.0, sigma=0.15, size=(steps, n))
    t0 = time.perf_counter()
    sync_time = dur.max(axis=1).sum()          # barrier per step
    async_time = dur.sum(axis=0).max()         # each worker free-runs
    us = (time.perf_counter() - t0) * 1e6
    speedup = sync_time / async_time
    return [f"table3_async_speedup,{us:.1f},{speedup:.3f}"]


def bench_table4_cifar_topologies(seed: int = 0) -> list[str]:
    """Tab 4 analogue: final consensus distance per topology, w/ and w/o
    A2CiD2 (ring shows the gap; complete does not)."""
    rows = []
    for name in ("complete", "ring"):
        for accel in (False, True):
            cold, warm, cons = _sim_consensus(name, 16, accel, 1.0,
                                              seed=seed)
            tag = "acid" if accel else "base"
            rows.append(f"table4_consensus_{name}_{tag},{warm:.0f},"
                        f"{cons:.4f};cold_us={cold:.0f}")
    return rows


def bench_fig1_virtual_doubling(seed: int = 0) -> list[str]:
    """Fig 1 / Fig 5b: A2CiD2 @ rate 1 vs baseline @ rate 2 on the ring."""
    c1, us1, base1 = _sim_consensus("ring", 16, False, 1.0, seed=seed)
    c2, us2, base2 = _sim_consensus("ring", 16, False, 2.0, seed=seed)
    c3, us3, acid1 = _sim_consensus("ring", 16, True, 1.0, seed=seed)
    ratio = acid1 / base2
    return [
        f"fig1_base_rate1,{us1:.0f},{base1:.4f};cold_us={c1:.0f}",
        f"fig1_base_rate2,{us2:.0f},{base2:.4f};cold_us={c2:.0f}",
        f"fig1_acid_rate1,{us3:.0f},{acid1:.4f};cold_us={c3:.0f}",
        f"fig1_acid_vs_doubled_ratio,0.0,{ratio:.3f}",
    ]


def bench_table5_worker_scaling(seed: int = 0) -> list[str]:
    """Tab 5 trend: ring-graph consensus degradation with n, and A2CiD2's
    recovery (n = 16, 32)."""
    rows = []
    for n in (16, 32):
        _, _, base = _sim_consensus("ring", n, False, 1.0, seed=seed)
        _, _, acid = _sim_consensus("ring", n, True, 1.0, seed=seed)
        rows.append(f"table5_ring_n{n}_gain,0.0,{base / max(acid, 1e-9):.3f}")
    return rows


# --------------------------------------------------------- systems benchmarks

def bench_kernels(seed: int = 0) -> list[str]:
    """Microbenchmarks of the Pallas kernels' oracle paths (CPU timing).

    The a2cid2_mixing rows report the FULL HBM traffic of one gossip event
    at f32: unfused (mix pass + p2p pass) moves 6 reads + 4 writes of
    parameter-sized tensors, the fused kernel 3 reads + 2 writes.  A timed
    interpret-mode Pallas row sits next to the jnp oracle as a smoke check
    (interpret timings are NOT hardware-representative).
    """
    from repro.kernels.a2cid2_mixing.kernel import mixing_p2p
    from repro.kernels.a2cid2_mixing.ref import mixing_p2p_ref
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rmsnorm.ref import rmsnorm_ref

    key = jax.random.PRNGKey(0)
    n = 1 << 20
    x = jax.random.normal(key, (n,))
    xt = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    xp = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    gb = n * 4 / 1e9
    kw = dict(eta=0.2, alpha=0.5, alpha_t=1.3)
    jf = jax.jit(lambda: mixing_p2p_ref(x, xt, xp, 0.5, **kw)[0])
    cold_f, warm_f = _timeit(jf)
    rows = [
        f"kernel_a2cid2_mixing_1M_unfused_traffic,0.0,"
        f"{6 * gb:.3f}GB_read+{4 * gb:.3f}GB_write",
        f"kernel_a2cid2_mixing_1M,{warm_f:.0f},"
        f"{3 * gb:.3f}GB_read+{2 * gb:.3f}GB_write_fused"
        f";cold_us={cold_f:.0f}",
    ]
    jp = jax.jit(lambda: mixing_p2p(x, xt, xp, jnp.float32(0.5),
                                    interpret=True, **kw)[0])
    cold_p, warm_p = _timeit(jp, 1)
    rows.append(f"kernel_a2cid2_mixing_1M_pallas_interpret,{warm_p:.0f},"
                f"{3 * gb:.3f}GB_read+{2 * gb:.3f}GB_write_fused"
                f";cold_us={cold_p:.0f}")

    q = jax.random.normal(key, (4, 512, 64))
    jg = jax.jit(lambda: attention_ref(q, q, q))
    cold_g, warm_g = _timeit(jg)
    rows.append(f"kernel_flash_attention_ref_4x512,{warm_g:.0f},"
                f"causal;cold_us={cold_g:.0f}")

    xx = jax.random.normal(key, (4096, 1024))
    sc = jnp.zeros(1024)
    jh = jax.jit(lambda: rmsnorm_ref(xx, sc))
    cold_h, warm_h = _timeit(jh)
    rows.append(f"kernel_rmsnorm_ref_4096x1024,{warm_h:.0f},"
                f"fused;cold_us={cold_h:.0f}")
    return rows


_SIM_BENCH = {"n": 16, "d": 256, "rounds": 100, "comms_per_grad": 1.0}


def _sim_setup(seed=0):
    from repro.core import (Simulator, coalesce_schedule, make_schedule,
                            params_from_graph, ring_graph)
    n, d, rounds = _SIM_BENCH["n"], _SIM_BENCH["d"], _SIM_BENCH["rounds"]
    b = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    g = ring_graph(n)
    sim = Simulator(_quad_grad_fn(b), params_from_graph(g, True), gamma=0.05)
    st = sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))
    sched = make_schedule(g, rounds=rounds,
                          comms_per_grad=_SIM_BENCH["comms_per_grad"],
                          seed=seed)
    cs = coalesce_schedule(sched)
    ref_arrays = sim.reference_arrays(sched)
    eng_arrays = sim.coalesced_arrays(st, sched, cs=cs)
    return sim, st, sched, cs, ref_arrays, eng_arrays


def bench_simulator_throughput(seed: int = 0) -> list[str]:
    """Event-simulator throughput (rounds/s) — the repro's own hot loop,
    on the flat-buffer coalesced/fused engine path (the default)."""
    sim, st, _, _, _, eng_arrays = _sim_setup(seed)
    run = lambda: sim.run_coalesced(st, eng_arrays)[1].loss
    cold, warm = _timeit(run, repeats=1)
    dt = warm / 1e6
    return [f"simulator_100rounds_n16,{warm:.0f},{100/dt:.0f}_rounds_per_s"
            f";cold_us={cold:.0f}"]


def bench_gossip_engine(seed: int = 0) -> list[str]:
    """Fused flat-buffer event engine vs the per-event reference path on the
    same schedule (100 rounds, n=16, d=256), plus the event-coalescing and
    HBM-traffic accounting.  Emits BENCH_gossip.json next to the repo root.

    Traffic accounting (state-tensor units, (n, D) each): the per-event
    reference sweeps every schedule SLOT (masked or not) with an unfused
    mix pass (2R+2W) + p2p pass (4R+2W incl. the partner gather); the engine
    sweeps only coalesced BATCHES, each one fused pass of 3 reads + 2 writes
    (x self + x partner rows + x~ self; the trailing mix rides along free).
    """
    sim, st, sched, cs, ref_arrays, eng_arrays = _sim_setup(seed)
    ref = lambda: sim.run(st, ref_arrays)[1].loss
    eng = lambda: sim.run_coalesced(st, eng_arrays)[1].loss
    cold_ref, us_ref = _timeit(ref, repeats=7)
    cold_eng, us_eng = _timeit(eng, repeats=7)
    speedup = us_ref / us_eng

    raw_slots = int(sched.partners.shape[0] * sched.partners.shape[1])
    batches = cs.num_batches()
    active_events = int(sched.event_mask.sum())
    # per-sweep state-tensor traffic: reference (mix + p2p unfused) vs fused
    ref_rw = (6, 4)
    fused_rw = (3, 2)
    report = {
        "config": dict(_SIM_BENCH),
        "simulator_100rounds_n16": {
            "seed_us": round(us_ref, 1),       # per-event path = seed code
            "engine_us": round(us_eng, 1),
            "speedup": round(speedup, 3),
            "seed_cold_us": round(cold_ref, 1),
            "engine_cold_us": round(cold_eng, 1),
        },
        "event_sweeps": {
            "raw_slots": raw_slots,
            "active_events": active_events,
            "coalesced_batches": batches,
            "sweep_reduction": round(raw_slots / max(batches, 1), 3),
        },
        "state_traffic_per_sweep": {
            "reference_reads_writes": ref_rw,
            "fused_reads_writes": fused_rw,
        },
        "executables": [
            _exec_cost("gossip_engine_replay", jax.jit(eng)),
            _exec_cost("gossip_reference_replay", jax.jit(ref)),
        ],
    }
    _dump_json(__file__, "BENCH_gossip.json", report)
    return [
        f"gossip_ref_100rounds_n16,{us_ref:.0f},{1e8/us_ref:.0f}_rounds_per_s",
        f"gossip_engine_100rounds_n16,{us_eng:.0f},"
        f"{1e8/us_eng:.0f}_rounds_per_s",
        f"gossip_engine_speedup,0.0,{speedup:.2f}x",
        f"gossip_event_sweeps,0.0,raw={raw_slots};active={active_events};"
        f"coalesced={batches}",
        f"gossip_traffic_per_sweep,0.0,ref={ref_rw[0]}R+{ref_rw[1]}W;"
        f"fused={fused_rw[0]}R+{fused_rw[1]}W",
    ]


_TOPO_BENCH = {"n": 64, "d": 32, "rounds": 150, "comms_per_grad": 1.0,
               "gamma": 0.05, "noise": 0.05, "seeds": 3,
               "families": ["ring", "torus", "hypercube", "complete"]}


def bench_topology_sweep(seed: int = 0) -> list[str]:
    """Paper-figure-shaped artifact: consensus-distance-vs-communication
    curves, accelerated vs baseline, across the paper's topology families at
    n=64 (Tab 4/5 + Fig 4 regime: the ring/torus gains, the complete-graph
    wash), plus heterogeneous-world scenarios (straggler clocks, a
    ring->hypercube phase switch with churn, Poisson failure/repair churn,
    and a bandwidth-degraded ring).  Emits BENCH_topology.json.

    The WHOLE artifact — every family x {baseline, accelerated} x seed,
    plus every scenario — is ONE batched replay (``Simulator.run_worlds``,
    DESIGN.md §11): per-world A2CiD2 params ride the batch axis, so the
    sweep costs one jit trace and one device dispatch instead of one per
    point.  Family curves carry mean +- std bands over ``seeds`` seeds.

    Every curve is described by a declarative ``World`` (core/world.py);
    its serialized spec is embedded next to the curve so the artifact names
    the exact scenario that produced it, and each world carries a
    bandwidth-aware ``LinkModel`` (TPU ICI bandwidth from
    ``analysis/roofline.py``) giving the curves a wall-clock x-axis.
    Curves are downsampled to <= 48 points (shared indices per entry) and
    the JSON is written compact; world specs stay intact.
    """
    from repro.analysis.roofline import V5E, device_peaks
    from repro.core import (ChurnProcess, LinkModel, PhaseSwitch, Simulator,
                            WorkerModel, World, build_graph,
                            params_from_graph)

    n, d = _TOPO_BENCH["n"], _TOPO_BENCH["d"]
    rounds, rate = _TOPO_BENCH["rounds"], _TOPO_BENCH["comms_per_grad"]
    seeds = [seed + i for i in range(_TOPO_BENCH["seeds"])]
    b = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    grad_fn = _quad_grad_fn(b, noise=_TOPO_BENCH["noise"])
    # one p2p message = the d-float replica; a gradient tick reads + writes
    # the replica through HBM (the memory term of the roofline)
    # the modelled deployment is a v5e ring, whatever host replays it
    v5e = device_peaks(V5E)
    ici_bw = v5e.ici_bw
    msg_bytes = float(d * 4)
    grad_seconds = 2 * msg_bytes / v5e.hbm_bw

    def link_model(bandwidth=ici_bw):
        return LinkModel(bandwidth_bytes_per_s=bandwidth,
                         msg_bytes=msg_bytes, grad_seconds=grad_seconds)

    ring = build_graph("ring", n)

    # -------- declare the grid: (key, world, chi_graph, accel, seed) per
    # point; families sweep seeds, scenarios replay at the base seed.
    # Worlds are constructed ONCE per curve and shared across the
    # baseline/accelerated arms, so each (world, seed) schedule compiles
    # once (the arms replay the identical schedule).
    points = []
    family_graphs = {}
    family_worlds = {}
    for name in _TOPO_BENCH["families"]:
        g = build_graph(name, n)
        family_graphs[name] = g
        family_worlds[name] = World(topology=g, links=link_model(),
                                    comms_per_grad=rate)
        for accel in (False, True):
            for s in seeds:
                points.append((("families", name), family_worlds[name],
                               g, accel, s))

    grad_rates = np.where(np.arange(n) % 2 == 0, 1.0, 0.25)
    scen_worlds = {"ring_stragglers": World(
        topology=ring, workers=WorkerModel(grad_rates=grad_rates),
        links=link_model(), comms_per_grad=rate)}
    active = np.ones(n, bool)
    active[: n // 8] = False
    scen_worlds["ring_churn_hypercube"] = World(
        topology=ring, links=link_model(),
        faults=(PhaseSwitch(rounds // 3, active=tuple(active)),
                PhaseSwitch(2 * (rounds // 3),
                            topology=build_graph("hypercube", n))),
        comms_per_grad=rate)
    scen_worlds["ring_poisson_churn"] = World(
        topology=ring, links=link_model(),
        faults=(ChurnProcess(fail_rate=0.02, repair_rate=0.2),),
        comms_per_grad=rate)
    bw = np.full(ring.num_edges, ici_bw)
    bw[::8] /= 8.0
    scen_worlds["ring_degraded_links"] = World(
        topology=ring,
        links=LinkModel(bandwidth_bytes_per_s=tuple(bw),
                        msg_bytes=msg_bytes, grad_seconds=grad_seconds),
        comms_per_grad=rate)
    for sname, w in scen_worlds.items():
        for accel in (False, True):
            points.append((("scenarios", sname), w, ring, accel, seed))

    # -------- compile the grid host-side (one compile per unique
    # (world, seed) — both accel arms share it), replay in ONE dispatch
    compiled = _schedule_compiler(rounds)
    scheds = [compiled(w, s) for _, w, _, _, s in points]
    plist = [params_from_graph(g, accelerated=a)
             for _, _, g, a, _ in points]
    sim = Simulator(grad_fn, plist[0], gamma=_TOPO_BENCH["gamma"])
    states = [sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))
              for _ in points]
    traces = Simulator._run_worlds_jit._cache_size()
    out = {}

    def replay():
        out["trace"] = sim.run_worlds(states, scheds, params=plist)[1]
        return out["trace"]

    cold_us, warm_us = _timeit(replay, repeats=1)
    trace = out["trace"]
    traces = Simulator._run_worlds_jit._cache_size() - traces
    cons = np.asarray(trace.consensus, np.float64)  # (B, rounds)

    def curves_for(key, accel):
        idx = [i for i, (k, _, _, a, _) in enumerate(points)
               if k == key and a == accel]
        return cons[idx], [scheds[i] for i in idx]

    def curve_entry(key, world):
        """Mean +- std bands over the key's seeds (scenarios: one seed,
        std 0), x-axes from the first seed's schedule."""
        base, schs = curves_for(key, False)
        acid, _ = curves_for(key, True)
        sched = schs[0]
        tail_b = float(base.mean(axis=0)[-30:].mean())
        tail_a = float(acid.mean(axis=0)[-30:].mean())
        wall = world.round_seconds(sched)
        entry = {
            "world": world.to_dict(),
            "seeds": seeds if base.shape[0] > 1 else [seed],
            "cumulative_comm_events":
                np.cumsum(sched.comm_events_per_round()).tolist(),
            "wall_clock_seconds": np.cumsum(wall).tolist(),
            "consensus_baseline": base.mean(axis=0).tolist(),
            "consensus_baseline_std": base.std(axis=0).tolist(),
            "consensus_acid": acid.mean(axis=0).tolist(),
            "consensus_acid_std": acid.std(axis=0).tolist(),
            "tail_consensus_baseline": tail_b,
            "tail_consensus_acid": tail_a,
            "acid_gain": tail_b / max(tail_a, 1e-12),
        }
        return _downsample_entry(entry, ("cumulative_comm_events",
                                         "wall_clock_seconds",
                                         "consensus_baseline",
                                         "consensus_baseline_std",
                                         "consensus_acid",
                                         "consensus_acid_std")), sched

    rows, report = [], {"config": dict(_TOPO_BENCH), "seed": seed,
                        "families": {}, "scenarios": {},
                        "batched_replay": {
                            "num_worlds": len(points),
                            "cold_us": round(cold_us, 1),
                            "warm_us": round(warm_us, 1),
                            "jit_traces": traces,
                        }}
    for name in _TOPO_BENCH["families"]:
        g = family_graphs[name]
        entry, _ = curve_entry(("families", name), family_worlds[name])
        entry.update(chi1=g.chi1(), chi2=g.chi2())
        report["families"][name] = entry
        rows.append(f"topology_{name}_n{n},0.0,"
                    f"gain={entry['acid_gain']:.3f};chi1={g.chi1():.1f}")

    for sname, w in scen_worlds.items():
        entry, sched = curve_entry(("scenarios", sname), w)
        if sname == "ring_churn_hypercube":
            entry["phases"] = [
                {"graph": ph.graph.name, "rounds": ph.rounds,
                 "active_workers": int(ph.active_mask().sum()),
                 "chi1": ph.chis()[0], "chi2": ph.chis()[1]}
                for ph in w.phase_plan(rounds, seed).phases]
        elif sname == "ring_poisson_churn":
            entry["mean_alive_fraction"] = float(sched.alive_arr().mean())
            entry["num_segments"] = len(w.segments(rounds, seed))
        elif sname == "ring_degraded_links":
            entry["slow_links"] = int((bw < ici_bw).sum())
        report["scenarios"][sname] = entry

    cost_fn, cost_args = sim.worlds_executable(states, scheds, params=plist)
    report["executables"] = [_exec_cost("topology_grid_replay",
                                        cost_fn, *cost_args)]
    _dump_json(__file__, "BENCH_topology.json", report)
    rows.append(f"topology_batched_dispatch,{warm_us:.0f},"
                f"worlds={len(points)};traces={traces};"
                f"cold_us={cold_us:.0f}")
    rows.append("topology_scenarios,0.0,"
                f"stragglers_gain="
                f"{report['scenarios']['ring_stragglers']['acid_gain']:.3f};"
                f"churn_alive="
                f"{report['scenarios']['ring_poisson_churn']['mean_alive_fraction']:.3f}")
    return rows


_CHAN_BENCH = {
    "n": 32, "d": 32, "rounds": 150, "comms_per_grad": 1.0,
    "gamma": 0.05, "noise": 0.05,
    "horizons": [0, 2, 4, 8],          # staleness sweep (ring-buffer depth)
    "stale_prob": 1.0,
    "byz_fracs": [0.0, 0.05, 0.1, 0.2],  # fraction of ring edges Byzantine
    "byz_mode": "scale", "byz_scale": 1e3, "byz_prob": 0.5,
    "byz_seeds": 3,                    # variance bands over >= 3 seeds
    "robust_clip": 5.0, "robust_rule": "trim",
}


def bench_channel_sweep(seed: int = 0) -> list[str]:
    """Unreliable-channel artifact (DESIGN.md §10): consensus + breakdown
    curves vs staleness horizon and vs the fraction of Byzantine edges on
    the ring, accelerated vs baseline, with the robust-aggregation (norm
    trim) replay next to the non-robust one.  Emits BENCH_channel.json.

    Each family runs as ONE batched replay (DESIGN.md §11): every
    (point, baseline/accelerated, seed) world shares a single jit trace
    and device dispatch per replay config — the staleness family is one
    dispatch and, since the robust tau became per-world ``(B,)`` data
    (DESIGN.md §12), the Byzantine family's non-robust AND robust arms
    ride one dispatch too.  Batching makes multi-seed cheap: the
    Byzantine family carries mean +- std bands over ``byz_seeds`` >= 3
    seeds.

    The Byzantine family is a garbage-injection adversary (``scale`` mode
    at 1e3, 50% duty cycle — an intermittent compromised link): without
    the defense the replay diverges outright; with ``robust_rule='trim'``
    the corrupted exchanges are rejected wholesale while the honest duty
    cycle keeps the ring connected, so the accelerated gain survives.
    The headline numbers are ``summary.gain_retention_at_0.1`` (robust
    gain on the 10%-Byzantine ring over the clean-channel gain; the
    acceptance bar is >= 0.8) and the divergent non-robust tails.

    Every curve embeds its serialized ``World`` spec — channel included —
    and NaN/Inf values of diverged non-robust replays are emitted as null
    plus a ``diverged`` flag (the compact/NaN-safe writer contract).
    """
    from repro.core import (ByzantineEdges, ChannelModel, DelayProcess,
                            Simulator, World, build_graph,
                            params_from_graph)

    cfg = _CHAN_BENCH
    n, d, rounds = cfg["n"], cfg["d"], cfg["rounds"]
    rate = cfg["comms_per_grad"]
    b = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    grad_fn = _quad_grad_fn(b, noise=cfg["noise"])
    ring = build_graph("ring", n)
    p_acid = params_from_graph(ring, accelerated=True)
    p_base = params_from_graph(ring, accelerated=False)

    compiled = _schedule_compiler(rounds)

    cost_fns = {}

    def run_family(worlds_accels_seeds, clips=None, cost_tag=None):
        """Replay a family grid in ONE batched dispatch; ``clips`` lifts
        the robust tau to per-world data (None = non-robust arm).
        Returns the (B, rounds) consensus curves + dispatch wall time.
        ``cost_tag`` stashes the replay closure for the per-executable
        cost rows embedded in the artifact."""
        sim = Simulator(grad_fn, p_acid, gamma=cfg["gamma"],
                        robust_rule=cfg["robust_rule"])
        scheds = [compiled(w, s) for w, _, s in worlds_accels_seeds]
        plist = [p_acid if a else p_base for _, a, _ in worlds_accels_seeds]
        states = [sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))
                  for _ in scheds]
        if cost_tag is not None:
            cost_fns[cost_tag] = sim.worlds_executable(
                states, scheds, params=plist, robust_clips=clips)
        t0 = time.perf_counter()
        _, trace = sim.run_worlds(states, scheds, params=plist,
                                  robust_clips=clips)
        jax.block_until_ready(trace)
        us = (time.perf_counter() - t0) * 1e6
        return np.asarray(trace.consensus, np.float64), us

    def nantail(curve):
        tail = curve[-30:]
        if not np.isfinite(tail).any():
            return float("nan")
        return float(np.nanmean(tail))

    def band(curves):
        """(mean, std) curves over seeds, NaN-tolerant (a seed that
        diverged at round r contributes nothing there onward)."""
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            return np.nanmean(curves, axis=0), np.nanstd(curves, axis=0)

    def curve_entry(world, robust, base_curves, acid_curves, seeds_used):
        base, base_std = band(base_curves)
        acid, acid_std = band(acid_curves)
        tail_b = nantail(base)
        tail_a = nantail(acid)
        diverged = not (np.isfinite(base_curves).all()
                        and np.isfinite(acid_curves).all())
        gain = tail_b / max(tail_a, 1e-12) if np.isfinite(tail_b) \
            and np.isfinite(tail_a) else float("nan")
        entry = {
            "world": world.to_dict(),
            "robust": bool(robust),
            "seeds": list(seeds_used),
            "consensus_baseline": [_finite_or_none(v) for v in base],
            "consensus_acid": [_finite_or_none(v) for v in acid],
            "consensus_baseline_std": [_finite_or_none(v)
                                       for v in base_std],
            "consensus_acid_std": [_finite_or_none(v) for v in acid_std],
            "tail_consensus_baseline": _finite_or_none(tail_b),
            "tail_consensus_acid": _finite_or_none(tail_a),
            "acid_gain": _finite_or_none(gain),
            "diverged": diverged,
        }
        return _downsample_entry(entry, ("consensus_baseline",
                                         "consensus_acid",
                                         "consensus_baseline_std",
                                         "consensus_acid_std"))

    def fmt(g):  # sanitized gains are None when a replay diverged
        return "None" if g is None else f"{g:.3f}"

    rows = []
    report = {"config": dict(cfg), "seed": seed,
              "staleness": {}, "byzantine": {}, "summary": {}}

    # family 1: staleness horizon sweep (all reads stale, uniform in
    # [1, H]; H=0 is the clean exact-reduction anchor) — one dispatch
    stale_worlds = {}
    for h in cfg["horizons"]:
        delay = DelayProcess(horizon=int(h), prob=cfg["stale_prob"])
        stale_worlds[h] = World(topology=ring, comms_per_grad=rate,
                                channel=None if h == 0
                                else ChannelModel(delay=delay))
    grid = [(w, a, seed) for w in stale_worlds.values()
            for a in (False, True)]
    cons, us_stale = run_family(grid, cost_tag="channel_stale_family")
    for i, h in enumerate(cfg["horizons"]):
        entry = curve_entry(stale_worlds[h], False,
                            cons[2 * i:2 * i + 1], cons[2 * i + 1:2 * i + 2],
                            [seed])
        report["staleness"][f"h{h}"] = entry
        rows.append(f"channel_stale_h{h}_n{n},0.0,"
                    f"gain={fmt(entry['acid_gain'])}")
    rows.append(f"channel_stale_dispatch,{us_stale:.0f},"
                f"worlds={len(grid)};dispatches=1")

    # family 2: Byzantine-edge fraction sweep, non-robust vs robust arms
    # TOGETHER in one dispatch (per-world robust_clips), mean +- std
    # bands over byz_seeds seeds per point
    E = ring.num_edges
    byz_seeds = [seed + i for i in range(cfg["byz_seeds"])]
    byz_worlds = {}
    for frac in cfg["byz_fracs"]:
        k = int(round(frac * E))
        if k == 0:
            byz_worlds[frac] = World(topology=ring, comms_per_grad=rate)
        else:
            picks = np.linspace(0, E, k, endpoint=False).astype(int)
            adversary = ByzantineEdges(
                tuple(ring.edges[i] for i in picks), cfg["byz_mode"],
                scale=cfg["byz_scale"], prob=cfg["byz_prob"])
            byz_worlds[frac] = World(topology=ring, comms_per_grad=rate,
                                     channel=ChannelModel(
                                         adversary=adversary))
    grid = [(w, a, s) for w in byz_worlds.values()
            for a in (False, True) for s in byz_seeds]

    def rows_for(cons, frac_i, accel):
        off = frac_i * 2 * len(byz_seeds) + (len(byz_seeds) if accel else 0)
        return cons[off:off + len(byz_seeds)]

    both = grid + grid
    clips = [None] * len(grid) + [cfg["robust_clip"]] * len(grid)
    cons_both, us_byz = run_family(both, clips=clips)
    entries = {}
    for robust in (False, True):
        cons = cons_both[len(grid):] if robust else cons_both[:len(grid)]
        for i, frac in enumerate(cfg["byz_fracs"]):
            entries[(frac, robust)] = curve_entry(
                byz_worlds[frac], robust, rows_for(cons, i, False),
                rows_for(cons, i, True), byz_seeds)
    for frac in cfg["byz_fracs"]:
        k = int(round(frac * E))
        tag = f"f{frac:g}"
        nonrobust = entries[(frac, False)]
        robust = entries[(frac, True)]
        report["byzantine"][tag] = {"edge_fraction": k / E,
                                    "num_byzantine_edges": k,
                                    "nonrobust": nonrobust,
                                    "robust": robust}
        gains = (nonrobust["acid_gain"], robust["acid_gain"])
        rows.append(
            f"channel_byz_{tag}_n{n},0.0,"
            f"gain_nonrobust={gains[0]};gain_robust={gains[1]};"
            f"diverged={nonrobust['diverged']}")
    rows.append(f"channel_byz_dispatch,{us_byz:.0f},"
                f"worlds={len(both)};dispatches=1;"
                f"seeds={len(byz_seeds)}")

    clean_gain = report["byzantine"]["f0"]["nonrobust"]["acid_gain"]
    summary = {"clean_gain": clean_gain}
    for frac in cfg["byz_fracs"]:
        if frac == 0.0:
            continue
        cell = report["byzantine"][f"f{frac:g}"]
        rg = cell["robust"]["acid_gain"]
        summary[f"gain_retention_at_{frac:g}"] = (
            None if rg is None or not clean_gain
            else rg / clean_gain)
        summary[f"nonrobust_diverged_at_{frac:g}"] = \
            cell["nonrobust"]["diverged"]
    report["summary"] = summary
    report["executables"] = [_exec_cost(tag, fn, *fargs)
                             for tag, (fn, fargs) in cost_fns.items()]
    _dump_json(__file__, "BENCH_channel.json", report)
    nonzero = [f for f in cfg["byz_fracs"] if f > 0]
    headline = min(nonzero, key=lambda f: abs(f - 0.1)) if nonzero else None
    retention = summary.get(f"gain_retention_at_{headline:g}") \
        if headline is not None else None
    rows.append(f"channel_summary,0.0,clean_gain={fmt(clean_gain)};"
                f"retention_at_{headline:g}="
                f"{retention if retention is None else round(retention, 3)}")
    return rows


_SWEEP_BENCH = {
    "n": 32, "d": 32, "rounds": 150, "comms_per_grad": 1.0,
    "gamma": 0.05, "noise": 0.05,
    # B = 16 grid: the two channel axes of BENCH_channel.json crossed
    "horizons": [0, 2, 4, 8], "stale_prob": 1.0,
    "byz_fracs": [0.0, 0.05, 0.1, 0.2],
    "byz_mode": "scale", "byz_scale": 1e3, "byz_prob": 0.5,
    "robust_clip": 5.0, "robust_rule": "trim",
}


def bench_batched_sweep(seed: int = 0) -> list[str]:
    """Batched-vs-serial replay of one sweep family — the perf artifact of
    the many-worlds subsystem (DESIGN.md §11).  Emits BENCH_sweep.json.

    The family is the channel grid: ``horizons`` x ``byz_fracs`` ring
    worlds (staleness crossed with Byzantine fraction, B = 16 at full
    size) under the robust accelerated replay (robust keeps every curve
    finite, so timings measure arithmetic, not NaN propagation).  Serial
    replays the B points one ``run_schedule`` at a time — every distinct
    stream shape AND every distinct ring horizon (a static arg of the
    channel scan) pays its own jit trace; batched replays them as ONE
    ``run_worlds`` scan at the shared ring depth H = max horizon.  Both
    are reported cold (first call, compiles included — the number a sweep
    actually costs) and warm (steady state), with jit trace counts from
    the cache deltas: the batched family compiles EXACTLY ONCE per family
    shape.
    """
    from repro.core import (ByzantineEdges, ChannelModel, DelayProcess,
                            Simulator, World, build_graph,
                            params_from_graph)

    cfg = _SWEEP_BENCH
    n, d, rounds = cfg["n"], cfg["d"], cfg["rounds"]
    b = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    grad_fn = _quad_grad_fn(b, noise=cfg["noise"])
    ring = build_graph("ring", n)
    p = params_from_graph(ring, accelerated=True)
    E = ring.num_edges

    worlds = []
    for h in cfg["horizons"]:
        delay = None if h == 0 else DelayProcess(horizon=int(h),
                                                 prob=cfg["stale_prob"])
        for frac in cfg["byz_fracs"]:
            k = int(round(frac * E))
            adversary = None
            if k:
                picks = np.linspace(0, E, k, endpoint=False).astype(int)
                adversary = ByzantineEdges(
                    tuple(ring.edges[i] for i in picks), cfg["byz_mode"],
                    scale=cfg["byz_scale"], prob=cfg["byz_prob"])
            channel = None if delay is None and adversary is None \
                else ChannelModel(delay=delay, adversary=adversary)
            worlds.append(World(topology=ring,
                                comms_per_grad=cfg["comms_per_grad"],
                                channel=channel))
    # every grid point replays under its own rng stream — the multi-seed
    # variance-band regime the batcher exists for (and what keeps the
    # serial arm honest: stream shapes are ragged across points, so serial
    # pays a jit trace per distinct (shape, horizon), not one total)
    point_seeds = [seed + i for i in range(len(worlds))]
    scheds = [w.compile(rounds, seed=s)
              for w, s in zip(worlds, point_seeds)]
    B = len(scheds)

    sim = Simulator(grad_fn, p, gamma=cfg["gamma"],
                    robust_clip=cfg["robust_clip"],
                    robust_rule=cfg["robust_rule"])
    states = [sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))
              for _ in scheds]

    # serial: one replay per point (the pre-batching bench structure);
    # trace count = distinct compiled shapes across the grid
    serial_traces = Simulator._run_channel_jit._cache_size()

    def serial():
        out = None
        for st, sch in zip(states, scheds):
            _, tr = sim.run_schedule(st, sch)
            out = tr
        jax.block_until_ready(out)

    t0 = time.perf_counter()
    serial()
    serial_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    serial()
    serial_warm = time.perf_counter() - t0
    serial_traces = Simulator._run_channel_jit._cache_size() - serial_traces

    # batched: the whole grid in one scan
    batched_traces = Simulator._run_worlds_channel_jit._cache_size()

    def batched():
        _, tr = sim.run_worlds(states, scheds)
        jax.block_until_ready(tr)

    t0 = time.perf_counter()
    batched()
    batched_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    batched()
    batched_warm = time.perf_counter() - t0
    batched_traces = (Simulator._run_worlds_channel_jit._cache_size()
                      - batched_traces)

    cost_fn, cost_args = sim.worlds_executable(states, scheds)
    report = {
        "config": dict(cfg), "seed": seed,
        "family": "channel_grid_horizons_x_byz_fracs",
        "sweep": {"worlds": [w.to_dict() for w in worlds],
                  "point_seeds": point_seeds},
        "num_worlds": B,
        "serial": {
            "wall_s_cold": round(serial_cold, 4),
            "wall_s_warm": round(serial_warm, 4),
            "jit_traces": serial_traces,
        },
        "batched": {
            "wall_s_cold": round(batched_cold, 4),
            "wall_s_warm": round(batched_warm, 4),
            "jit_traces": batched_traces,
        },
        "speedup_cold": round(serial_cold / batched_cold, 3),
        "speedup_warm": round(serial_warm / batched_warm, 3),
        "executables": [_exec_cost("sweep_batched_replay",
                                   cost_fn, *cost_args)],
    }
    _dump_json(__file__, "BENCH_sweep.json", report)
    return [
        f"sweep_serial_B{B},{serial_warm * 1e6:.0f},"
        f"cold_us={serial_cold * 1e6:.0f};traces={serial_traces}",
        f"sweep_batched_B{B},{batched_warm * 1e6:.0f},"
        f"cold_us={batched_cold * 1e6:.0f};traces={batched_traces}",
        f"sweep_speedup,0.0,cold={report['speedup_cold']:.2f}x;"
        f"warm={report['speedup_warm']:.2f}x",
    ]


_DEF_BENCH = {
    "n": 32, "d": 32, "rounds": 150, "comms_per_grad": 1.0,
    "gamma": 0.05, "noise": 0.05, "target": 0.3,
    "byz_frac": 0.1,                  # fraction of ring edges compromised
    # the two adversaries the control loop must separate: garbage
    # injection (norm 1e3 — static trim catches it) and sign flips at
    # honest scale (norm ~2||x|| < static tau — only adaptive tau does)
    "attacks": {
        "scale": {"mode": "scale", "scale": 1e3, "prob": 0.5},
        "sign_flip": {"mode": "sign_flip", "scale": 1.0, "prob": 1.0},
    },
    "robust_clip": 5.0, "robust_rule": "trim",
    "seeds": 3,
    # comm-controller demo: a lossy world thinned by the degradation-
    # aware scheduler (host-side — separate from the in-scan grid)
    "comm": {"horizon": 4, "stale_prob": 1.0,
             "lo": 0.5, "hi": 1.0, "degrade": 0.5},
}


def bench_defense(seed: int = 0) -> list[str]:
    """Self-healing gossip artifact (DESIGN.md §12): the static-trim vs
    adaptive-defense grid under Byzantine attacks, and the degradation-
    aware comm controller on a lossy ring.  Emits BENCH_defense.json.

    The headline grid is (clean + {scale, sign_flip} x {none, static,
    adaptive}) x {baseline, accelerated} x seeds — every arm a declared
    ``World`` (defense included), replayed as ONE ``run_worlds`` batch:
    one device dispatch, and the row asserts exactly one fresh jit trace
    (the per-world defense knobs are (B,) data, DESIGN.md §12).

    The story the summary tells: static trim already retains the clean
    accelerated gain under garbage injection (norms 1e3 >> tau), but a
    sign-flip adversary at honest scale (||corrupted|| ~ 2||x|| < tau)
    passes the static threshold BITWISE — ``static`` equals ``none`` on
    that family — while the adaptive quantile-tracking tau learns the
    honest-norm floor and rejects it.  Acceptance bars: adaptive
    retention >= 0.95 of the clean accelerated gain at 10% Byzantine
    edges on BOTH attacks, adaptive sign-flip tail < 3x clean while the
    static tail is > 10x clean (unbounded drift).

    The comm-control section replays the same lossy world with and
    without the controller and reports the kept-event fraction and the
    consensus cost of communicating less.
    """
    from repro.core import (AdaptiveDefense, ByzantineEdges, ChannelModel,
                            DelayProcess, Simulator, Telemetry, World,
                            build_graph, params_from_graph, trace_summary)

    cfg = _DEF_BENCH
    n, d, rounds = cfg["n"], cfg["d"], cfg["rounds"]
    # shared target: every worker pulls toward the same point, so the
    # equilibrium consensus floor is the noise floor and a sign-flipped
    # delta has norm ~2||x|| — comfortably under the static tau
    b = jnp.broadcast_to(cfg["target"] * jnp.ones(d), (n, d))
    grad_fn = _quad_grad_fn(b, noise=cfg["noise"])
    ring = build_graph("ring", n)
    p_acid = params_from_graph(ring, accelerated=True)
    p_base = params_from_graph(ring, accelerated=False)
    compiled = _schedule_compiler(rounds)
    sim = Simulator(grad_fn, p_acid, gamma=cfg["gamma"],
                    robust_rule=cfg["robust_rule"])
    state = sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))
    seeds = [seed + i for i in range(cfg["seeds"])]

    E = ring.num_edges
    k = max(1, int(round(cfg["byz_frac"] * E)))
    picks = np.linspace(0, E, k, endpoint=False).astype(int)
    edges = tuple(ring.edges[i] for i in picks)
    channels = {
        name: ChannelModel(adversary=ByzantineEdges(
            edges, a["mode"], scale=a["scale"], prob=a["prob"]))
        for name, a in cfg["attacks"].items()}

    # arm = (tag, channel, robust_clip, defense); clean anchor first
    tau = cfg["robust_clip"]
    arms = [("clean", None, None, None)]
    for name, ch in channels.items():
        arms += [(f"{name}/none", ch, None, None),
                 (f"{name}/static", ch, tau, None),
                 (f"{name}/adaptive", ch, tau, AdaptiveDefense())]

    worlds, scheds, states, plist, clips, defs = [], [], [], [], [], []
    for tag, ch, clip, dfn in arms:
        for accel in (False, True):
            for s in seeds:
                w = World(topology=ring, comms_per_grad=cfg["comms_per_grad"],
                          channel=ch, defense=dfn)
                worlds.append(w)
                scheds.append(compiled(w, s))
                states.append(state)
                plist.append(p_acid if accel else p_base)
                clips.append(clip)
                defs.append(dfn)

    # flight recorder: the compiled per-round telemetry columns ride the
    # SAME batched scan (one trace, one dispatch — asserted below)
    tel = Telemetry()
    before = Simulator._run_worlds_defense_jit._cache_size()
    t_span = _TRACER.now_us() if _TRACER is not None else 0.0
    t0 = time.perf_counter()
    _, trace = sim.run_worlds(states, scheds, params=plist,
                              robust_clips=clips, defenses=defs,
                              telemetry=tel)
    jax.block_until_ready(trace)
    us_grid = (time.perf_counter() - t0) * 1e6
    traces = Simulator._run_worlds_defense_jit._cache_size() - before
    if _TRACER is not None:
        _TRACER.complete("dispatch.defense_grid", t_span, us_grid,
                         lane="dispatch",
                         args={"worlds": len(worlds),
                               "jit_traces": int(traces)})
    cons = np.asarray(trace.consensus, np.float64)
    rejn = np.asarray(trace.defense.rejections, np.float64)
    quarn = np.asarray(trace.defense.quarantined, np.float64)

    def nantail(curve):
        t = curve[-30:]
        return float(np.nanmean(t)) if np.isfinite(t).any() else float("nan")

    def band(curves):
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            return np.nanmean(curves, axis=0), np.nanstd(curves, axis=0)

    S = len(seeds)
    entries, i = {}, 0
    for tag, ch, clip, dfn in arms:
        rows_b = slice(i, i + S)
        rows_a = slice(i + S, i + 2 * S)
        i += 2 * S
        base, base_std = band(cons[rows_b])
        acid, acid_std = band(cons[rows_a])
        tail_b, tail_a = nantail(base), nantail(acid)
        gain = tail_b / max(tail_a, 1e-12) if np.isfinite(tail_b) \
            and np.isfinite(tail_a) else float("nan")
        entry = {
            "world": worlds[rows_a.start].to_dict(),
            "robust_clip": clip,
            "seeds": seeds,
            "consensus_baseline": [_finite_or_none(v) for v in base],
            "consensus_acid": [_finite_or_none(v) for v in acid],
            "consensus_baseline_std": [_finite_or_none(v)
                                       for v in base_std],
            "consensus_acid_std": [_finite_or_none(v) for v in acid_std],
            "tail_consensus_baseline": _finite_or_none(tail_b),
            "tail_consensus_acid": _finite_or_none(tail_a),
            "acid_gain": _finite_or_none(gain),
            "diverged": not np.isfinite(cons[rows_b.start:i]).all(),
            "rejections_per_round": float(np.mean(rejn[rows_b.start:i])),
            "quarantined_per_round": float(np.mean(quarn[rows_b.start:i])),
        }
        entries[tag] = _downsample_entry(
            entry, ("consensus_baseline", "consensus_acid",
                    "consensus_baseline_std", "consensus_acid_std"))

    clean = entries["clean"]
    clean_gain = clean["acid_gain"]
    clean_tail = clean["tail_consensus_acid"]
    summary = {"clean_gain": clean_gain,
               "byz_edge_fraction": k / E,
               "num_byzantine_edges": k,
               "grid_worlds": len(worlds),
               "grid_traces": int(traces)}
    for name in cfg["attacks"]:
        for arm in ("none", "static", "adaptive"):
            e = entries[f"{name}/{arm}"]
            g = e["acid_gain"]
            summary[f"{name}_retention_{arm}"] = (
                None if g is None or not clean_gain else g / clean_gain)
            t = e["tail_consensus_acid"]
            summary[f"{name}_tail_vs_clean_{arm}"] = (
                None if t is None or not clean_tail else t / clean_tail)
    adaptive_ok = all(
        (summary[f"{name}_retention_adaptive"] or 0.0) >= 0.95
        for name in cfg["attacks"])
    summary["adaptive_retention_ok"] = adaptive_ok
    summary["signflip_adaptive_contained"] = \
        (summary["sign_flip_tail_vs_clean_adaptive"] or np.inf) < 3.0
    summary["signflip_static_fails"] = \
        (summary["sign_flip_tail_vs_clean_static"] or np.inf) > 10.0

    rows = [f"defense_grid_dispatch,{us_grid:.0f},"
            f"worlds={len(worlds)};dispatches=1;traces={traces};"
            f"seeds={S}"]
    for tag, e in entries.items():
        label = tag.replace("/", "_")
        g = e["acid_gain"]
        rows.append(
            f"defense_{label}_n{n},0.0,"
            f"gain={'None' if g is None else f'{g:.3f}'};"
            f"rej_per_round={e['rejections_per_round']:.2f};"
            f"quar_per_round={e['quarantined_per_round']:.2f};"
            f"diverged={e['diverged']}")

    # ------------------------------------------- comm controller demo
    cc = cfg["comm"]
    lossy = ChannelModel(delay=DelayProcess(horizon=cc["horizon"],
                                            prob=cc["stale_prob"]))
    ctrl = AdaptiveDefense(adaptive_tau=False, trust=False,
                           comm_lo=cc["lo"], comm_hi=cc["hi"],
                           comm_degrade=cc["degrade"])
    w_full = World(topology=ring, comms_per_grad=cfg["comms_per_grad"],
                   channel=lossy)
    w_ctrl = dataclasses.replace(w_full, defense=ctrl)
    s_full = compiled(w_full, seed)
    s_ctrl = compiled(w_ctrl, seed)
    kept = (int(np.sum(np.asarray(s_ctrl.event_mask)))
            / max(int(np.sum(np.asarray(s_full.event_mask))), 1))
    t0 = time.perf_counter()
    _, tr_cc = sim.run_worlds([state, state], [s_full, s_ctrl],
                              params=[p_acid, p_acid])
    jax.block_until_ready(tr_cc)
    us_cc = (time.perf_counter() - t0) * 1e6
    cc_cons = np.asarray(tr_cc.consensus, np.float64)
    tail_full, tail_ctrl = nantail(cc_cons[0]), nantail(cc_cons[1])
    report_cc = {
        "world_full": w_full.to_dict(), "world_controlled": w_ctrl.to_dict(),
        "kept_event_fraction": kept,
        "tail_consensus_full": _finite_or_none(tail_full),
        "tail_consensus_controlled": _finite_or_none(tail_ctrl),
        "consensus_cost_ratio": _finite_or_none(
            tail_ctrl / max(tail_full, 1e-12)),
    }
    rows.append(f"defense_comm_control,{us_cc:.0f},"
                f"kept_fraction={kept:.3f};"
                f"cost_ratio={report_cc['consensus_cost_ratio']:.3f}")

    tel_digest = trace_summary(trace.telemetry)
    rows.append(
        f"defense_telemetry,0.0,"
        f"applied={tel_digest['applied_total']:.0f};"
        f"rejected={tel_digest['rejected_total']:.0f};"
        f"dropped={tel_digest['dropped_total']:.0f};"
        f"bytes={tel_digest['bytes_moved_total']:.3e}")
    cost_fn, cost_args = sim.worlds_executable(
        states, scheds, params=plist, robust_clips=clips, defenses=defs,
        telemetry=tel)
    report = {"config": _sanitize_json(dict(cfg)), "seed": seed,
              "arms": entries, "comm_control": report_cc,
              "summary": summary,
              "telemetry": {"spec": tel.to_dict(),
                            "summary": tel_digest},
              "executables": [_exec_cost("defense_grid_replay",
                                         cost_fn, *cost_args)]}
    _dump_json(__file__, "BENCH_defense.json", report)
    fmt = lambda v: "None" if v is None else f"{v:.3f}"  # noqa: E731
    rows.append(
        f"defense_summary,0.0,clean_gain={fmt(clean_gain)};"
        f"scale_retention_adaptive={fmt(summary['scale_retention_adaptive'])};"
        f"signflip_retention_adaptive="
        f"{fmt(summary['sign_flip_retention_adaptive'])};"
        f"signflip_static_tail_x="
        f"{fmt(summary['sign_flip_tail_vs_clean_static'])};"
        f"adaptive_ok={adaptive_ok}")
    return rows


def bench_roofline_summary(seed: int = 0) -> list[str]:
    """Roofline terms from the dry-run artifacts (if present)."""
    import json
    import os
    rows = []
    path = os.path.join(os.path.dirname(__file__), "..",
                        "dryrun_single.json")
    if not os.path.exists(path):
        return ["roofline_summary,0,missing_dryrun_json"]
    data = json.load(open(path))
    for r in data:
        if not r.get("ok"):
            continue
        rows.append(
            f"roofline_{r['arch']}_{r['shape']},0.0,"
            f"bottleneck={r['bottleneck']}"
            f";compute_s={r['compute_s']:.3e}"
            f";memory_s={r['memory_s']:.3e}"
            f";collective_s={r['collective_s']:.3e}")
    return rows


_TRAIN_BENCH = {
    "n": 64, "seeds": 3, "gamma": 0.05,
    "topologies": ["ring", "hypercube"],
    # DADAO decoupled clocks: gradients thinned to 3/4 rate, gossip at 2x
    "dadao_grad_rate": 0.75, "dadao_gossip_rate": 2.0,
    "tail_frac": 0.25,                  # tail window = last quarter rounds
    # workers start from a NOISY BROADCAST of one shared init (no initial
    # all-reduce): per-parameter N(0, init_sigma^2) on top of params0.
    # The consensus axis then exercises the accelerated TRANSIENT Prop 3.6
    # actually bounds.  From an exact-consensus start with iid worker data
    # the tail sits at the gradient-noise equilibrium, where acceleration
    # is neutral — momentum amplifies injected noise by the same factor it
    # speeds contraction (measured while calibrating: ring-16 gain 1.03
    # +- 0.03 from a consensus start vs ~3 from a spread start; the PR 5
    # topology bench sees gain 3.3 from a consensus start only because its
    # quad workers have HETEROGENEOUS optima — persistent drift, not
    # noise).
    "init_sigma": 0.05,
    "families": {
        "resnet8_cifar": {"rounds": 16, "batch_size": 1},
        "nano_lm_bench": {"rounds": 150, "batch_size": 2, "seq_len": 32},
    },
}


def _train_family_setups():
    """(name, grad_fn, params0) per model family of the train bench —
    lazy imports so the other benches don't pay for model code."""
    from repro.configs.nano_lm import train_bench
    from repro.data import LMTaskStream, SyntheticCIFAR
    from repro.models import Model
    from repro.models.resnet import init_resnet, resnet8_cifar, resnet_loss

    fams = {}
    if "resnet8_cifar" in _TRAIN_BENCH["families"]:
        rcfg = resnet8_cifar()
        rconf = _TRAIN_BENCH["families"]["resnet8_cifar"]
        rstream = SyntheticCIFAR(batch_size=rconf["batch_size"], noise=0.5)

        def resnet_grad(params, key, wid):
            batch = rstream.sample(jax.random.fold_in(key, wid))

            def loss_fn(p):
                loss, _ = resnet_loss(p, rcfg, batch)
                return loss

            return jax.value_and_grad(loss_fn)(params)

        fams["resnet8_cifar"] = (resnet_grad,
                                 init_resnet(jax.random.PRNGKey(0), rcfg))
    if "nano_lm_bench" in _TRAIN_BENCH["families"]:
        lcfg = train_bench()
        model = Model(lcfg)
        lconf = _TRAIN_BENCH["families"]["nano_lm_bench"]
        lstream = LMTaskStream(vocab_size=lcfg.vocab_size,
                               seq_len=lconf["seq_len"],
                               batch_size=lconf["batch_size"],
                               concentration=0.15)

        def lm_grad(params, key, wid):
            batch = lstream.sample(jax.random.fold_in(key, wid))

            def loss_fn(p):
                loss, _ = model.loss(p, batch)
                return loss

            return jax.value_and_grad(loss_fn)(params)

        fams["nano_lm_bench"] = (lm_grad, model.init(jax.random.PRNGKey(0)))
    return fams


def bench_train(seed: int = 0) -> list[str]:
    """The paper's actual claim, end-to-end (Tab 4/5 regime): REAL models
    (ResNet-8/CIFAR-like and the nano-lm transformer) trained by the
    asynchronous algorithm zoo on n=64 ring and hypercube worlds —
    {a2cid2, adpsgd, dadao} x {base, accelerated} x seeds — emitting
    BENCH_train.json with consensus + loss curves, mean +- std bands, and
    the ring-gain trend the CI gate reads.

    The zoo is per-world DATA (DESIGN.md §13): each arm is a declarative
    ``World(algorithm=...)`` and the entire family grid replays as ONE
    batched ``run_worlds`` dispatch — dynamics columns (eta, alpha_t, chi)
    ride the (B,) parameter arrays, DADAO's decoupled clocks ride the
    schedule masks/intensities.  The artifact asserts the dispatch count
    (one per model family) and the jit-trace delta.

    Coupled-clock arms (a2cid2/adpsgd x base/accel) share one compiled
    schedule per (topology, seed); the dadao arms share the decoupled one.
    a2cid2-base and adpsgd-base carry identical dynamics by construction
    (Prop 3.6 eta=0 == AD-PSGD) — both are emitted; their bitwise equality
    is pinned in tests/test_algorithms.py, and here they must agree to the
    float tolerance of a shared batched scan.

    Workers start from a noisy broadcast of one shared init (no initial
    all-reduce; ``init_sigma`` in the config comment explains why the
    consensus gain is measured on this transient, not on the iid-noise
    equilibrium), so the ring-gain trend tracks the accelerated decay of
    Prop 3.6 and the loss curves still show real training progress.
    """
    from repro.core import Algorithm, Simulator, World, build_graph

    n = _TRAIN_BENCH["n"]
    gamma = _TRAIN_BENCH["gamma"]
    seeds = [seed + i for i in range(_TRAIN_BENCH["seeds"])]
    arms = [
        ("a2cid2_base", Algorithm("a2cid2", accelerated=False)),
        ("a2cid2_accel", Algorithm("a2cid2", accelerated=True)),
        ("adpsgd_base", Algorithm("adpsgd", accelerated=False)),
        ("adpsgd_accel", Algorithm("adpsgd", accelerated=True)),
        ("dadao_base", Algorithm(
            "dadao", accelerated=False,
            grad_rate=_TRAIN_BENCH["dadao_grad_rate"],
            gossip_rate=_TRAIN_BENCH["dadao_gossip_rate"])),
        ("dadao_accel", Algorithm(
            "dadao", accelerated=True,
            grad_rate=_TRAIN_BENCH["dadao_grad_rate"],
            gossip_rate=_TRAIN_BENCH["dadao_gossip_rate"])),
    ]
    graphs = {t: build_graph(t, n) for t in _TRAIN_BENCH["topologies"]}

    rows = []
    report = {"config": dict(_TRAIN_BENCH), "seed": seed,
              "arms": [name for name, _ in arms],
              "dispatches": 0, "families": {}}
    dispatches = 0

    for fam, (grad_fn, params0) in _train_family_setups().items():
        rounds = _TRAIN_BENCH["families"][fam]["rounds"]
        tail = max(2, int(rounds * _TRAIN_BENCH["tail_frac"]))
        num_params = int(sum(p.size for p in jax.tree.leaves(params0)))

        # -------- declare the grid: every (topology, arm, seed) point is a
        # World; schedules compile once per (topology, clock-group, seed)
        # because base/accel and a2cid2/adpsgd share the coupled clock
        points, worlds, scheds, states = [], [], [], []
        sim = Simulator(grad_fn, None, gamma=gamma)
        arm_worlds = {
            (t, name): World(topology=g, algorithm=algo)
            for t, g in graphs.items() for name, algo in arms}
        for t, g in graphs.items():
            for s in seeds:
                sched_coupled = arm_worlds[(t, "a2cid2_accel")].compile(
                    rounds, seed=s)
                sched_dadao = arm_worlds[(t, "dadao_accel")].compile(
                    rounds, seed=s)
                # noisy broadcast (see _TRAIN_BENCH["init_sigma"]): every
                # arm of a seed starts from the SAME spread state
                st = sim.init(params0, n, jax.random.PRNGKey(1000 + s))
                sigma = _TRAIN_BENCH["init_sigma"]
                leaves, treedef = jax.tree_util.tree_flatten(st.x)
                keys = jax.random.split(jax.random.PRNGKey(3000 + s),
                                        len(leaves))
                spread = jax.tree_util.tree_unflatten(treedef, [
                    l + sigma * jax.random.normal(k, l.shape, l.dtype)
                    for l, k in zip(leaves, keys)])
                st = st._replace(x=spread, x_tilde=spread)
                for name, algo in arms:
                    w = arm_worlds[(t, name)]
                    points.append((t, name, s))
                    worlds.append(w)
                    scheds.append(sched_dadao if algo.kind == "dadao"
                                  else sched_coupled)
                    states.append(st)
        sim = dataclasses.replace(sim, params=worlds[0].algorithm_params())

        # -------- ONE batched dispatch for the whole family grid.  The
        # trace delta counts BOTH run_worlds caches: the engine path falls
        # back to the per-event reference when FlatLayout rejects the
        # model's pytree, and that fallback must still be one dispatch.
        before = (Simulator._run_worlds_jit._cache_size()
                  + Simulator._run_worlds_reference_jit._cache_size())
        # single timed call (cold, compile-inclusive): real-model grids are
        # minutes-per-dispatch on CPU, so the warm re-run the other benches
        # afford would double the bench for one redundant number
        t0 = time.perf_counter()
        trace = sim.run_worlds(states, scheds, worlds=worlds)[1]
        jax.block_until_ready(trace.consensus)
        cold_us = (time.perf_counter() - t0) * 1e6
        traces = (Simulator._run_worlds_jit._cache_size()
                  + Simulator._run_worlds_reference_jit._cache_size()
                  - before)
        dispatches += 1
        cons = np.asarray(trace.consensus, np.float64)   # (B, rounds)
        loss = np.asarray(trace.loss, np.float64)

        fam_entry = {"params": num_params, "rounds": rounds,
                     "batched_replay": {"num_worlds": len(points),
                                        "cold_us": round(cold_us, 1),
                                        "jit_traces": traces},
                     "topologies": {}}

        def rows_for(t, name):
            idx = [i for i, (pt, pn, _) in enumerate(points)
                   if pt == t and pn == name]
            return cons[idx], loss[idx]           # (seeds, rounds)

        for t, g in graphs.items():
            topo_entry = {"chi1": g.chi1(), "chi2": g.chi2(), "arms": {}}
            for name, _ in arms:
                c, l = rows_for(t, name)
                entry = {
                    "world": arm_worlds[(t, name)].to_dict(),
                    "seeds": seeds,
                    "consensus_mean": c.mean(axis=0).tolist(),
                    "consensus_std": c.std(axis=0).tolist(),
                    "loss_mean": l.mean(axis=0).tolist(),
                    "loss_std": l.std(axis=0).tolist(),
                    "tail_consensus": float(c.mean(axis=0)[-tail:].mean()),
                    "tail_loss": float(l.mean(axis=0)[-tail:].mean()),
                }
                topo_entry["arms"][name] = _downsample_entry(
                    entry, ("consensus_mean", "consensus_std",
                            "loss_mean", "loss_std"))
            # ring-gain trend: accelerated A2CiD2 vs the async baseline,
            # per seed, so the band is a real noise floor
            c_bas, _ = rows_for(t, "adpsgd_base")
            c_acc, _ = rows_for(t, "a2cid2_accel")
            per_seed = (c_bas[:, -tail:].mean(axis=1)
                        / np.maximum(c_acc[:, -tail:].mean(axis=1), 1e-30))
            gain_mean = float(per_seed.mean())
            gain_std = float(per_seed.std())
            topo_entry["gain"] = {
                "per_seed": per_seed.tolist(),
                "mean": gain_mean, "std": gain_std,
                "predicted_sqrt_chi_ratio":
                    float(np.sqrt(g.chi1() / g.chi2())),
                "exceeds_baseline_by_band": bool(
                    gain_mean - gain_std > 1.0),
            }
            fam_entry["topologies"][t] = topo_entry
            rows.append(
                f"train_{fam}_{t},0.0,"
                f"gain={gain_mean:.3f}+-{gain_std:.3f};"
                f"tail_loss="
                f"{topo_entry['arms']['a2cid2_accel']['tail_loss']:.4f}")

        # cost row: analytic, not HLO — AOT-lowering a real-model grid a
        # second time would double a minutes-long compile for one number.
        # 6ND train FLOPs over the grid, parameter-row read+write traffic
        # per round, gossip bytes from the compiled schedules' event count
        from repro.analysis import model_flops
        conf = _TRAIN_BENCH["families"][fam]
        tokens = (rounds * n * conf.get("batch_size", 1)
                  * conf.get("seq_len", 1))
        grid_flops = (model_flops(num_params, 0, tokens, "train")
                      * len(points))
        total_events = sum(int(np.asarray(s.event_mask).sum())
                           for s in scheds)
        coll_bytes = 2.0 * total_events * num_params * 4
        write_bytes = float(len(points)) * rounds * n * num_params * 4 * 2
        fam_entry["executables"] = [{
            "executable": f"train_{fam}_grid", "method": "analytic",
            "flops": grid_flops, "write_bytes": write_bytes,
            "collective_bytes": coll_bytes,
            **_roofline(grid_flops, write_bytes, coll_bytes)}]

        report["families"][fam] = fam_entry
        rows.append(f"train_{fam}_dispatch,{cold_us:.0f},"
                    f"worlds={len(points)};traces={traces};"
                    f"params={num_params}")

    # the batching contract the artifact asserts: one dispatch per family
    assert dispatches == len(report["families"]), \
        (dispatches, list(report["families"]))
    report["dispatches"] = dispatches
    _dump_json(__file__, "BENCH_train.json", report)
    return rows


# --------------------------------------------------------------------- serve
# Gossip-serving fleet (DESIGN.md §14): {no-gossip, base async, A²CiD²} x
# {clean ring, lossy ring, churn} fleets serving ONE shared request trace.

_SERVE_BENCH = {
    "replicas": 8, "rounds": 120, "max_batch": 4, "max_len": 24,
    "rate": 1.2, "prompt_len": (3, 6), "gen_len": (4, 10),
    "arrive_frac": 0.55,
    # drift/stall physics: every replica random-walks by drift_scale per
    # round (online fine-tuning stand-in); each gossip event costs its
    # replica stall_per_event decode-rounds of debt (communication steals
    # compute) — what makes the p95-retention gate a real claim
    "drift_scale": 0.02, "stall_per_event": 0.03,
    "delay_horizon": 2, "delay_prob": 0.3, "drop_prob": 0.1,
    "kill_round_frac": 0.33,   # churn scenario: one replica dies here
    "tail_frac": 0.25,
    "p95_retention_max": 1.15,
}


def bench_serve(seed: int = 0) -> list[str]:
    """The millions-of-users scenario: a continuous-batching inference
    fleet whose replicas never stop averaging.  Every fleet admits the
    IDENTICAL request trace (``ServeLoad``'s dedicated rng stream) and
    reports throughput, p50/p95/p99 latency, request loss, and consensus
    distance — the latency cost and consensus benefit of gossip, measured
    under one workload.

    Arms: {none (comms_per_grad=0), adpsgd, a2cid2} x {clean ring, lossy
    ring (stale reads + drops), churn (one replica killed mid-serve)}.
    CI gates (ci.yml): the A²CiD² clean-ring fleet holds p95 latency
    within ``p95_retention_max`` of the no-gossip fleet while its final
    consensus distance stays a small fraction of the no-gossip drift; the
    churn fleets complete EVERY request (re-admission, zero loss).
    """
    import jax
    import jax.numpy as jnp

    from repro.configs.nano_lm import train_bench
    from repro.core import (Algorithm, ChannelModel, DelayProcess,
                            PhaseSwitch, ServeLoad, World, ring_graph)
    from repro.core.flatbuf import FlatLayout
    from repro.launch.fleet import GossipFleet, make_fleet_step
    from repro.models import Model

    c = _SERVE_BENCH
    W, rounds = c["replicas"], c["rounds"]
    cfg = train_bench()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    load = ServeLoad(rate=c["rate"], prompt_len=tuple(c["prompt_len"]),
                     gen_len=tuple(c["gen_len"]),
                     arrive_frac=c["arrive_frac"])
    base = World(topology=ring_graph(W), serve=load)
    lossy = ChannelModel(delay=DelayProcess(horizon=c["delay_horizon"],
                                            prob=c["delay_prob"]),
                         drop_prob=c["drop_prob"])
    kill_round = max(1, int(c["kill_round_frac"] * rounds))
    kill_mask = tuple(i != W - 1 for i in range(W))
    algos = {
        "none": dict(algorithm=Algorithm("adpsgd"), comms_per_grad=0.0),
        "adpsgd": dict(algorithm=Algorithm("adpsgd")),
        "a2cid2": dict(algorithm=Algorithm("a2cid2")),
    }
    scenarios = {
        "clean": dict(),
        "lossy": dict(channel=lossy),
        "churn": dict(faults=(PhaseSwitch(kill_round, active=kill_mask),)),
    }

    # one decode executable for all 9 arms (they differ only in schedule
    # data), packed over the shared (W, D) layout
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (W,) + a.shape),
                           params)
    layout = FlatLayout.from_pytree(stacked, stacked=True)
    step_fn = jax.jit(make_fleet_step(model, layout))

    # roofline-annotated cost of the one decode executable all arms share
    bank0 = layout.pack(stacked)
    caches0 = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (W,) + a.shape),
        model.init_cache(c["max_batch"], c["max_len"]))
    executables = [_exec_cost(
        "fleet_decode_step", step_fn, bank0, caches0,
        jnp.zeros((W, c["max_batch"], 1), jnp.int32),
        jnp.zeros((W, c["max_batch"]), jnp.int32),
        jnp.zeros((W, c["max_batch"]), bool))]

    from repro.analysis import MetricsRegistry
    registry = MetricsRegistry()
    rows: list[str] = []
    fleets: dict = {}
    for aname, akw in algos.items():
        for sname, skw in scenarios.items():
            world = dataclasses.replace(base, **akw, **skw)
            fleet = GossipFleet(model, params, world,
                                max_batch=c["max_batch"],
                                max_len=c["max_len"], drift="perturb",
                                drift_scale=c["drift_scale"],
                                stall_per_event=c["stall_per_event"],
                                decode_step_fn=step_fn)
            if aname == "a2cid2" and sname == "clean":
                # cost the compiled gossip round once, on the arm whose
                # schedule actually communicates
                from functools import partial as _partial
                arrays, horizon = fleet.sim.channel_reference_arrays(
                    world.compile(rounds, seed))
                ring0 = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (horizon,) + a.shape),
                    fleet._bank0) if horizon else None
                executables.append(_exec_cost(
                    "fleet_gossip_round",
                    jax.jit(_partial(fleet.sim._round_channel, horizon)),
                    (fleet._bank0, jnp.array(fleet._bank0),
                     jnp.zeros((W,)), ring0, jax.random.PRNGKey(0)),
                    tuple(jnp.asarray(np.asarray(a)[0]) for a in arrays)))
            rep = fleet.run(rounds, seed=seed, tracer=_TRACER,
                            metrics=registry)
            summ = rep.summary()
            idxs = _curve_indices(len(rep.consensus))
            # gossip stops at rep.rounds: gates read the scheduled prefix
            # so the constant drain tail can't dilute tail statistics
            prefix = rep.consensus[:rep.rounds]
            pidx = _curve_indices(len(prefix))
            fleets[f"{aname}/{sname}"] = {
                "world": world.to_dict(),
                **summ,
                "round_axis": [int(i) for i in idxs],
                "consensus": [float(rep.consensus[i]) for i in idxs],
                "consensus_scheduled": [float(prefix[i]) for i in pidx],
                "consensus_final_scheduled":
                    float(prefix[-1]) if prefix.size else 0.0,
            }
            rows.append(
                f"serve_{aname}_{sname},"
                f"{1e6 * rep.wall_seconds / max(rounds, 1):.0f},"
                f"p95={summ['latency_p95']:.1f};lost={summ['lost']};"
                f"ttft_p50={summ['ttft_p50']:.1f};"
                f"tok_per_round={summ['throughput_tokens_per_round']:.2f}")

    trace = load.sample_trace(rounds, seed)

    def tail_ratio(entry):
        # scheduled prefix only: the drain tail is constant by
        # construction (gossip stopped) and would flatten the statistic
        cur = np.asarray(entry["consensus_scheduled"])
        k = max(1, int(len(cur) * c["tail_frac"]))
        mid = np.mean(cur[len(cur) // 2: len(cur) // 2 + k])
        return float(np.mean(cur[-k:]) / max(mid, 1e-12))

    acid, nog = fleets["a2cid2/clean"], fleets["none/clean"]
    churn_arms = {k: v for k, v in fleets.items() if k.endswith("/churn")}
    gates = {
        "p95_retention": acid["latency_p95"] / max(nog["latency_p95"], 1e-9),
        "p95_retention_max": c["p95_retention_max"],
        "consensus_ratio_vs_nogossip":
            acid["consensus_final_scheduled"]
            / max(nog["consensus_final_scheduled"], 1e-12),
        "consensus_tail_over_mid": tail_ratio(acid),
        "churn_lost": {k: v["lost"] for k, v in churn_arms.items()},
        "churn_restarted": {k: v["restarted"]
                            for k, v in churn_arms.items()},
    }
    gates["p95_retention_ok"] = \
        gates["p95_retention"] <= c["p95_retention_max"]
    # bounded consensus: gossip holds the fleet at a small fraction of the
    # unmixed random-walk drift AND its own tail has stopped growing the
    # way the no-gossip walk does (linear => tail/mid ~ 2 at these sizes)
    gates["consensus_bounded_ok"] = (
        gates["consensus_ratio_vs_nogossip"] <= 0.25
        and gates["consensus_tail_over_mid"] <= 1.75)
    gates["churn_zero_loss_ok"] = all(
        v["lost"] == 0 for v in churn_arms.values())

    report = {
        "config": {k: list(v) if isinstance(v, tuple) else v
                   for k, v in c.items()},
        "model": {"config": cfg.name, "params": model.param_count(params),
                  "flat_dim": int(layout.d)},
        "trace": {"requests": trace.num_requests, "rounds": rounds,
                  "kill_round": kill_round},
        "fleets": fleets,
        "gates": gates,
        "executables": executables,
        "metrics": registry.snapshot(),
    }
    _dump_json(__file__, "BENCH_serve.json", report)
    rows.append(f"serve_gates,0,p95_retention="
                f"{gates['p95_retention']:.3f};zero_loss="
                f"{gates['churn_zero_loss_ok']}")
    return rows


# --------------------------------------------------------------------------
# Sharded giant-world replay: weak scaling over the worker mesh
# (DESIGN.md §16)
# --------------------------------------------------------------------------

_SCALE_BENCH = {
    # one giant fixed world split over ever-more shards: the curve is
    # events/s vs workers-per-shard (n / n_shards)
    "n": 4096, "d": 64, "rounds": 12,
    "shards": [1, 2, 4, 8],
    # staleness probe: replay the max-shard point again with the permute
    # ring's boundary reads floored at this lag
    "lag": 2,
    "repeats": 3,
}


def bench_scale(seed: int = 0) -> list[str]:
    """Sharded giant-world scaling artifact (DESIGN.md §16).

    Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
    CI forced-multi-device job does); shard counts above the live device
    count are skipped, so the family degrades to a single-shard row on a
    plain host.

    ONE giant world (n = 4096 workers full-size) is compiled once, then
    replayed with its worker axis split over 1, 2, 4, 8 shards — the
    curve is events/s vs workers-per-shard.  The timed region is the
    jitted sharded replay only: ``worlds_executable(..., mesh=...)``
    arguments are committed to the mesh with ``MeshReplay.place_args``
    first, so the clock never sees host prep or input resharding.
    Efficiency is t(1 shard) / t(ns shards).  On real accelerators the
    split divides the per-device work, so flat time (efficiency 1.0)
    is the FLOOR of the win; on a forced-host mesh every "device" shares
    the same cores, total work is constant, and the ideal is exactly
    flat — efficiency there isolates the cost the sharding machinery
    adds (the per-step boundary all_gather + SPMD partitioning), which
    is what the CI gate pins on the --small config.

    Each row also carries the wire split the flight recorder assigns the
    permute ring — cross-shard bytes = boundary rows x flat-row width vs
    intra-shard bytes (schedule-exact, DESIGN.md §15/§16) — and the
    compiled replay's HLO cost row (collective bytes = the ring's
    exchange traffic).  A final row replays the widest mesh with
    ``lag > 0`` to price bounded staleness against the lag-0 exchange.
    Emits BENCH_scale.json.
    """
    from repro.core import Simulator, Telemetry, World, params_from_graph, \
        ring_graph, trace_summary
    from repro.launch.mesh import make_replay_mesh
    from repro.launch.mesh_replay import MeshReplay, sharded_twin

    cfg = _SCALE_BENCH
    n, d, rounds = cfg["n"], cfg["d"], cfg["rounds"]
    avail = jax.local_device_count()
    shard_counts = [s for s in cfg["shards"] if s <= avail]
    skipped = [s for s in cfg["shards"] if s > avail]
    if skipped:
        print(f"# scale: {avail} local devices — skipping shard counts "
              f"{skipped} (force more with XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8)")

    g = ring_graph(n)
    b = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, d))
    sim = Simulator(_quad_grad_fn(b), params_from_graph(g, True),
                    gamma=0.05)
    sched = World(topology=g).compile(rounds, seed=seed)
    states = [sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))]
    tel = Telemetry(norm_moments=False, participation=False)

    def arm(ns, lag):
        """One scaling point of the SAME world: (row dict, fn, args)."""
        mr = MeshReplay(make_replay_mesh(ns), lag=lag)
        fn, args = sim.worlds_executable(states, [sched], telemetry=tel,
                                         mesh=mr)
        args = mr.place_args(args)
        stream_len = int(args[5][1].shape[0])
        _, trace = sim.run_worlds(states, [sched], telemetry=tel, mesh=mr)
        summary = trace_summary(trace.telemetry)
        row = {"n_shards": ns, "lag": lag, "n": n,
               "workers_per_shard": n // ns,
               "stream_len": stream_len, "rounds": rounds,
               "scheduled_total": summary["scheduled_total"],
               "cross_reads_total": summary.get("cross_reads_total", 0),
               "bytes_intra_total": summary.get("bytes_intra_total"),
               "bytes_cross_total": summary.get("bytes_cross_total"),
               "row_bytes": summary["row_bytes"]}
        return row, fn, args

    rows_out, report_rows, t1_warm = [], [], None
    flavor = sharded_twin("channel", donate=False)
    executables = []
    for ns in shard_counts:
        row, fn, args = arm(ns, 0)
        before = flavor._cache_size()
        cold, warm = _timeit(lambda: fn(*args), repeats=cfg["repeats"])
        row.update(us_cold=cold, us_warm=warm,
                   jit_traces=flavor._cache_size() - before,
                   events_per_s=row["stream_len"] / (warm * 1e-6),
                   reads_per_s=row["scheduled_total"] / (warm * 1e-6))
        if t1_warm is None:
            t1_warm = warm
        row["efficiency"] = t1_warm / warm
        executables.append(_exec_cost(f"scale_replay_ns{ns}", fn, *args))
        report_rows.append(row)
        rows_out.append(
            f"scale_ns{ns}_wps{row['workers_per_shard']},{warm:.0f},"
            f"events_per_s={row['events_per_s']:.0f};"
            f"eff={row['efficiency']:.2f};"
            f"cross_reads={row['cross_reads_total']}")

    lag_row = None
    if cfg["lag"] > 0 and shard_counts and shard_counts[-1] > 1:
        ns = shard_counts[-1]
        lag_row, fn, args = arm(ns, cfg["lag"])
        cold, warm = _timeit(lambda: fn(*args), repeats=cfg["repeats"])
        lag0 = report_rows[-1]
        lag_row.update(us_cold=cold, us_warm=warm,
                       events_per_s=lag_row["stream_len"] / (warm * 1e-6),
                       speedup_vs_lag0=lag0["us_warm"] / warm)
        executables.append(
            _exec_cost(f"scale_replay_ns{ns}_lag{cfg['lag']}", fn, *args))
        rows_out.append(
            f"scale_lag{cfg['lag']}_ns{ns},{warm:.0f},"
            f"vs_lag0={lag_row['speedup_vs_lag0']:.2f}x")

    eff_at_max = report_rows[-1]["efficiency"] if report_rows else None
    report = {
        "config": {k: list(v) if isinstance(v, list) else v
                   for k, v in cfg.items()},
        "seed": seed, "devices": avail,
        "shard_counts": shard_counts, "skipped_shard_counts": skipped,
        "rows": report_rows, "lag_probe": lag_row,
        "efficiency_at_max_shards": eff_at_max,
        "executables": executables,
    }
    _dump_json(__file__, "BENCH_scale.json", report)
    if eff_at_max is not None:
        rows_out.append(f"scale_efficiency,0,"
                        f"at_{shard_counts[-1]}_shards="
                        f"{eff_at_max:.2f}")
    return rows_out


BENCHES = {
    "table2": bench_table2_comm_rates,
    "table3": bench_table3_training_time,
    "table4": bench_table4_cifar_topologies,
    "table5": bench_table5_worker_scaling,
    "fig1": bench_fig1_virtual_doubling,
    "kernels": bench_kernels,
    "simulator": bench_simulator_throughput,
    "gossip": bench_gossip_engine,
    "topology": bench_topology_sweep,
    "channel": bench_channel_sweep,
    "defense": bench_defense,
    "sweep": bench_batched_sweep,
    "train": bench_train,
    "serve": bench_serve,
    "roofline": bench_roofline_summary,
    "scale": bench_scale,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated bench names, e.g. kernels,simulator")
    ap.add_argument("--seed", type=int, default=0,
                    help="rng seed threaded into every world compilation "
                         "(schedules, scenario sampling)")
    ap.add_argument("--small", action="store_true",
                    help="CI-sized sweeps (n=16, fewer rounds/families/"
                         "channel points) — for the scenario-smoke jobs")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.small:
        _TOPO_BENCH.update(n=16, rounds=60, seeds=2,
                           families=["ring", "complete"])
        # cap the channel family too: 2 horizons + 2 Byzantine fractions at
        # n=16/60 rounds keeps the CI smoke step inside its current budget
        # (byz_seeds stays 3 — the variance-band contract)
        _CHAN_BENCH.update(n=16, rounds=60, horizons=[0, 2],
                           byz_fracs=[0.0, 0.125])
        # B = 8 batched-vs-serial grid for the CI perf gate
        _SWEEP_BENCH.update(n=16, rounds=60, horizons=[0, 2, 4, 8],
                            byz_fracs=[0.0, 0.125])
        # defense grid at n=16/80 rounds, 2 seeds: the sign-flip physics
        # still holds (||corrupted|| ~ 2*0.3*sqrt(16) = 2.4 < tau = 5,
        # so the static arm stays bitwise-blind to the attack)
        _DEF_BENCH.update(n=16, d=16, rounds=80, seeds=2)
        # train smoke: n=16 keeps both topologies valid (hypercube needs a
        # power of two) and the ring gain still clears the gate
        # (sqrt(chi1/chi2) ~ 3.7 at n=16).  The nano family keeps 60
        # rounds — the gate reads ITS ring gain, and the noisy-broadcast
        # transient needs that long to separate from the adpsgd baseline
        # (measured 4.00 +- 0.68 at 60 rounds); the resnet family is the
        # expensive one, so it shrinks to a 6-round schema/dispatch check
        _TRAIN_BENCH.update(n=16, seeds=2)
        _TRAIN_BENCH["families"] = {
            "resnet8_cifar": {"rounds": 6, "batch_size": 1},
            "nano_lm_bench": {"rounds": 60, "batch_size": 1,
                              "seq_len": 16},
        }
        # serve smoke: 4 replicas, fewer rounds — the retention and
        # zero-loss gates still bind (the trace shrinks with the rounds)
        _SERVE_BENCH.update(replicas=4, rounds=60, max_batch=2)
        # scale smoke: a fixed n=1024 world keeps the per-step mixing
        # heavy enough that the forced-host ideal (flat time — total work
        # is constant, cores are shared) is measurable against the
        # per-step exchange overhead — the CI gate reads efficiency
        # (t1/t8) at 8 shards
        _SCALE_BENCH.update(n=1024, d=128, rounds=10, repeats=5)
    names = _parse_only(args.only) if args.only else list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; choose from {list(BENCHES)}")
    from repro.analysis import SpanTracer
    global _TRACER
    print("name,us_per_call,derived")
    for name in names:
        # one trace file per family: TRACE_<name>.json beside the
        # BENCH_<name>.json it narrates (Perfetto-loadable)
        _TRACER = SpanTracer("bench", metadata={
            "family": name, "seed": args.seed, "small": bool(args.small)})
        try:
            with _TRACER.span(f"bench.{name}", lane="bench",
                              args={"seed": args.seed}):
                rows = BENCHES[name](seed=args.seed)
            _TRACER.write(_artifact_path(f"TRACE_{name}.json"))
        finally:
            _TRACER = None
        for row in rows:
            print(row)


if __name__ == "__main__":
    main()
