"""Device time per phase of the training step in one cell, read through
the compiled replay's named scopes.

    python3 benchmarks/chip/scope_profile.py --workload <cell> \\
        --seeds <n> [<n> ...] [--seconds 4]

For each seed it builds the cell's run as ``bench.py`` does, then runs two
windows of ``--seconds`` back to back on the same state: one untraced and
one under the profiler.  It prints one JSON line per seed with

  * the rate of each window, so the cost of tracing shows;
  * the traced window's busy and idle time (``trace_reduce.reduce``);
  * device seconds per replay scope and per model scope
    (``trace_reduce.reduce`` with ``scopes.maps``) and the three scope
    readings: ``grad_mfu``, ``bank_ms_per_tick``, ``record_ms_per_tick``;
  * the top device ops, each named with its scope and followed by the
    scopes fused into it;
  * Python's garbage collections in each window (count, seconds, longest)
    beside its longest dispatch interval.

It checks nothing and is not the benchmark's command; it exits non-zero
without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import bench
import catalog
import scopes
import trace_reduce
from bench import jax, log
from peaks import peaks
from repro.analysis.tracing import gc_spans


def _gc(stats, win) -> dict:
    return {"collections": stats.collections, "seconds": stats.seconds,
            "longest": stats.longest,
            "longest_interval": float(win["intervals"].max())}


def profile(name: str, seeds, seconds: float, devices, *, cfg=None,
            traffic=None, backend: str = "auto"):
    """One result per seed, each from its own run of cell ``name``."""
    entry = catalog.workload(name, catalog.benchmark())
    cfg = cfg or catalog.config(entry["config"])
    traffic = traffic or catalog.traffic(entry["traffic"])
    mod = catalog.config_module(entry["config"])
    cell = bench.Cell(name, cfg, traffic, mod, backend)
    pk = peaks(devices[0].device_kind)
    maps = fused = None
    for seed in seeds:
        run = cell.start(seed)
        cell.first_steps(run)
        jax.block_until_ready(run["state"])
        if maps is None:
            text = cell.stream.hlo
            maps, fused = scopes.maps(text), scopes.fused_scopes(text)
        with gc_spans() as gc_plain:
            plain = cell.window(run, seconds)
        trace_dir = tempfile.mkdtemp(prefix="scope-trace-")
        try:
            with jax.profiler.trace(trace_dir):
                with jax.profiler.TraceAnnotation("window"):
                    with gc_spans() as gc_traced:
                        traced = cell.window(run, seconds)
            record = trace_reduce.extract(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run = None
        red = trace_reduce.reduce(record, maps)
        scope_s = red["scope_s"]
        facts = {"chips": len(devices), "grad_ticks": traced["grad_ticks"],
                 "traced_units": traced["grad_ticks"] * cell.units,
                 "flops_per_unit": mod.flops_per_unit(cfg, traffic),
                 "peak_flops": pk["flops_bf16"]}
        rate = f"train_{mod.UNIT}_per_s"
        yield {
            "cell": name, "seed": seed,
            rate: {"untraced": plain["grad_ticks"] * cell.units
                   / plain["seconds"],
                   "traced": facts["traced_units"] / traced["seconds"]},
            "grad_ticks": traced["grad_ticks"],
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "scope_s": dict(sorted(scope_s.items(), key=lambda kv: -kv[1])),
            "model_s": red["model_s"],
            "grad_mfu": scopes.grad_mfu(scope_s, facts),
            "bank_ms_per_tick": scopes.bank_ms_per_tick(scope_s, facts),
            "record_ms_per_tick": scopes.record_ms_per_tick(scope_s, facts),
            "device_ops": scopes.scoped_ops(red["breakdown"]["device_ops"],
                                            maps.replay, fused),
            "idle_gaps": red["breakdown"]["idle_gaps"],
            "gc": {"untraced": _gc(gc_plain, plain),
                   "traced": _gc(gc_traced, traced)},
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=bench.TRACE_SECONDS)
    args = ap.parse_args(argv)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # bench.py's cache, so a run after it loads the replay compiled
        jax.config.update("jax_compilation_cache_dir",
                          str(bench.ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    entry = catalog.workload(args.workload, catalog.benchmark())
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        log(f"scope_profile: this cell needs {entry['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s)")
        return 2
    for out in profile(args.workload, args.seeds, args.seconds,
                       devices[:entry["chips"]]):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
