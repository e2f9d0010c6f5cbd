"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 3 \\
        [--control] [--faults]

For each seed, in one process (one compile): the program's first
dispatches against the plain reference; with ``--control`` the reference
computed in bfloat16 put in the program's place; with ``--faults`` the
reference put in the program's place with each fault the cell can have
planted: half of each batch left out (``half_batch``), or one value of
every gossip answer altered (``altered_answer``).  A state left unchanged
reads 1 on the change numbers by their definition and needs no run.  Prints one JSON line per
seed and reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import types

import bench
import jax
import jax.numpy as jnp

import catalog


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    entry = catalog.workload(args.workload, catalog.benchmark())
    if jax.devices()[0].platform != "tpu":
        bench.log("calibrate: no TPU")
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      str(bench.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = bench.Cell(args.workload, catalog.config(entry["config"]),
                      catalog.traffic(entry["traffic"]),
                      catalog.config_module(entry["config"]))
    for seed in args.seeds:
        run = cell.start(seed)
        cell.first_steps(run)
        run["state"] = run["trace"] = run["dev"] = run["x0"] = None
        ref = cell.reference(run)
        print(json.dumps({"seed": seed, "reading": "program",
                          **cell.compare(run, ref)}), flush=True)
        readings = {}
        if args.control:
            readings["control"] = cell.reference(run, jnp.bfloat16)
        if args.faults:
            readings["half_batch"] = planted(cell, run, "half_batch")
            readings["altered_answer"] = planted(cell, run,
                                                 "altered_answer")
        for name, (losses, norms) in readings.items():
            nums = bench.reference.numbers(losses, norms, *ref, cell.marks())
            print(json.dumps({"seed": seed, "reading": name, **nums}),
                  flush=True)
    return 0


def planted(cell, run, fault: str):
    """The reference in the program's place, with one fault planted."""
    mod, comm = cell.mod, bench.reference._comm
    if fault == "half_batch":
        def example_batch(key, cfg, traffic):
            data = mod.example_batch(key, cfg, traffic)
            return {k: v[: v.shape[0] // 2] for k, v in data.items()}
        cell.mod = types.SimpleNamespace(example_batch=example_batch,
                                         reference_loss=mod.reference_loss)
    else:
        def altered(x, xt, *args):
            x, xt = comm(x, xt, *args)
            leaves, tdef = jax.tree.flatten(x)
            leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1.0)
            return tdef.unflatten(leaves), xt
        bench.reference._comm = altered
    try:
        return cell.reference(run)
    finally:
        cell.mod, bench.reference._comm = mod, comm


if __name__ == "__main__":
    sys.exit(main())
