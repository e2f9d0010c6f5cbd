import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

# Multi-pod dry-run: lower + compile every (arch x shape) on the production
# meshes, print memory/cost analysis, and emit roofline terms.
#
# MUST be the process entrypoint (jax locks the device count on first init):
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
#         --shape train_4k --mesh single
#     PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
#
# The two os.environ lines above run before ANY other import by design.

import argparse
import dataclasses
import json
import time
import traceback

import jax
import numpy as np


def run_one(arch: str, shape_name: str, mesh_name: str,
            serve_param_mode: str = "fsdp",
            train_microbatches: int = 4,
            carry_shard: str = None) -> dict:
    from repro.analysis.roofline import (V5E, device_peaks, model_flops,
                                         roofline_terms)
    from repro.configs import get_config
    from repro.launch.mesh import make_gossip_mesh, make_production_mesh, rules_for
    from repro.launch.steps import bundle_for
    from repro.models.transformer import Model
    from repro.shapes import adapt_config, shape_for

    t0 = time.time()
    if mesh_name == "single":
        mesh = make_production_mesh(multi_pod=False)
    elif mesh_name == "multi":
        mesh = make_production_mesh(multi_pod=True)
    elif mesh_name == "gossip":
        mesh = make_gossip_mesh()
    else:
        raise ValueError(mesh_name)
    rules = rules_for(mesh)

    cfg = get_config(arch).with_updates(param_dtype="bfloat16",
                                        compute_dtype="bfloat16")
    if carry_shard:
        cfg = cfg.with_updates(carry_shard=carry_shard)
    shape = shape_for(shape_name)
    spec = bundle_for(cfg, shape, mesh, rules,
                      train_microbatches=train_microbatches,
                      serve_param_mode=serve_param_mode)
    with mesh:
        lowered = spec.lower(mesh, rules)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # XLA's cost_analysis counts while bodies once (ignores trip counts) —
    # our HLO-text cost model multiplies scan bodies by their trip counts.
    from repro.analysis.hlo_cost import cost_from_hlo
    hc = cost_from_hlo(hlo)

    acfg = adapt_config(cfg, shape)
    model = Model(acfg)
    pcounts = _param_counts(model)
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind in ("train", "prefill") else shape.global_batch)
    kind = "train" if shape.kind == "train" else "serve"
    mf = model_flops(pcounts["total"], pcounts["active"], tokens, kind)

    peak = getattr(mem, "temp_size_in_bytes", 0) + \
        getattr(mem, "argument_size_in_bytes", 0) + \
        getattr(mem, "output_size_in_bytes", 0) - \
        getattr(mem, "alias_size_in_bytes", 0)
    report = roofline_terms(
        arch=arch, shape=shape_name, mesh_name=mesh_name,
        chips=mesh.devices.size,
        cost={"flops": hc.flops, "bytes accessed": hc.write_bytes},
        hlo_text="", model_flops_total=mf, peak_memory=float(peak),
        peaks=device_peaks(V5E))
    report = dataclasses.replace(
        report, collective_bytes=float(hc.collective_bytes),
        collective_detail=hc.collective_detail)
    out = report.to_dict()
    out.update({
        "ok": True,
        "fits_v5e_hbm": bool(peak <= 16e9),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "param_count": pcounts["total"], "active_params": pcounts["active"],
        "xla_cost_analysis_flops": float(dict(cost).get("flops", 0.0))
        if cost else 0.0,
        "memory_analysis": str(mem),
    })
    print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: OK "
          f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s, "
          f"peak/device {peak/1e9:.2f} GB, bottleneck {out['bottleneck']})")
    print(f"  memory_analysis: {mem}")
    print(f"  cost_analysis: flops/device={out['hlo_flops_per_device']:.3e} "
          f"bytes/device={out['hlo_bytes_per_device']:.3e} "
          f"collective/device={out['collective_bytes_per_device']:.3e}")
    return out


def _param_counts(model) -> dict:
    """Total and *active* (per-token) parameter counts, analytic."""
    cfg = model.cfg
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    active = total
    if cfg.moe is not None:
        # routed experts contribute top_k/num_experts of their weights
        def leaf_count(path, leaf):
            return int(np.prod(leaf.shape))
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        routed = sum(int(np.prod(l.shape)) for p, l in flat
                     if "moe_" in _path(p) and l.ndim >= 3)
        active = total - routed + int(routed * cfg.moe.top_k
                                      / cfg.moe.num_experts)
    return {"total": total, "active": active}


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", ""))) for p in path)


ALL_MESHES = ("single", "multi")


def run_gossip_step(arch: str = "qwen3-0.6b", n_workers: int = 8,
                    accelerated: bool = True, mode: str = "gossip",
                    comms_per_step: int = 1) -> dict:
    """Lower + compile the decentralized A2CiD2 train step on the gossip
    mesh (8 workers x 8 data x 8 model = 512 chips, ring graph).

    Uses the stacked (pjit-native) trainer: state leaves carry a leading
    worker axis sharded over "worker"; gossip is a gather along it, which
    XLA lowers to collective-permute."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import sharding as shardlib
    from repro.analysis.hlo_cost import cost_from_hlo
    from repro.configs import get_config
    from repro.core import params_from_graph, ring_graph
    from repro.launch import shardings as S
    from repro.launch.gossip_train import StackedGossipTrainer
    from repro.launch.mesh import make_gossip_mesh, rules_for
    from repro.models.transformer import Model
    from repro.optim import sgd

    t0 = time.time()
    mesh = make_gossip_mesh(n_workers=n_workers)
    rules = rules_for(mesh)
    cfg = get_config(arch).with_updates(param_dtype="bfloat16",
                                        compute_dtype="bfloat16")
    model = Model(cfg)
    graph = ring_graph(n_workers)
    acid = params_from_graph(graph, accelerated=accelerated)

    def grad_fn(params, batch):
        def loss_fn(p):
            loss, _ = model.loss(p, batch, remat=True)
            return loss, None
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    trainer = StackedGossipTrainer(
        grad_fn, sgd(), graph, acid, lr=0.1,
        comms_per_step=(0 if mode == "grad_only" else comms_per_step))
    step = {"ar": trainer.make_ar_step,
            "pair_ring": trainer.make_pair_ring_step}.get(
        mode, trainer.make_step)()

    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    state = jax.eval_shape(
        lambda: trainer.init(
            jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype), params),
            jax.random.PRNGKey(0)))
    B, Sq = 256 // n_workers, 4096  # per-worker slice of train_4k
    batch = {"inputs": jax.ShapeDtypeStruct((n_workers, B, Sq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((n_workers, B, Sq), jnp.int32)}

    psh = S.stacked_param_shardings(state.x, mesh, rules)
    state_sh = state._replace(
        x=psh, x_tilde=psh,
        opt=type(state.opt)(NamedSharding(mesh, P("worker")), psh, None),
        key=NamedSharding(mesh, P()))
    batch_sh = jax.tree.map(
        lambda _: NamedSharding(mesh, P("worker", "data", None)), batch)

    with shardlib.use_mesh(mesh, rules):
        lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                          donate_argnums=(0,)).lower(state, batch)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hc = cost_from_hlo(compiled.as_text())
    peak = (getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0))
    out = {
        "ok": True, "arch": arch, "shape": "train_4k", "mesh": "gossip",
        "accelerated": accelerated,
        "n_workers": n_workers, "chips": int(mesh.devices.size),
        "peak_memory_per_device": float(peak),
        "fits_v5e_hbm": bool(peak <= 16e9),
        "hlo_flops_per_device": hc.flops,
        "hlo_bytes_per_device": hc.write_bytes,
        "collective_bytes_per_device": hc.collective_bytes,
        "collective_detail": hc.collective_detail,
        "compile_s": round(time.time() - t0, 1),
        "memory_analysis": str(mem),
    }
    out["mode"] = mode
    out["comms_per_step"] = comms_per_step
    tag = mode if mode != "gossip" else ("A2CiD2" if accelerated
                                         else "baseline")
    print(f"[dryrun] gossip({tag}) {arch} x train_4k x (8,8,8): OK "
          f"(total {out[chr(39)+'compile_s'+chr(39)] if False else out['compile_s']}s, peak/device {peak/1e9:.2f} GB, "
          f"collective/device {hc.collective_bytes/1e9:.1f} GB)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", type=str, default="single",
                    choices=("single", "multi", "gossip"))
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) on --mesh")
    ap.add_argument("--out", type=str, default=None,
                    help="append JSON results to this file")
    ap.add_argument("--serve-param-mode", default="fsdp",
                    choices=("fsdp", "tp_only"))
    ap.add_argument("--train-microbatches", type=int, default=4)
    ap.add_argument("--carry-shard", default=None,
                    choices=(None, "embed", "seq", "none"))
    args = ap.parse_args()

    if args.mesh == "gossip":
        a = args.arch or "qwen3-0.6b"
        results = [run_gossip_step(a, accelerated=True),
                   run_gossip_step(a, accelerated=False),
                   run_gossip_step(a, mode="grad_only"),
                   run_gossip_step(a, mode="ar"),
                   run_gossip_step(a, accelerated=True, comms_per_step=2),
                   run_gossip_step(a, mode="pair_ring")]
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
        return

    from repro.configs import ARCHITECTURES
    from repro.shapes import SHAPES

    combos = ([(args.arch, args.shape)] if not args.all else
              [(a, s) for a in ARCHITECTURES for s in SHAPES])

    results = []
    for arch, shape in combos:
        try:
            results.append(run_one(
                arch, shape, args.mesh,
                serve_param_mode=args.serve_param_mode,
                train_microbatches=args.train_microbatches,
                carry_shard=args.carry_shard))
        except Exception as e:  # noqa: BLE001 — record and continue
            traceback.print_exc()
            results.append({"arch": arch, "shape": shape, "mesh": args.mesh,
                            "ok": False, "error": f"{type(e).__name__}: {e}"})
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"[dryrun] {n_ok}/{len(results)} combos OK")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
