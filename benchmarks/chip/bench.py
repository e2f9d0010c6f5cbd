"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is a gossip-training replay: W workers of one configuration
(``configs/``) on the schedule of one traffic mix (``traffic/``), replayed
by the engine scan ``Simulator.run_schedule`` takes for a plain schedule,
on the ``auto`` backend (Pallas on a TPU), with donation and without
telemetry.  The run:

  1. exits non-zero without a TPU, or with fewer chips than the cell asks;
  2. keeps JAX's persistent compile cache in ``.jax_cache`` at the root of
     the checkout;
  3. draws the weights on the device from the seed, in one jitted call;
  4. draws the schedule from the seed and compiles it to the engine's
     event stream on the host;
  5. cuts the stream into dispatches of the traffic's fixed number of
     steps, so every seed runs one executable, compiled ahead of the
     window; a compiled replay without the Pallas gossip kernel is an
     error;
  6. runs the first three dispatches and keeps what they produced for the
     check;
  7. measures for ``--seconds``: dispatches enqueued back to back, one in
     flight behind the one the host waits on, the window closing on
     ``block_until_ready``; with ``--trace 1`` a shorter stretch is traced
     instead and reduced to the per-layer metrics, every one of which the
     cell lists has to be found in the trace.  The scope maps that put
     each device op down to its replay and model scope (``scopes.maps``)
     are read from the compiled replay's HLO text after the window has
     closed, so neither the set-up nor the window pays for them;
  8. replays the first three dispatches with the plain reference
     (``reference.py``) and compares, then prints the result as the last
     line of stdout, and the compared numbers beside their limits as the
     last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import catalog  # noqa: E402
import reference  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402
import traffic as T  # noqa: E402
from peaks import peaks  # noqa: E402

FIRST_STEPS = 3          # dispatches the check follows
TRACE_SECONDS = 4.0      # length of the traced stretch of a --trace 1 run
GOSSIP_KERNEL = "a2cid2_gossip"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX traces and backend compiles, from JAX's own events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1


def leaf_slices(shapes) -> list[tuple[int, int]]:
    """(offset, size) of each leaf of one replica in the flat vector:
    the leaves in pytree order, back to back."""
    out, off = [], 0
    for leaf in jax.tree.leaves(shapes):
        size = int(np.prod(leaf.shape, dtype=np.int64))
        out.append((off, size))
        off += size
    return out


class EngineStream:
    """The only place that knows the engine's event-stream format and how
    its replay is called.

    ``Simulator.schedule_executable`` gives the jitted scan and the stream
    arrays ``run_schedule`` would dispatch for a whole schedule; this cuts
    those arrays into dispatches of ``steps`` steps each (one shape for
    every seed), compiles the scan once for that shape, and calls the
    compiled executable.  The reference follows the raw schedule one event
    at a time, so the stream has to take one event per step."""

    # the scan's stream arrays, in the order it takes them; those in
    # NOT_STEPPED carry no step axis, every other one's first axis is the
    # stream's step
    KEYS = ("prologue", "partners", "dt_next", "is_grad", "grad_scale",
            "grad_pos", "t_final")
    NOT_STEPPED = ("prologue", "grad_pos", "t_final")

    def __init__(self, sim, steps: int, backend: str):
        self.sim, self.steps, self.backend = sim, steps, backend
        self.fn = self.compiled = self.hlo = None

    def dispatches(self, state, sched: dict) -> list[dict]:
        """The schedule's stream, cut into whole dispatches; a tail shorter
        than one is not replayed."""
        from repro.core.events import Schedule

        raw = Schedule(sched["partners"], sched["event_times"],
                       sched["event_mask"], sched["grad_times"])
        self.fn, (_, _, arrays) = self.sim.schedule_executable(state, raw)
        arrays = dict(zip(self.KEYS, map(np.asarray, arrays)))
        pattern = np.array([it[0] == "grad" for it in T.items(sched)])
        if not np.array_equal(arrays["is_grad"], pattern):
            raise RuntimeError("the engine's stream does not step through "
                               "the schedule's events one by one; the "
                               "reference cannot follow it")
        return self.cut(arrays)

    def cut(self, arrays: dict) -> list[dict]:
        """Later dispatches start with a zero mixing prologue (the previous
        dispatch's last step already mixed up to their first step), and
        ``grad_pos`` keeps every step, so every dispatch has one shape."""
        steps = self.steps
        prologue = np.asarray(arrays["prologue"], np.float32)
        n = arrays["partners"].shape[-1]
        out = []
        clock = prologue.copy()
        for k in range(len(arrays["is_grad"]) // steps):
            sl = slice(k * steps, (k + 1) * steps)
            chunk = {name: np.asarray(a)[sl] for name, a in arrays.items()
                     if name not in self.NOT_STEPPED}
            clock = clock + chunk["dt_next"].sum(axis=0)
            comm = chunk["partners"][~chunk["is_grad"]]
            chunk.update(
                prologue=prologue if k == 0 else np.zeros_like(prologue),
                grad_pos=np.arange(steps, dtype=np.int32),
                t_final=clock.astype(np.float32),
                grad_ticks=int(chunk["is_grad"].sum()),
                exchanges=int((comm != np.arange(n)).sum()) // 2)
            out.append(chunk)
        return out

    def place(self, chunk: dict) -> tuple:
        return tuple(jnp.asarray(chunk[k]) for k in self.KEYS)

    def compile(self, state, placed: tuple) -> None:
        """Compile the scan for one dispatch's shape; on the Pallas
        backend its program has to hold the gossip kernel."""
        self.compiled = self.fn.lower(self.sim, state, placed).compile()
        self.hlo = self.compiled.as_text()
        if self.backend == "pallas" and not any(
                GOSSIP_KERNEL in line for line in self.hlo.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line):
            raise RuntimeError(f"the compiled replay holds no "
                               f"tpu_custom_call named {GOSSIP_KERNEL}")

    def __call__(self, state, placed: tuple):
        return self.compiled(state, placed)


class Cell:
    """The program built for one cell: the simulator, the weight draw and
    the reading of per-leaf change norms, shared by every seed run in the
    process.  The program draws each worker's batch through ``batch``, the
    seam a test breaks without touching the reference's own draw."""

    def __init__(self, name: str, cfg: dict, traffic: dict, mod,
                 backend: str = "auto"):
        from repro.core import Simulator
        from repro.core.a2cid2 import A2CiD2Params
        from repro.kernels.a2cid2_mixing.ops import resolve_backend

        self.name, self.cfg, self.traffic, self.mod = name, cfg, traffic, mod
        self.workers = traffic["workers"]
        self.steps = traffic["steps_per_dispatch"]
        self.prog = mod.program(cfg, traffic, self.batch)
        self.consts = T.a2cid2_constants(traffic)
        self.backend = resolve_backend(backend)
        self.sim = Simulator(self.prog.grad_fn,
                             A2CiD2Params(**self.consts),
                             gamma=traffic["gamma"], backend=backend,
                             donate=True)
        self.stream = EngineStream(self.sim, self.steps, self.backend)
        self.units = mod.units_per_example(cfg, traffic) * self.workers
        self.init = jax.jit(lambda k: self.prog.pack(
            mod.init_params(k, self.prog.shapes)))
        self.init_tree = jax.jit(lambda k: mod.init_params(
            k, self.prog.shapes))
        slices = leaf_slices(self.prog.shapes)

        @jax.jit
        def norms(bx, bxt, x0):
            def bank(b):
                return jnp.stack([
                    jnp.sqrt(jnp.sum(jnp.square(b[:, o:o + s] - x0[o:o + s]),
                                     axis=1)) for o, s in slices], axis=1)
            return jnp.stack([bank(bx), bank(bxt)])
        self.norms = norms

    def batch(self, key: jax.Array) -> dict:
        """One worker's batch for the program, from its key."""
        return self.mod.example_batch(key, self.cfg, self.traffic)

    # ----------------------------------------------------------- one seed
    def start(self, seed: int) -> dict:
        """State, schedule and dispatches of one seed, on the device; the
        replay compiled for the dispatches' shape."""
        k_w, k_sim = jax.random.split(T.prng_key(seed))
        x0 = self.init(k_w)
        # the replay donates its state, key included: hand it a copy
        state = self.sim.init(x0, self.workers, jnp.copy(k_sim))
        with jax.profiler.TraceAnnotation("schedule"):
            sched = T.schedule(self.traffic, seed, self.traffic["rounds"])
            chunks = self.stream.dispatches(state, sched)
            if len(chunks) <= FIRST_STEPS:
                raise ValueError(f"{self.traffic['rounds']} rounds give "
                                 f"{len(chunks)} dispatches")
            dev = [self.stream.place(c) for c in chunks]
        if self.stream.compiled is None:
            self.stream.compile(state, dev[0])
        return {"seed": seed, "k_w": k_w, "k_sim": k_sim, "x0": x0,
                "state": state, "sched": sched, "chunks": chunks,
                "dev": dev, "trace": None}

    def dispatch(self, run: dict, k: int):
        run["state"], run["trace"] = self.stream(run["state"], run["dev"][k])
        return run["trace"]

    def first_steps(self, run: dict) -> None:
        """The first dispatches, through the window's own call: their
        gradient-tick losses and the change norms after the first and the
        last of them."""
        marks = self.marks()
        losses, run["norms"] = [], {}
        for k in range(FIRST_STEPS):
            tr = self.dispatch(run, k)
            losses.append(tr.loss)
            if (k + 1) * self.steps in marks:
                st = run["state"]
                run["norms"][(k + 1) * self.steps] = np.asarray(
                    self.norms(st.x, st.x_tilde, run["x0"]))
        per_step = np.concatenate([np.asarray(v).reshape(-1)
                                   for v in losses])
        grad = np.concatenate([c["is_grad"] for c in
                               run["chunks"][:FIRST_STEPS]])
        run["losses"] = per_step[grad]

    def marks(self) -> list[int]:
        return [self.steps, FIRST_STEPS * self.steps]

    def window(self, run: dict, seconds: float) -> dict:
        """Dispatch back to back for ``seconds``; the host waits on the
        dispatch before the newest, and the window closes when the last
        is done.  Dispatches after the first steps cycle through the rest
        of the schedule.  ``done_at`` holds the host's time as each wait
        returned: a dispatch that runs slow shows as a long interval."""
        rest = len(run["chunks"]) - FIRST_STEPS
        done, pending, done_at = [], [], []
        t0 = time.perf_counter()
        i = 0
        while True:
            k = FIRST_STEPS + i % rest
            i += 1
            with jax.profiler.TraceAnnotation("dispatch"):
                tr = self.dispatch(run, k)
            done.append((k, tr.loss))
            pending.append(tr.loss)
            if len(pending) > 1:
                with jax.profiler.TraceAnnotation("wait"):
                    pending.pop(0).block_until_ready()
                done_at.append(time.perf_counter())
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("wait"):
            jax.block_until_ready(run["state"])
        elapsed = time.perf_counter() - t0
        done_at.append(t0 + elapsed)
        chunks = [run["chunks"][k] for k, _ in done]
        losses = [np.asarray(v).reshape(-1) for _, v in done]
        failed = sum(not np.all(np.isfinite(v[c["is_grad"]]))
                     for v, c in zip(losses, chunks))
        return {"seconds": elapsed, "dispatches": len(done),
                "failed": int(failed),
                "grad_ticks": sum(c["grad_ticks"] for c in chunks),
                "exchanges": sum(c["exchanges"] for c in chunks),
                "cycles": (i - 1) // rest, "last": done[-1][0],
                "intervals": np.diff(done_at)}

    def reference(self, run: dict, dtype=jnp.float32):
        """The plain reference's losses and change norms over the first
        dispatches, f32 matmuls at full precision unless ``dtype`` is
        lower."""
        x0 = self.init_tree(run["k_w"])
        precision = "highest" if dtype == jnp.float32 else "default"
        with jax.default_matmul_precision(precision):
            return reference.replay(self.mod, self.cfg, self.traffic,
                                    self.consts, run["sched"], x0,
                                    run["k_sim"], self.marks(), dtype)

    def compare(self, run: dict, ref) -> dict:
        ref_losses, ref_norms = ref
        return reference.numbers(run["losses"], run["norms"], ref_losses,
                                 ref_norms, self.marks())


def run_cell(name: str, bench: dict, seed: int, seconds: float,
             trace: bool, devices, *, cfg=None, traffic=None, limits=None,
             backend: str = "auto") -> dict:
    """One run of cell ``name``; returns the result line's object."""
    entry = catalog.workload(name, bench)
    cfg = cfg or catalog.config(entry["config"])
    traffic = traffic or catalog.traffic(entry["traffic"])
    limits = limits or catalog.limits(name)
    mod = catalog.config_module(entry["config"])
    counter = CompileCounter()
    cell = Cell(name, cfg, traffic, mod, backend)
    log(f"cell {name}: {cell.workers} workers, {cell.prog.d} flat params "
        f"per replica, backend {cell.backend}, telemetry off, "
        f"{cell.steps} steps per dispatch")
    run = cell.start(seed)
    cell.first_steps(run)
    jax.block_until_ready(run["state"])
    setup_s = time.perf_counter() - T_START
    log(f"setup {setup_s:.3f} s, {counter.count} traces and compiles")

    before = counter.count
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            with jax.profiler.trace(trace_dir):
                with jax.profiler.TraceAnnotation("window"):
                    win = cell.window(run, min(seconds, TRACE_SECONDS))
            record = trace_reduce.extract(trace_dir)
        else:
            win = cell.window(run, seconds)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    window_compiles = counter.count - before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    last = run["trace"]
    at = np.flatnonzero(run["chunks"][win["last"]]["is_grad"])
    loss = np.asarray(last.loss).reshape(-1)
    consensus = np.asarray(last.consensus).reshape(-1)
    grad = loss[at[-1]] if len(at) else float("nan")
    gaps = win["intervals"]
    log(f"window {win['seconds']:.3f} s: {win['dispatches']} dispatches, "
        f"{win['grad_ticks']} gradient ticks, {win['exchanges']} pairwise "
        f"exchanges, {win['cycles']} passes over the schedule; "
        f"{window_compiles} traces and compiles inside the window")
    log(f"dispatch intervals: min {gaps.min():.6f} s, median "
        f"{np.median(gaps):.6f} s, max {gaps.max():.6f} s over "
        f"{len(gaps)}")
    log(f"last gradient tick: loss {float(grad):.6f}, consensus distance "
        f"{float(consensus[at[-1]]) if len(at) else 0:.6e}")

    units = win["grad_ticks"] * cell.units
    values = {"setup_s": setup_s,
              f"train_{cell.mod.UNIT}_per_s": units / win["seconds"]}

    # free the program's state before the reference takes the chip
    run["state"] = run["trace"] = run["dev"] = run["x0"] = last = None
    ref = cell.reference(run)
    nums = cell.compare(run, ref)
    correct = reference.verdict(nums, limits)

    result = {"correct": bool(correct), "attempted": win["dispatches"],
              "failed": win["failed"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if trace:
        red = trace_reduce.reduce(record, scopes.maps(cell.stream.hlo))
        pk = peaks(dev.device_kind)
        facts = {"chips": len(devices), "traced_units": units,
                 "flops_per_unit": mod.flops_per_unit(cfg, traffic),
                 "peak_flops": pk["flops_bf16"],
                 "peak_hbm": pk["hbm_bytes_per_s"],
                 "workers_per_chip": cell.workers // len(devices),
                 "parameters": cfg["parameters"],
                 "grad_ticks": win["grad_ticks"],
                 "exchanges": win["exchanges"],
                 "cfg": cfg, "traffic": traffic}
        metrics, missing = {}, []
        for m in catalog.cell_metrics(name, bench, "per_layer"):
            v = catalog.metric_reader(m["name"]).read(red, facts)
            if v is None:
                missing.append(m["name"])
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"trace: busy {red['busy_s']:.6f} s of {red['window_s']:.6f} "
            f"s; {red['gossip_calls']} gossip kernel calls, "
            f"{red['gossip_s']:.6f} s")
        if missing:
            raise RuntimeError(f"the trace holds nothing for {missing}, "
                               f"which this cell lists")
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["metrics"] = metrics
        result["breakdown"] = red["breakdown"]
    else:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in catalog.cell_metrics(name, bench, "end_to_end")}
    result["device"] = device
    result["window_compiles"] = window_compiles
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in nums.items()}
    for k, v in nums.items():
        log(f"check {k} {v!r} limit {limits[k]!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    bench = catalog.benchmark()
    entry = catalog.workload(args.workload, bench)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        log(f"bench: this cell needs {entry['chips']} TPU chip(s); JAX "
            f"found {len(devices)} {devices[0].platform} device(s)")
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = devices[:entry["chips"]]
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}")
    result = run_cell(args.workload, bench, args.seed, args.seconds,
                      bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
