"""Flight recorder, host side: span tracer + metrics registry
(analysis/tracing.py, analysis/metrics.py, DESIGN.md §15).

The contracts under test:

  * golden schema — a tracer used the way the fleet/benchmarks use it
    (nested spans, counters, instants, multiple processes/lanes) emits a
    trace that ``validate_trace`` accepts, that survives a JSON
    write/``load_trace`` round-trip, and whose metadata events announce
    every process/lane exactly once;
  * schema gate actually gates — each malformed-event family raises;
  * metrics semantics — counters are monotonic, histograms expose
    Prometheus cumulative le-buckets, kind collisions are errors;
  * exposition round-trip — ``parse_exposition(reg.exposition())``
    recovers every sample value, labels and +Inf buckets included;
  * one clock — replay scopes take only their own names, and host spans
    (``tracing.span``, ``SpanTracer.span``, ``gc_spans``) land in the
    profiler's host plane, beside the device ops.
"""
import gc
import glob
import json
import math

import jax
import numpy as np
import pytest

from repro.analysis import (MetricsRegistry, SpanTracer, load_trace,
                            parse_exposition, tracing, validate_trace)


def _bench_shaped_tracer():
    """Exercise the tracer the way fleet.run / benchmarks/run.py do."""
    tr = SpanTracer("bench", metadata={"family": "serve", "seed": 0})
    with tr.span("bench.serve", lane="bench", args={"seed": 0}):
        for r in range(3):
            args = {"round": r}
            with tr.span("fleet.round", process="fleet", lane="rounds",
                         args=args):
                with tr.span("fleet.decode", process="fleet",
                             lane="decode",
                             args={"round": r,
                                   "active_slots": np.int64(2)}):
                    pass
                args["alive"] = 4     # filled in before the span closes
            tr.counter("fleet.queue", {"queue_depth": r,
                                       "slot_occupancy": np.float32(0.5)},
                       process="fleet")
        tr.instant("churn.kill", process="fleet", lane="churn",
                   args={"worker": 1, "round": 2})
    return tr


def test_trace_schema_golden(tmp_path):
    tr = _bench_shaped_tracer()
    obj = tr.to_dict()
    validate_trace(obj)  # does not raise
    assert obj["displayTimeUnit"] == "ms"
    assert obj["metadata"] == {"family": "serve", "seed": 0}

    names = [e["name"] for e in obj["traceEvents"]]
    for expected in ("bench.serve", "fleet.round", "fleet.decode",
                     "fleet.queue", "churn.kill"):
        assert expected in names

    # processes/lanes announced exactly once, as metadata events
    procs = [e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert sorted(procs) == ["bench", "fleet"]
    lanes = [e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert sorted(lanes) == ["bench", "churn", "decode", "rounds"]

    # numpy leaked into args must already be plain JSON types
    path = tmp_path / "TRACE_test.json"
    tr.write(str(path))
    loaded = load_trace(str(path))
    assert loaded == json.loads(json.dumps(obj))


def test_span_timestamps_nest_and_order():
    tr = _bench_shaped_tracer()
    spans = [e for e in tr.events if e["ph"] == "X"]
    outer = [e for e in spans if e["name"] == "bench.serve"]
    assert len(outer) == 1
    o = outer[0]
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0
        if e is not o:  # every other span closed inside the outer one
            assert e["ts"] >= o["ts"]
            assert e["ts"] + e["dur"] <= o["ts"] + o["dur"] + 1e-6


@pytest.mark.parametrize("mutate, message", [
    (lambda ev: ev.update(ph="B"), "unknown phase"),
    (lambda ev: ev.update(name=""), "name"),
    (lambda ev: ev.update(pid="fleet"), "pid"),
    (lambda ev: ev.pop("dur"), "dur"),
    (lambda ev: ev.update(args=[1, 2]), "args"),
])
def test_validate_trace_rejects(mutate, message):
    tr = SpanTracer("t")
    with tr.span("ok"):
        pass
    obj = tr.to_dict()
    ev = [e for e in obj["traceEvents"] if e["ph"] == "X"][0]
    mutate(ev)
    with pytest.raises(ValueError, match=message):
        validate_trace(obj)


def test_validate_trace_rejects_bad_counter_and_shape():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"events": []})
    with pytest.raises(ValueError, match="JSON object"):
        validate_trace([])
    tr = SpanTracer("t")
    tr.counter("q", {"depth": 3})
    obj = tr.to_dict()
    [c] = [e for e in obj["traceEvents"] if e["ph"] == "C"]
    c["args"] = {"depth": "three"}
    with pytest.raises(ValueError, match="numeric"):
        validate_trace(obj)


# ----------------------------------------------------------------- metrics

def test_counter_monotonic_and_kind_collision():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests seen")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    # same name, same labels -> the SAME child; different labels -> new
    assert reg.counter("requests_total") is c
    assert reg.counter("requests_total", labels={"arm": "a"}) is not c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("requests_total")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")


def test_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("ttft_rounds", "time to first token",
                      buckets=(1, 2, 4))
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(104.5)
    assert h.cumulative() == [2, 2, 3, 4]  # le=1, le=2, le=4, +Inf
    with pytest.raises(ValueError, match="strictly"):
        reg.histogram("bad_hist", buckets=(2, 1))


def test_exposition_roundtrip():
    reg = MetricsRegistry()
    reg.counter("fleet_requests_total", "admitted",
                labels={"fleet": "ring"}).inc(7)
    reg.gauge("fleet_drain_rounds", "drain tail").set(33)
    h = reg.histogram("fleet_ttft_rounds", "ttft", buckets=(1, 2, 4))
    for v in (0.5, 3.0, 9.0):
        h.observe(v)

    text = reg.exposition()
    assert "# TYPE fleet_requests_total counter" in text
    assert "# HELP fleet_ttft_rounds ttft" in text

    parsed = parse_exposition(text)
    assert parsed["fleet_requests_total"]['{fleet="ring"}'] == 7
    assert parsed["fleet_drain_rounds"][""] == 33
    buckets = parsed["fleet_ttft_rounds_bucket"]
    assert buckets['{le="1"}'] == 1
    assert buckets['{le="4"}'] == 2
    assert buckets['{le="+Inf"}'] == 3
    assert parsed["fleet_ttft_rounds_count"][""] == 3
    assert parsed["fleet_ttft_rounds_sum"][""] == pytest.approx(12.5)

    with pytest.raises(ValueError):
        parse_exposition("just words without value structure {")


def test_snapshot_is_jsonable_and_complete():
    reg = MetricsRegistry()
    reg.counter("a_total").inc(2)
    reg.histogram("lat", buckets=(1.0,)).observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["a_total"]["series"]["{}"] == 2
    assert snap["lat"]["series"]["{}"]["buckets"] == {"1": 1, "+Inf": 1}
    assert snap["lat"]["series"]["{}"]["count"] == 1


def test_exposition_handles_inf_and_label_escaping():
    reg = MetricsRegistry()
    reg.gauge("edge_case", labels={"path": 'a\\b says "hi"'}).set(math.inf)
    text = reg.exposition()
    assert "+Inf" in text
    parsed = parse_exposition(text)
    [(labels, value)] = parsed["edge_case"].items()
    assert value == math.inf
    assert '\\\\' in labels and '\\"' in labels


# -------------------------------------------------- scopes and one clock

def test_scope_takes_only_replay_names():
    assert len(set(tracing.SCOPES)) == len(tracing.SCOPES)
    with pytest.raises(ValueError, match="unknown replay scope"):
        tracing.scope("replay.grads")

    @jax.jit
    def f(x):
        with tracing.scope("replay.grad"):
            return x * 2.0
    assert 'op_name="jit(f)/replay.grad/mul"' in \
        f.lower(1.0).compile().as_text()


def test_span_args_filled_inside_the_block():
    tr = SpanTracer("t")
    args = {"worlds": 2}
    with tr.span("dispatch.grid", args=args):
        args["jit_traces"] = 1
    [ev] = [e for e in tr.events if e["ph"] == "X"]
    assert ev["args"] == {"worlds": 2, "jit_traces": 1}


def test_host_spans_share_the_profiler_clock(tmp_path):
    """``tracing.span``, ``SpanTracer.span`` and a ``gc`` span opened
    under ``jax.profiler.trace`` are events of the profile's host plane."""
    from jax.profiler import ProfileData

    tr = SpanTracer("t")
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("replay.dispatch"):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
        with tr.span("fleet.round"):
            pass
        with tracing.gc_spans() as stats:
            gc.collect()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events}
    assert {"replay.dispatch", "fleet.round", "gc"} <= names
    assert stats.collections >= 1


def test_gc_spans_count_collections_and_unhook():
    hooks = list(gc.callbacks)
    with tracing.gc_spans() as stats:
        gc.collect()
        gc.collect()
    assert gc.callbacks == hooks
    assert stats.collections == 2
    assert 0 < stats.longest <= stats.seconds
    gc.collect()
    assert stats.collections == 2
