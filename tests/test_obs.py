"""Observability layer (DESIGN.md §14): span tracer, metrics registry,
event-schema registry, telemetry sink contracts, XLA-profile
summarization, and the ops report."""
import gc
import gzip
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.core import ClusterSpec
from repro.models.model import Model
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.schema import (
    EVENT_SCHEMAS,
    extract_generated_block,
    render_markdown,
    validate_event,
    validate_events,
)
from repro.obs.trace import (
    NULL_TRACER,
    SCOPES,
    SpanTracer,
    scope_map,
    scope_of,
)
from repro.runtime.serve_loop import ServeConfig, Server
from repro.runtime.telemetry import Telemetry
from repro.serve import Request, SlotScheduler, make_workload

KEY = jax.random.PRNGKey(0)


def _req(rid, arrival=0.0, out_len=4, cls="standard", plen=3):
    return Request(rid=rid, arrival=arrival,
                   prompt=tuple(range(1, plen + 1)), out_len=out_len,
                   deadline_class=cls)


# ------------------------------------------------------------ span tracer
def test_span_nesting_records_depth_parent_attrs():
    tr = SpanTracer()
    with tr.span("decode_chunk", steps=4) as outer:
        with tr.span("dispatch"):
            pass
        outer.set(placed=2)
    inner, top = tr.spans
    assert (inner.name, inner.depth, inner.parent) == ("dispatch", 1,
                                                       "decode_chunk")
    assert (top.name, top.depth, top.parent) == ("decode_chunk", 0, None)
    assert top.attrs == {"steps": 4, "placed": 2}
    assert top.dur_s >= inner.dur_s >= 0.0
    assert top.t0_s <= inner.t0_s


def test_span_exception_propagates_but_still_records():
    tr = SpanTracer()
    with pytest.raises(RuntimeError, match="boom"):
        with tr.span("dispatch"):
            raise RuntimeError("boom")
    assert [s.name for s in tr.spans] == ["dispatch"]


def test_spans_emit_schema_valid_telemetry_events():
    tel = Telemetry(None)
    tr = SpanTracer(tel)
    with tr.span("admit", round=3):
        pass
    (rec,) = tel.events
    assert rec["event"] == "span" and rec["span"] == "admit"
    assert rec["attrs"] == {"round": 3}
    validate_event(rec)


def test_null_tracer_is_one_shared_noop():
    a = NULL_TRACER.span("x", foo=1)
    b = NULL_TRACER.span("y")
    assert a is b  # never allocates on the disabled path
    with a as s:
        s.set(ignored=True)
    assert NULL_TRACER.spans == () and not NULL_TRACER.enabled


def test_span_ring_is_bounded():
    tr = SpanTracer(max_spans=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert [s.name for s in tr.spans] == ["s2", "s3", "s4"]
    with pytest.raises(ValueError, match="max_spans"):
        SpanTracer(max_spans=0)


def test_null_tracer_span_allocates_nothing():
    """The disabled path as the serve loop uses it: no attributes at the
    call, ``set`` only behind ``tracer.enabled``."""
    import tracemalloc

    tracer = NULL_TRACER

    def loop(n):
        for _ in range(n):
            with tracer.span("prepare") as sp:
                if tracer.enabled:
                    sp.set(host_bytes=1)

    loop(10)  # warm the code path
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        loop(10_000)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [d for d in after.compare_to(before, "filename")
             if d.size_diff > 0
             and d.traceback[0].filename.endswith(("trace.py", "test_obs.py"))]
    assert grown == []


@pytest.mark.parametrize("op_name, scope", [
    ("jit(p)/while/body/closed_call/coded_head/solve/jit(lu_solve)/dot",
     "coded_head/solve"),
    ("jit(p)/cond/branch_1_fun/prefill/model/layers/while/body/closed_call/"
     "model/attention/gather", "model/attention"),
    ("jit(p)/while/body/closed_call/model/layers/while/body/dynamic_slice",
     "model/layers"),
    ("jit(p)/cond/branch_1_fun/prefill/select_n", "prefill"),
    ("jit(p)/while/body/closed_call/sample/argmax", "sample"),
    ("jit(prefill)/model/attentions/dot", None),  # whole segments only
    ("jit(p)/while/body/add", None),
])
def test_scope_of_is_the_innermost_named_scope(op_name, scope):
    assert scope_of(op_name) == scope


def test_scope_map_reads_metadata_and_follows_copies_without_it():
    text = "\n".join([
        "%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {",
        '  %param_0.1 = f32[4]{0} parameter(0)',
        '  ROOT %mul.2 = f32[4]{0} multiply(%param_0.1, %param_0.1), '
        'metadata={op_name="jit(p)/model/mlp/mul"}',
        "}",
        "ENTRY %main (p: f32[4]) -> f32[4] {",
        '  %p = f32[4]{0} parameter(0)',
        '  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_name="jit(p)/model/mlp/mul" '
        'stack_frame_id=3}',
        '  %while.5 = (f32[4]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(p)/model/layers/while"}',
        "  %get-tuple-element.6 = f32[4]{0} get-tuple-element(%while.5), "
        'index=0, metadata={op_name="jit(p)/model/layers/while"}',
        "  %copy.7 = f32[4]{0} copy(%get-tuple-element.6), "
        'backend_config={"flag_configs":[]}',
        "  %copy.8 = f32[4]{0} copy(%p)",
        "  %custom-call.3 = (f32[4,4]{1,0:T(8,128)S(1)}, s32[4]{0:T(128)}) "
        'custom-call(%p), custom_call_target="LuDecompositionBlock", '
        'metadata={op_name="jit(p)/coded_head/solve/jit(lu)/lu"}',
        "  %get-tuple-element.4 = f32[4,4]{1,0:T(8,128)S(1)} "
        "get-tuple-element(%custom-call.3), index=0",
        '  ROOT %add.9 = f32[4]{0} add(%copy.7, %fusion.1), '
        'metadata={op_name="jit(p)/add"}',
        "}",
    ])
    assert scope_map(text) == {
        "%mul.2": "model/mlp", "%fusion.1": "model/mlp",
        "%while.5": "model/layers", "%get-tuple-element.6": "model/layers",
        # no metadata: the scope of its operand
        "%copy.7": "model/layers",
        # a tuple shape, with parentheses of its own
        "%custom-call.3": "coded_head/solve",
        "%get-tuple-element.4": "coded_head/solve",
    }
    assert set(SCOPES) >= set(scope_map(text).values())


# -------------------------------------------------------- metrics registry
def test_counter_is_monotonic_and_merges():
    c = Counter()
    assert c.inc() == 1 and c.inc(4) == 5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    other = Counter()
    other.inc(2)
    c.merge(other)
    assert c.value == 7
    c.reset()
    assert c.value == 0


def test_gauge_last_writer_wins():
    g = Gauge()
    g.set(3)
    other = Gauge()
    other.set(9.5)
    g.merge(other)
    assert g.value == 9.5


def test_histogram_percentiles_clamped_to_observed_range():
    h = Histogram(bounds=(1.0, 10.0, 100.0))
    for v in (2.0, 3.0, 4.0, 50.0):
        h.observe(v)
    assert h.count == 4 and h.sum == 59.0 and h.mean == pytest.approx(14.75)
    assert 2.0 <= h.percentile(0.5) <= 10.0
    assert h.percentile(0.0) >= h.min and h.percentile(1.0) <= h.max
    # sparse histograms must not report values outside what was seen
    one = Histogram(bounds=(1.0, 10.0))
    one.observe(5.0)
    assert one.percentile(0.5) == 5.0 == one.percentile(0.99)


def test_histogram_merge_requires_equal_bounds():
    a, b = Histogram(bounds=(1.0, 2.0)), Histogram(bounds=(1.0, 2.0))
    a.observe(0.5)
    b.observe(3.0)
    a.merge(b)
    assert a.count == 2 and a.min == 0.5 and a.max == 3.0
    with pytest.raises(ValueError, match="different bounds"):
        a.merge(Histogram(bounds=(1.0, 3.0)))
    with pytest.raises(ValueError, match="ascending"):
        Histogram(bounds=(2.0, 1.0))


def test_registry_get_or_create_and_type_guard():
    reg = MetricsRegistry()
    c1 = reg.counter("requests_shed", reason="queue_full")
    c2 = reg.counter("requests_shed", reason="queue_full")
    assert c1 is c2 and len(reg) == 1
    reg.counter("requests_shed", reason="deadline_risk")  # distinct labels
    assert len(reg) == 2
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("requests_shed", reason="queue_full")


def test_registry_emit_writes_one_schema_valid_snapshot():
    reg = MetricsRegistry()
    reg.counter("tokens_emitted").inc(42)
    reg.gauge("queue_depth").set(3)
    reg.histogram("request_latency", deadline_class="strict")  # empty
    tel = Telemetry(None)
    reg.emit(tel, phase="serve", rounds=7.0)
    (rec,) = tel.events
    validate_event(rec)
    assert rec["size"] == 3 and rec["phase"] == "serve"
    hist = next(m for m in rec["metrics"] if m["type"] == "histogram")
    assert hist["p50"] is None  # NaN of the empty histogram -> JSON null
    json.dumps(rec)  # strictly serializable
    assert reg.emit(None) is None


def test_registry_merge_folds_counts():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("tokens_emitted").inc(1)
    b.counter("tokens_emitted").inc(2)
    b.counter("requests_admitted").inc(5)
    a.merge(b)
    assert a.counter("tokens_emitted").value == 3
    assert a.counter("requests_admitted").value == 5


def test_scheduler_populates_registry():
    reg = MetricsRegistry()
    sched = SlotScheduler(1, queue_cap=1, metrics=reg)
    sched.offer(_req(0, out_len=2, cls="strict"), 0.0)
    sched.offer(_req(1), 0.0)  # queue full -> shed
    sched.fill_slots(1.0)
    sched.advance(2)
    sched.retire_done(3.0)
    assert sched.admitted == reg.counter("requests_admitted").value == 1
    assert sched.shed == reg.counter("requests_shed_total").value == 1
    assert reg.counter("requests_shed", reason="queue_full").value == 1
    assert reg.counter("tokens_emitted").value == 2
    lat = reg.histogram("request_latency", deadline_class="strict")
    assert lat.count == 1 and lat.max == 3.0
    assert reg.gauge("queue_depth").value == 0


def test_alloc_cache_counters_back_the_info_api():
    from repro.core.schemes import (
        allocate_cache_clear,
        allocate_cache_info,
        make_scheme,
    )

    allocate_cache_clear()
    scheme = make_scheme("optimal")
    cluster = ClusterSpec.make([2, 2], [2.0, 0.5])
    scheme.allocate(cluster, 100)
    first = allocate_cache_info()
    assert first["misses"] == 1 and first["hits"] == 0
    scheme.allocate(cluster, 100)
    again = allocate_cache_info()
    assert again["hits"] == 1 and again["misses"] == 1
    allocate_cache_clear()
    info = allocate_cache_info()
    assert info["size"] == 0 and info["hits"] == info["misses"] == 0


# ------------------------------------------------------------ event schema
def test_validate_event_enforces_contracts():
    good = {"event": "replan", "t": 0, "wall_s": 1.0, "workers": 4,
            "n": 12, "deadline": 1.5}
    assert validate_event(good) is EVENT_SCHEMAS["replan"]
    with pytest.raises(ValueError, match="missing required"):
        validate_event({"event": "replan", "workers": 4})
    with pytest.raises(ValueError, match="undeclared fields"):
        validate_event({**good, "oops": 1})
    with pytest.raises(ValueError, match="unknown event"):
        validate_event({"event": "not_a_thing"})
    with pytest.raises(ValueError, match="no 'event' field"):
        validate_event({"t": 0})
    # optional fields are accepted without being required
    snap = {"event": "metrics_snapshot", "metrics": [], "size": 0}
    validate_event(snap)
    validate_event({**snap, "phase": "serve", "rounds": 3.0})


def test_design_md_event_table_is_generated_and_in_sync():
    design = os.path.join(os.path.dirname(__file__), "..", "DESIGN.md")
    with open(design) as f:
        block = extract_generated_block(f.read())
    assert block == render_markdown(), (
        "DESIGN.md §8 event table is stale — regenerate with: "
        "python -m repro.obs.schema"
    )
    # and the table covers every declared event
    for name in EVENT_SCHEMAS:
        assert f"| `{name}` |" in block


def test_serve_run_emits_only_declared_events_and_spans():
    """End to end: a traced paged serve run's ENTIRE event stream
    satisfies the schema registry, and the loop actually spans."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    server = Server(m, m.init_params(KEY),
                    ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64))
    wl = make_workload("poisson", num_requests=6, prompt_len=(4, 8),
                       out_len=(2, 4), vocab=c.vocab_size)
    tel = Telemetry(None)
    rep = server.serve(wl.trace(seed=3), slots=2, decode_block=2,
                       telemetry=tel)
    assert rep.admitted > 0
    n = validate_events(tel.events, source="paged serve run")
    names = {e["event"] for e in tel.events}
    assert {"span", "metrics_snapshot", "request_admitted",
            "blocks_in_use"} <= names
    spans = {e["span"] for e in tel.events if e["event"] == "span"}
    assert {"serve_setup", "admit", "prepare", "prefill_chunk", "dispatch",
            "retire", "finish"} <= spans
    assert n == len(tel.events) > 0


# ---------------------------------------------------------- telemetry sink
def test_telemetry_stamps_wall_s_and_keeps_caller_override():
    tel = Telemetry(None)
    before = time.perf_counter()
    rec = tel.event("replan", workers=4, n=12, deadline=1.5)
    assert before <= rec["wall_s"] <= time.perf_counter()
    # round_timing-style override: the caller's measured window wins
    rec2 = tel.event("replan", workers=4, n=12, deadline=1.5, wall_s=123.0)
    assert rec2["wall_s"] == 123.0
    assert [r["t"] for r in tel.events] == [0, 1]


def test_telemetry_log_coerces_and_ring_bounds_events(tmp_path):
    tel = Telemetry(str(tmp_path / "t.jsonl"), max_events=3)
    rec = tel.log(0, {"loss": jnp.float32(1.5), "scheme": "optimal"})
    assert rec["loss"] == 1.5 and rec["scheme"] == "optimal"
    for i in range(5):
        tel.event("replan", workers=i, n=1, deadline=1.0)
    assert [r["workers"] for r in tel.events] == [2, 3, 4]  # ring kept 3
    tel.close()
    lines = [json.loads(x) for x in open(tmp_path / "t.jsonl")]
    assert len(lines) == 6  # the JSONL sink stays complete
    with pytest.raises(ValueError, match="max_events"):
        Telemetry(None, max_events=0)


# ----------------------------------------------------- profile attribution
def _write_trace(profile_dir, sub, events):
    d = os.path.join(profile_dir, sub, "plugins", "profile", "run")
    os.makedirs(d)
    with gzip.open(os.path.join(d, "host.trace.json.gz"), "wt") as f:
        json.dump({"traceEvents": events}, f)


def _x(name, ts, dur):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "pid": 0}


def test_profile_summarize_merges_phase_captures(tmp_path):
    from repro.obs.profile import diff_summaries, format_diff, summarize

    # two capture sessions with unrelated time bases, one phase each
    _write_trace(tmp_path, "generate", [
        _x("jit_generate#meta#", 1000, 100),
        _x("matmul", 1010, 40), _x("matmul", 1060, 20),
        _x("outside_window", 5000, 50),
    ])
    _write_trace(tmp_path, "prefill", [
        _x("prefill", 40, 10), _x("splice", 42, 6),
    ])
    summ = summarize(str(tmp_path), ("jit_generate", "prefill"))
    assert summ["jit_generate"]["wall_us"] == 100
    assert summ["jit_generate"]["n_ops"] == 2
    assert summ["jit_generate"]["ops"][0] == {
        "name": "matmul", "total_us": 60.0, "count": 2,
    }
    assert summ["prefill"]["wall_us"] == 10 and summ["prefill"]["n_ops"] == 1

    golden = {
        "jit_generate": {"wall_us": 50.0, "op_total_us": 60.0, "n_ops": 2,
                         "ops": [{"name": "matmul", "total_us": 60.0,
                                  "count": 2}]},
        "prefill": {"wall_us": 10.0, "op_total_us": 6.0, "n_ops": 1,
                    "ops": []},
    }
    diff = diff_summaries(summ, golden)
    assert diff["worst_phase"] == "jit_generate"
    assert diff["worst_ratio"] == pytest.approx(2.0)
    text = format_diff(diff)
    assert "jit_generate" in text and "<-- regressed" in text
    assert "matmul" in text


def test_profile_summarize_raises_without_captures(tmp_path):
    from repro.obs.profile import summarize

    with pytest.raises(FileNotFoundError, match="no profiler capture"):
        summarize(str(tmp_path), ("jit_generate",))


# --------------------------------------------------------------- obsreport
def _report_records():
    tel = Telemetry(None)
    tr = SpanTracer(tel)
    with tr.span("decode_chunk", steps=2):
        with tr.span("dispatch"):
            pass
    tel.event("request_admitted", request_id=0, slot=0, queue_wait=1.0,
              deadline_class="standard", round=1.0)
    tel.event("request_done", request_id=0, slot=0, tokens=4, latency=9.0,
              deadline_class="standard", round=10.0)
    tel.event("request_evicted", request_id=1, reason="queue_full",
              deadline_class="strict", round=2.0, queue_depth=3)
    tel.event("adapt_decision", round=4, replanned=True,
              reason="improvement", current=2.0, candidate=1.5, gain=0.25,
              deadline=1.9, workers=4)
    tel.event("round_timing", round=0, wall_s=0.5, dispatch_s=0.4,
              pad_wall_s=0.0, scale=1.1, unit_s=0.01, workers=4, fed=True,
              skipped=None, t_max=0.2, t_mean=0.1)
    tel.event("blocks_in_use", in_use=3, free=1, capacity=4, request_id=0,
              round=1.0)
    tel.event("kv_bytes", bytes_in_use=384, bytes_total=512,
              utilization=0.75, request_id=0, round=1.0)
    reg = MetricsRegistry()
    reg.counter("tokens_emitted").inc(4)
    reg.emit(tel, phase="serve", rounds=10.0)
    validate_events(tel.events)
    return list(tel.events) + [{"step": 0, "loss": 2.5}]


def test_obsreport_renders_every_section():
    from repro.launch.obsreport import render_report

    md = render_report(_report_records(), source="unit.jsonl")
    for heading in ("# Ops report", "## Overview", "## Span waterfall",
                    "## Request latency", "## Replan / decision timeline",
                    "## Straggler-estimate drift", "## KV block pool",
                    "## Metrics snapshot"):
        assert heading in md, f"missing section {heading!r}"
    assert "`decode_chunk`" in md and "1 scalar log lines" in md
    assert "`deadline_risk`" not in md  # only observed reasons appear
    assert "UNDECLARED" not in md


def test_obsreport_cli_writes_files_and_requires_spans(tmp_path, capsys):
    from repro.launch.obsreport import main

    src = tmp_path / "run.jsonl"
    with open(src, "w") as f:
        for rec in _report_records():
            f.write(json.dumps(rec) + "\n")
    out, html = tmp_path / "r.md", tmp_path / "r.html"
    main([str(src), "-o", str(out), "--html", str(html),
          "--require-spans"])
    assert "## Span waterfall" in out.read_text()
    assert html.read_text().startswith("<!doctype html>")
    assert "span coverage: 2 spans" in capsys.readouterr().out

    bare = tmp_path / "untraced.jsonl"
    with open(bare, "w") as f:
        f.write(json.dumps({"event": "replan", "t": 0, "wall_s": 0.0,
                            "workers": 2, "n": 4, "deadline": 1.0}) + "\n")
    main([str(bare)])  # fine without the flag
    with pytest.raises(SystemExit, match="no span events"):
        main([str(bare), "--require-spans"])


# ------------------------------------------------------- overhead (gated)
@pytest.mark.slow
def test_span_tracing_overhead_within_two_percent():
    """The instrumented serve loop must cost <= 2% wall time (the
    observability layer's budget). Run-to-run serve wall jitters ~10% on a loaded host —
    a raw traced-vs-untraced A/B at a 2% bound is a coin flip — so the
    budget is checked as (spans recorded by a real traced serve) x
    (per-span cost from a tight microbenchmark, which IS stable)
    against the untraced serve floor. What a wall-clock A/B could catch
    beyond that, tracing forcing a retrace, is checked exactly: the
    traced and untraced serves run the same compiled programs."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    server = Server(m, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64))
    wl = make_workload("poisson", num_requests=24, prompt_len=(4, 8),
                       out_len=(8, 16), vocab=c.vocab_size)
    trace = wl.trace(seed=5)

    def run(tracer) -> float:
        t0 = time.perf_counter()
        server.serve(trace, slots=2, decode_block=2, tracer=tracer)
        return time.perf_counter() - t0

    run(SpanTracer())  # shared warmup: all programs compile first
    traces = server.serve_traces
    tracer = SpanTracer()
    run(tracer)
    n_spans = len(tracer.spans)
    assert n_spans > 100, "workload too small to exercise tracing"
    assert {"serve_setup", "admit", "prepare", "prefill_chunk",
            "decode_chunk", "dispatch", "retire", "finish"} <= {
        s.name for s in tracer.spans}
    off = min(run(NULL_TRACER) for _ in range(2))
    # tracing never retraces: both modes ran the programs compiled first
    assert server.serve_traces == traces

    reps = 20_000

    def spans(tr) -> float:
        # the loop's use of a span: no attributes unless tracing is on
        t0 = time.perf_counter()
        for _ in range(reps):
            with tr.span("prepare") as s:
                if tr.enabled:
                    s.set(host_bytes=0)
        return time.perf_counter() - t0

    # a full collection that the serves above left due would otherwise
    # fall inside the span loop and be billed to the spans
    gc.collect()
    bench = SpanTracer()  # one tracer, like the serve loop holds one
    per_span_s = max(0.0, (spans(bench) - spans(NULL_TRACER)) / reps)

    cost = n_spans * per_span_s
    assert cost <= 0.02 * off, (
        f"span tracing budget blown: {n_spans} spans x "
        f"{per_span_s * 1e6:.2f}us = {cost * 1e3:.2f}ms > 2% of "
        f"{off * 1e3:.1f}ms untraced serve"
    )
