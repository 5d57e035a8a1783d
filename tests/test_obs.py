"""Observability layer (obs/): distributed tracing, the engine step
log, and mergeable fixed-bucket metrics.

Pins the four contracts OBSERVABILITY.md promises:

* histograms with process-wide fixed bounds merge EXACTLY and their
  quantiles preserve stochastic dominance (total >= ttft);
* one traced request produces ONE connected span tree across client,
  router, replica and kv-transfer source — over loopback and over the
  real MQTT broker — exported as valid Chrome trace-event JSON
  (golden-file pinned);
* zero-cost discipline: every ``trace.TRACER`` / ``steplog.RECORDER``
  site is ``is not None``-guarded, jitted modules import no obs
  symbol, and installing tracer+recorder leaves the serve-chunk jaxpr
  byte-identical;
* the log handler joins records to traces and rate-limits observably;
  every actor answers ``(metrics …)`` with Prometheus text.
"""

import json
import logging
import pathlib
import time

import numpy as np
import pytest

from aiko_services_tpu.obs import flight, steplog, trace
from aiko_services_tpu.obs.metrics import (
    DEFAULT_BOUNDS, CounterDict, Histogram, MetricsRegistry, REGISTRY,
)
from aiko_services_tpu.utils.sexpr import generate, parse

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "aiko_services_tpu"

#: One bucket spans 10^(1/8) ≈ 1.334× — the quantile error bound.
BUCKET_RATIO = 10.0 ** (1.0 / 8.0)


@pytest.fixture(autouse=True)
def _no_leaked_obs():
    """Never let a tracer or recorder escape the test that armed it."""
    yield
    trace.uninstall()
    steplog.uninstall()
    flight.uninstall()


# ---------------------------------------------------------------- #
# Histograms: quantile bounds, exact merge, wire encoding
# ---------------------------------------------------------------- #

def test_histogram_quantile_within_one_bucket():
    for value in (0.04, 1.0, 17.3, 950.0, 42_000.0):
        histogram = Histogram(name="h")
        histogram.observe(value)
        estimate = histogram.quantile(0.5)
        assert value / BUCKET_RATIO <= estimate <= value * BUCKET_RATIO
    empty = Histogram()
    assert empty.quantile(0.5) == 0.0 and empty.mean == 0.0


def test_histogram_merge_is_exact():
    """merge(a, b) is indistinguishable from having observed every
    sample into ONE histogram — the property that makes cross-replica
    fleet quantiles exact rather than an approximation."""
    import random as _random
    rng = _random.Random(3)
    samples_a = [rng.lognormvariate(3.0, 1.5) for _ in range(200)]
    samples_b = [rng.lognormvariate(5.0, 0.5) for _ in range(300)]
    a, b, combined = Histogram(), Histogram(), Histogram()
    for value in samples_a:
        a.observe(value)
        combined.observe(value)
    for value in samples_b:
        b.observe(value)
        combined.observe(value)
    merged = Histogram.merged([a, b], name="fleet")
    assert merged.counts == combined.counts
    assert merged.count == combined.count == 500
    assert merged.sum == pytest.approx(combined.sum)
    for q in (0.01, 0.25, 0.5, 0.9, 0.95, 0.99):
        assert merged.quantile(q) == combined.quantile(q)
    # Originals untouched by the classmethod merge.
    assert a.count == 200 and b.count == 300


def test_histogram_dominance_preserved_by_buckets():
    """Per-request ``total >= ttft`` implies the same inequality for
    every bucket-midpoint quantile — the ``total_p50 >= ttft_p50``
    share assertion in test_continuous relies on this."""
    import random as _random
    rng = _random.Random(7)
    ttft, total = Histogram(), Histogram()
    for _ in range(400):
        first = rng.lognormvariate(3.0, 1.0)
        ttft.observe(first)
        total.observe(first + rng.lognormvariate(2.0, 1.0))
    for q in (0.1, 0.5, 0.9, 0.95, 0.99):
        assert total.quantile(q) >= ttft.quantile(q)


def test_histogram_encode_decode_roundtrip():
    histogram = Histogram(name="ttft")
    for value in (0.5, 12.0, 12.1, 9_999.0, 10.0 ** 7):  # + overflow
        histogram.observe(value)
    clone = Histogram.decode(histogram.encode(), name="ttft")
    assert clone.counts == histogram.counts
    assert clone.count == histogram.count
    assert clone.sum == pytest.approx(histogram.sum, rel=1e-5)  # %.6g
    assert clone.quantile(0.5) == histogram.quantile(0.5)
    # Sparse: only non-empty buckets ride the wire (12.0 and 12.1
    # share one — that's the bucket resolution).
    assert histogram.encode().count("=") == 4
    empty = Histogram.decode(Histogram().encode())
    assert empty.count == 0 and empty.counts == [0] * (
        len(DEFAULT_BOUNDS) + 1)
    with pytest.raises(ValueError):
        Histogram.decode("h9:1:1:0=1")


def test_registry_prometheus_and_counter_dict():
    registry = MetricsRegistry()
    registry.counter("aiko_requests_total",
                     labels={"actor": "r0"}).inc(3)
    registry.gauge("aiko_queue_depth").set(7)
    histogram = registry.histogram("aiko_ttft_ms")
    histogram.observe(25.0)
    # Get-or-create: same (name, labels) → same instance.
    assert registry.histogram("aiko_ttft_ms") is histogram
    text = registry.to_prometheus()
    assert '# TYPE aiko_requests_total counter' in text
    assert 'aiko_requests_total{actor="r0"} 3' in text
    assert "# TYPE aiko_queue_depth gauge" in text
    assert "# TYPE aiko_ttft_ms histogram" in text
    assert 'aiko_ttft_ms_bucket{le="+Inf"} 1' in text
    assert "aiko_ttft_ms_count 1" in text
    snapshot = registry.snapshot()
    assert snapshot["aiko_queue_depth"] == 7
    assert snapshot["aiko_ttft_ms"]["count"] == 1
    # CounterDict: plain dict semantics + mirrored gauges.
    counters = CounterDict({"shed": 0}, "router",
                           labels={"actor": "r0"}, registry=registry)
    counters["shed"] += 2
    assert counters["shed"] == 2
    assert registry.gauge("aiko_router_shed",
                          labels={"actor": "r0"}).value == 2


# ---------------------------------------------------------------- #
# Tracing: spans, propagation helpers, Chrome export (golden)
# ---------------------------------------------------------------- #

def test_inject_extract_and_synth_span():
    context = trace.extract("abc123/def456")
    assert (context.trace_id, context.span_id) == ("abc123", "def456")
    assert trace.inject(context) == "abc123/def456"
    for junk in (None, "", "nodelim", "/", "x/", "/y", 17):
        assert trace.extract(junk) is None
    span = trace.synth_span("queue", "abc123/def456", "replica_0",
                            10.0, 10.5, attrs={"depth": 3})
    assert span.trace_id == "abc123" and span.parent_id == "def456"
    assert span.end == 10.5 and span.duration_ms == pytest.approx(500)
    # No parent context → fresh root trace.
    root = trace.synth_span("x", None, "svc", 1.0, 2.0)
    assert root.parent_id is None and len(root.trace_id) == 24


def test_span_codec_roundtrip_with_marks():
    span = trace.Span("t" * 24, "s" * 16, "p" * 16, "decode",
                      "replica", 100.0, attrs={"tokens": 5})
    span.end = 101.5
    span.mark("first_token", 100.2)
    decoded = trace.decode_spans(trace.encode_spans([span]))
    assert len(decoded) == 1
    clone = decoded[0]
    assert (clone.trace_id, clone.span_id, clone.parent_id) == \
        (span.trace_id, span.span_id, span.parent_id)
    assert clone.attrs == {"tokens": 5}
    assert clone.marks == [("first_token", 100.2)]
    assert trace.decode_spans("not json") == []
    assert trace.decode_spans(json.dumps([{"bogus": 1}])) == []


def test_tracer_context_nesting_and_ring():
    tracer = trace.install(trace.Tracer(service="svc", seed=11))
    assert trace.current_ids() is None
    with tracer.span("outer") as outer:
        assert trace.current_ids() == (outer.trace_id, outer.span_id)
        with tracer.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
    assert trace.current_ids() is None
    names = [span.name for span in tracer.finished()]
    assert names == ["inner", "outer"]       # finish order
    assert all(span.end is not None for span in tracer.finished())
    assert len(tracer.drain()) == 2 and tracer.finished() == []
    # Seeded tracers are reproducible (golden-file prerequisite).
    again = trace.Tracer(service="svc", seed=11)
    assert again.start_span("outer").span_id == \
        trace.Tracer(service="svc", seed=11).start_span("outer").span_id


def test_chrome_events_golden():
    """The exporter's exact event stream for a small cross-service
    tree — services get stable sorted pids, spans become X events,
    marks instants, and the cross-service edge an s/f flow pair."""
    root = trace.Span("aa" * 12, "11" * 8, None, "infer", "client", 1.0)
    root.end = 1.001
    child = trace.Span("aa" * 12, "22" * 8, "11" * 8, "decode",
                       "replica", 1.0002, attrs={"tokens": 2})
    child.end = 1.0008
    child.mark("first_token", 1.0004)
    events = trace.chrome_events([root, child])
    flow_id = int("22" * 4, 16)
    assert events == [
        {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
         "args": {"name": "client"}},
        {"ph": "M", "name": "process_name", "pid": 2, "tid": 0,
         "args": {"name": "replica"}},
        {"ph": "X", "name": "infer", "cat": "span", "pid": 1, "tid": 1,
         "ts": 1_000_000, "dur": 1_000,
         "args": {"trace_id": "aa" * 12, "span_id": "11" * 8}},
        {"ph": "X", "name": "decode", "cat": "span", "pid": 2,
         "tid": 1, "ts": 1_000_200, "dur": 600,
         "args": {"tokens": 2, "trace_id": "aa" * 12,
                  "span_id": "22" * 8, "parent_id": "11" * 8}},
        {"ph": "i", "name": "first_token", "cat": "mark", "pid": 2,
         "tid": 1, "ts": 1_000_400, "s": "t"},
        {"cat": "trace", "name": "link", "id": flow_id, "ph": "s",
         "pid": 1, "tid": 1, "ts": 1_000_000},
        {"cat": "trace", "name": "link", "id": flow_id, "ph": "f",
         "bp": "e", "pid": 2, "tid": 1, "ts": 1_000_200},
    ]


def test_export_chrome_writes_valid_json(tmp_path):
    span = trace.Span("ab" * 12, "cd" * 8, None, "infer", "svc", 5.0)
    span.end = 5.01
    path = trace.export_chrome(str(tmp_path / "t.json"), [span])
    with open(path, encoding="utf-8") as handle:
        document = json.load(handle)
    assert document["displayTimeUnit"] == "ms"
    assert {event["ph"] for event in document["traceEvents"]} == \
        {"M", "X"}


# ---------------------------------------------------------------- #
# Step log: ring, counts, Chrome rendering
# ---------------------------------------------------------------- #

def test_steplog_ring_bounds_and_counts():
    recorder = steplog.StepRecorder(capacity=4)
    for step in range(6):
        recorder.record("dispatch", step=step)
    assert len(recorder.events()) == 4
    assert recorder.dropped == 2
    assert recorder.events()[0][2]["step"] == 2   # oldest fell off
    recorder.record("sync", wait_ms=1.5)
    assert recorder.counts() == {"dispatch": 3, "sync": 1}
    recorder.clear()
    assert recorder.events() == [] and recorder.dropped == 0


def test_steplog_chrome_events_durations():
    recorder = steplog.StepRecorder()
    recorder.record("dispatch", ring=2)
    recorder.record("sync", wait_ms=2.0, steps=4)
    events = recorder.chrome_events(pid=9)
    assert events[0]["ph"] == "M"
    instant, duration = events[1], events[2]
    assert instant["ph"] == "i" and instant["name"] == "dispatch"
    assert duration["ph"] == "X" and duration["name"] == "sync"
    assert duration["dur"] == 2_000                 # µs
    # The wait is measured THEN recorded: the X event ends at the
    # recorded timestamp.
    assert duration["ts"] + duration["dur"] == \
        pytest.approx(instant["ts"], abs=5_000_000)
    assert duration["args"]["steps"] == 4


def test_steplog_spans_have_a_start_an_end_and_two_views():
    recorder = steplog.StepRecorder()
    span = recorder.begin("dispatch", chunk=7, steps=8)
    span.note(slice_slot=3, request_id="r9")
    span.end(ring=2)
    recorder.begin("admission").drop()          # an empty phase
    recorder.record("token_dispatch", ms=0.5)   # an instant
    (start, end, name, fields), instant = recorder.spans()
    assert name == "dispatch" and end >= start
    assert fields == {"chunk": 7, "steps": 8, "slice_slot": 3,
                      "request_id": "r9", "ring": 2}
    assert instant[0] == instant[1] and instant[2] == "token_dispatch"
    # The older view: one row per span, stamped with its end.
    assert recorder.events() == [(end, "dispatch", fields),
                                 (instant[1], "token_dispatch",
                                  {"ms": 0.5})]
    assert recorder.counts() == {"dispatch": 1, "token_dispatch": 1}
    drawn = recorder.chrome_events()[1]
    assert drawn["ph"] == "X" and drawn["name"] == "dispatch"
    assert drawn["ts"] + drawn["dur"] == pytest.approx(end * 1e6, abs=2)
    assert drawn["args"]["request_id"] == "r9"


#: Names of ``events()`` for the scripted run below, recorded on the
#: commit before the step log had spans (d19211b): the older view
#: keeps its names and their order.
SCRIPTED_EVENTS = (
    ["admission", "paged_prefill", "sampling_edit", "state_upload",
     "dispatch", "dispatch", "sync", "token_dispatch", "commit",
     "admission", "state_upload", "dispatch", "sync", "token_dispatch",
     "commit", "admission", "sync", "token_dispatch", "commit",
     "admission", "sampling_edit", "state_upload", "dispatch", "sync",
     "token_dispatch", "commit"]
    + ["admission", "paged_prefill", "sampling_edit", "state_upload",
       "dispatch", "sync", "token_dispatch", "commit"])


def test_steplog_events_of_a_scripted_run_are_what_they_were():
    """Whole-bucket admission, a chunked admission riding decode
    chunks and one finishing standalone, on a paged server with the
    ring pinned at depth 2: ``events()`` names the same phases in the
    same order as before the recorder kept spans; every span ends
    after it starts, nests or follows its neighbours, and is drawn as
    a complete event."""
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer,
    )
    server = PagedContinuousServer(
        config_name="tiny", slots=4, max_seq=256, chunk_steps=4, seed=3,
        block_size=16, total_blocks=64, chunk_prefill_tokens=32,
        ring_max=2)

    def request(name, length, new):
        prompt = np.random.default_rng([0, length]).integers(
            1, server.config.vocab_size, length)
        return DecodeRequest(request_id=name,
                             prompt=prompt.astype(np.int32),
                             max_new_tokens=new)

    recorder = steplog.install()
    server.submit(request("a", 20, 12))
    server.step()
    server.submit(request("b", 70, 4))
    server.run_until_drained()
    server.submit(request("c", 20, 4))
    server.run_until_drained()
    steplog.uninstall()

    assert [name for _, name, _ in recorder.events()] == SCRIPTED_EVENTS
    spans = recorder.spans()
    assert all(end >= start for start, end, _, _ in spans)
    assert [end for _, end, _, _ in spans] == \
        sorted(end for _, end, _, _ in spans)
    timed = [span for span in spans if span[1] > span[0]]
    assert {name for _, _, name, _ in timed} == {
        "admission", "paged_prefill", "sampling_edit", "state_upload",
        "dispatch", "sync", "commit"}
    drawn = [event for event in recorder.chrome_events()
             if event["ph"] != "M"]
    assert {event["ph"] for event in drawn} == {"X"}
    # The cause of a dispatch: the slice it carried, and for whom.
    mixed = [fields for _, _, name, fields in spans
             if name == "dispatch" and "slice_slot" in fields]
    assert mixed and all(fields["request_id"] == "b"
                         and fields["slice_width"] == 32
                         for fields in mixed)
    serials = [fields["chunk"] for _, _, name, fields in spans
               if name == "dispatch"]
    assert serials == list(range(1, len(serials) + 1))


def test_steplog_install_switchboard():
    assert steplog.RECORDER is None
    recorder = steplog.install(capacity=16)
    assert steplog.RECORDER is recorder
    steplog.uninstall()
    assert steplog.RECORDER is None


# ---------------------------------------------------------------- #
# Zero-cost discipline: AST guards + jaxpr pinning
# ---------------------------------------------------------------- #

def _load_obs_lint():
    """The AST sweeps live in ``scripts/obs_lint.py`` (standalone /
    pre-commit tool); tier-1 runs the SAME code via this loader so
    the lint and the tests can never drift apart."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_lint", REPO / "scripts" / "obs_lint.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_every_obs_site_is_guarded():
    obs_lint = _load_obs_lint()
    offenders, sites = obs_lint.check_guarded_sites()
    assert not offenders, \
        f"unguarded TRACER/RECORDER/FLIGHT sites: {offenders}"
    # The instrumentation is real, not vestigial: the engine has the
    # dispatch/sync/commit/admission/state_upload/sampling sites plus
    # the tracing sites in router/client/loadgen and the flight
    # trigger sites in watchdog/faults/autoscaler/actor.
    assert sites >= 20


def test_obs_lint_covers_the_new_modules():
    """The lint's site list includes every module that gained a
    flight trigger — a new trigger site added without lint coverage
    is the regression this pins against."""
    obs_lint = _load_obs_lint()
    names = {path.name for path in obs_lint.SITE_MODULES}
    assert {"continuous.py", "serving.py", "autoscaler.py",
            "actor.py", "faults.py"} <= names
    assert obs_lint.SWITCHBOARDS["flight"] == "FLIGHT"
    assert obs_lint.main([]) == 0


def test_steplog_covers_the_engine_step_events():
    source = (PKG / "orchestration" / "continuous.py").read_text()
    for event in ("dispatch", "sync", "commit", "admission",
                  "state_upload", "sampling_edit"):
        assert f'"{event}"' in source, f"engine lost the {event} site"
    paged = (PKG / "orchestration" / "paged.py").read_text()
    assert '"paged_prefill"' in paged


def test_no_obs_code_in_jitted_modules():
    """ops/ and models/ must not import ANY obs symbol — invariant 7:
    observability cannot reach a traced program."""
    obs_lint = _load_obs_lint()
    offenders = obs_lint.check_jit_dirs()
    assert not offenders, f"obs imports in jitted modules: {offenders}"


def test_installed_obs_does_not_change_jaxpr(tmp_path):
    """Tracer + step recorder + FLIGHT RECORDER installed vs not: the
    serve-chunk traced program is byte-identical — all observability,
    passive and active, is host-side (invariants 7 and 14)."""
    import jax

    from aiko_services_tpu.models import llama
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer,
    )

    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=32, chunk_steps=2)

    def traced():
        return str(jax.make_jaxpr(
            lambda state, cache: llama.serve_chunk_ragged(
                server.params, state, cache, 2, server.config,
                eos_id=-1, sampled=False))(server._state, server.cache))

    clean = traced()
    trace.install(service="test")
    steplog.install()
    flight.install(out_dir=str(tmp_path), service="test")
    try:
        assert traced() == clean
    finally:
        trace.uninstall()
        steplog.uninstall()
        flight.uninstall()


# ---------------------------------------------------------------- #
# Log handler: trace correlation + observable rate limit
# ---------------------------------------------------------------- #

class _CaptureMessage:
    connected = True

    def __init__(self):
        self.published = []

    def publish(self, topic, payload):
        self.published.append((topic, payload))


def test_log_handler_attaches_trace_ids():
    from aiko_services_tpu.utils.logger import TopicLogHandler
    message = _CaptureMessage()
    handler = TopicLogHandler(message, "test/svc/log")
    logger = logging.getLogger("obs_test_trace_logger")
    logger.setLevel("INFO")
    logger.handlers = [handler]
    logger.propagate = False
    logger.info("outside any span")
    tracer = trace.install(trace.Tracer(service="svc", seed=5))
    with tracer.span("work") as span:
        logger.info("inside the span")
    assert len(message.published) == 2
    assert "trace=" not in message.published[0][1]
    assert message.published[1][1].endswith(
        f"trace={span.trace_id}/{span.span_id}")


def test_log_handler_rate_limit_counts_drops():
    from aiko_services_tpu.utils.logger import TopicLogHandler
    message = _CaptureMessage()
    handler = TopicLogHandler(message, "test/hot/log",
                              rate_limit_hz=1e-9, burst=2)
    logger = logging.getLogger("obs_test_rate_logger")
    logger.setLevel("INFO")
    logger.handlers = [handler]
    logger.propagate = False
    before = REGISTRY.counter(
        "aiko_log_records_dropped_total",
        labels={"topic": "test/hot/log"}).value
    for index in range(5):
        logger.info("storm %d", index)
    assert len(message.published) == 2          # burst admitted
    assert handler.dropped == 3
    after = REGISTRY.counter(
        "aiko_log_records_dropped_total",
        labels={"topic": "test/hot/log"}).value
    assert after - before == 3


# ---------------------------------------------------------------- #
# Actor (metrics …) scrape command
# ---------------------------------------------------------------- #

def test_actor_metrics_command(engine):
    from aiko_services_tpu.runtime import (
        Actor, Process, actor_args, compose_instance,
    )
    process = Process(namespace="test", hostname="h", pid="41",
                      engine=engine, broker="obs")
    actor = compose_instance(Actor, actor_args("scraped"),
                             process=process)
    REGISTRY.counter("aiko_obs_scrape_probe_total").inc()
    replies = []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "metrics_response":
            replies.append(params)

    process.add_message_handler(handler, "test/obs/metrics")
    process.message.publish(actor.topic_in,
                            generate("metrics", ["test/obs/metrics"]))
    engine.drain()
    assert len(replies) == 1
    name, text = replies[0][0], str(replies[0][1])
    assert name == "scraped"
    assert "aiko_obs_scrape_probe_total" in text
    assert "# TYPE" in text


def test_metrics_scrape_includes_latency_histograms(engine):
    """The replica latency histograms are REGISTRY-created, so the
    wire scrape renders them as proper Prometheus histogram series —
    ``_bucket``/``_sum``/``_count`` with ``# HELP``/``# TYPE`` — not
    just the counter/gauge mirror."""
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer,
    )
    from aiko_services_tpu.runtime import (
        Actor, Process, actor_args, compose_instance,
    )
    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=32, chunk_steps=2)
    server.latency_hists["ttft"].observe(42.0)
    server.latency_hists["total"].observe(99.0)
    process = Process(namespace="test", hostname="h", pid="42",
                      engine=engine, broker="obs")
    actor = compose_instance(Actor, actor_args("scraped_h"),
                             process=process)
    replies = []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "metrics_response":
            replies.append(params)

    process.add_message_handler(handler, "test/obs/metrics_h")
    process.message.publish(
        actor.topic_in, generate("metrics", ["test/obs/metrics_h"]))
    engine.drain()
    assert len(replies) == 1
    text = str(replies[0][1])
    assert "# TYPE aiko_latency_ttft_ms histogram" in text
    assert "# HELP aiko_latency_ttft_ms" in text
    instance = server._metrics_labels["instance"]
    assert f'aiko_latency_ttft_ms_count{{instance="{instance}"}} 1' \
        in text
    assert 'le="+Inf"' in text
    assert f'aiko_latency_total_ms_sum{{instance="{instance}"}} 99' \
        in text


# ---------------------------------------------------------------- #
# Cross-process propagation: loopback client → replica
# ---------------------------------------------------------------- #

def _connected_tree(spans):
    """One trace_id, every non-root parent resolves inside the set."""
    assert spans, "no spans"
    trace_ids = {span.trace_id for span in spans}
    assert len(trace_ids) == 1, f"disconnected traces: {trace_ids}"
    by_id = {span.span_id: span for span in spans}
    roots = [span for span in spans if span.parent_id is None]
    assert len(roots) == 1, [s.name for s in roots]
    for span in spans:
        if span.parent_id is not None:
            assert span.parent_id in by_id, \
                f"{span.name} has dangling parent {span.parent_id}"
    return roots[0]


def test_trace_rides_back_over_loopback_client(engine):
    """InferClient with a tracer installed: the response resolves with
    the FULL tree — root infer span + the replica's synthesized
    queue/prefill/decode spans — plus the per-phase latency fields."""
    from .test_infer_client import _pump, _rig

    trace.install(trace.Tracer(service="client", seed=2))
    engine, server, client = _rig(engine, "obs1")
    prompt = np.arange(1, 10, dtype=np.int32)
    future = client.submit(prompt, max_new_tokens=5)
    assert _pump(engine, lambda: future.done)
    assert future.error is None

    root = _connected_tree(future.spans)
    assert root.name == "infer"
    names = {span.name for span in future.spans}
    assert {"infer", "replica", "queue", "prefill", "decode"} <= names
    decode = next(s for s in future.spans if s.name == "decode")
    assert [m for m, _ in decode.marks] == ["first_token",
                                            "last_token"]
    replica = next(s for s in future.spans if s.name == "replica")
    assert replica.parent_id == root.span_id
    assert replica.attrs["tokens_out"] == 5
    # Satellite: per-phase breakdown on the wire + histograms observed.
    for key in ("ttft_ms", "total_ms", "queue_ms", "prefill_ms",
                "decode_ms"):
        assert float(np.asarray(future.outputs[key])) >= 0.0
    for phase in ("ttft", "total", "queue", "prefill", "decode"):
        assert server.latency_hists[phase].count == 1
    assert server.latency_hists["kv_restore"].count == 0


def test_untraced_request_carries_no_span_payload(engine):
    """No tracer, no trace field → the response has NO trace_spans and
    no span objects materialize anywhere (zero-cost when off)."""
    from .test_infer_client import _pump, _rig

    engine, server, client = _rig(engine, "obs0")
    future = client.submit(np.arange(1, 8, dtype=np.int32),
                           max_new_tokens=3)
    assert _pump(engine, lambda: future.done)
    assert future.error is None
    assert "trace_spans" not in future.outputs
    assert future.spans == []


# ---------------------------------------------------------------- #
# Cross-process propagation: router + disaggregated kv transfer
# ---------------------------------------------------------------- #

def test_trace_connects_router_replicas_and_kv_source(engine,
                                                      tmp_path):
    """The acceptance-criterion tree: one traced request through a
    ReplicaRouter into a 2-replica PAGED fleet where the decode
    replica pulls prefix blocks from the prefill replica — route,
    replica phases, kv_restore AND the source's kv_export span all
    join one connected tree, exported as valid Chrome JSON."""
    from aiko_services_tpu.orchestration.serving import ReplicaRouter
    from aiko_services_tpu.pipeline.codec import decode_swag, encode_swag
    from aiko_services_tpu.registry import Registrar
    from aiko_services_tpu.runtime import actor_args, compose_instance
    from .test_kvstore import _paged_replica, make_process

    broker = "obstrace"
    p0 = make_process(engine, 1, broker)
    Registrar(process=p0)
    engine.advance(4.0)
    pp, server_p, replica_p = _paged_replica(engine, 2, broker,
                                             "prefiller",
                                             prefill_only=True)
    pd, server_d, replica_d = _paged_replica(engine, 3, broker,
                                             "decoder")
    pr = make_process(engine, 99, broker)
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=pr, kv_transfer=True,
                              disaggregate=True)
    engine.drain()
    assert router.share["replicas"] == 2
    engine.advance(6.0)                 # roles via kv advertisement
    engine.drain()

    tracer = trace.install(trace.Tracer(service="client", seed=9))
    root = tracer.start_span("infer")
    responses = []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "infer_response":
            responses.append(decode_swag(params[1]))

    pr.add_message_handler(handler, "test/obstrace/resp")
    prompt = np.arange(1, 41, dtype=np.int32)
    pr.message.publish(
        f"{router.topic_path}/in",
        generate("infer", ["t1", "test/obstrace/resp",
                           encode_swag({"tokens": prompt,
                                        "max_new_tokens": 4,
                                        "trace": trace.inject(root)})]))
    for _ in range(4000):
        engine.advance(0.01)
        engine.drain()
        if responses:
            break
    assert responses and "error" not in responses[0], responses
    tracer.finish(root)
    assert server_d.prefix_remote_hits == 1       # transfer really ran

    spans = [root] + trace.decode_spans(responses[0]["trace_spans"])
    tree_root = _connected_tree(spans)
    assert tree_root is root
    names = {span.name for span in spans}
    assert {"infer", "route", "replica", "queue", "prefill", "decode",
            "kv_restore", "kv_export"} <= names
    services = {span.service for span in spans}
    assert {"client", "prefiller", "decoder"} <= services
    kv_export = next(s for s in spans if s.name == "kv_export")
    assert kv_export.service == "prefiller"
    assert kv_export.attrs["keys"] >= 1
    assert responses[0]["kv_restore_ms"] >= 0.0

    # Valid, Perfetto-loadable Chrome JSON with cross-process flows.
    path = trace.export_chrome(str(tmp_path / "tree.json"), spans)
    with open(path, encoding="utf-8") as handle:
        events = json.load(handle)["traceEvents"]
    assert {e["ph"] for e in events} >= {"M", "X", "s", "f"}
    process_names = {e["args"]["name"] for e in events
                     if e["name"] == "process_name"}
    assert {"client", "prefiller", "decoder"} <= process_names


# ---------------------------------------------------------------- #
# Cross-process propagation: REAL MQTT broker
# ---------------------------------------------------------------- #

def test_trace_propagates_over_real_mqtt(monkeypatch):
    """Same contract over the real socket transport: the trace field
    survives the S-expression wire and spans ride back."""
    import queue

    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer, ContinuousReplica,
    )
    from aiko_services_tpu.pipeline.codec import decode_swag, encode_swag
    from aiko_services_tpu.runtime import (
        Process, actor_args, compose_instance,
    )
    from aiko_services_tpu.runtime.event import EventEngine
    from aiko_services_tpu.transport import MqttBroker

    broker = MqttBroker(port=0)
    monkeypatch.setenv("AIKO_MQTT_HOST", broker.host)
    monkeypatch.setenv("AIKO_MQTT_PORT", str(broker.port))
    engine = EventEngine()
    thread = engine.run_in_thread()
    replica_process = client_process = None
    try:
        replica_process = Process(
            namespace="mqtrace", engine=engine, transport="mqtt")
        server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                          max_seq=64, chunk_steps=3)
        replica = compose_instance(
            ContinuousReplica, actor_args("mq_replica"),
            process=replica_process, server=server)
        client_process = Process(
            namespace="mqtrace", engine=engine, transport="mqtt")
        deadline = time.time() + 15
        while time.time() < deadline and not (
                replica_process.message.connected
                and client_process.message.connected):
            time.sleep(0.05)
        assert client_process.message.connected

        tracer = trace.install(trace.Tracer(service="mq_client",
                                            seed=4))
        root = tracer.start_span("infer")
        responses: "queue.Queue" = queue.Queue()

        def handler(_topic, payload):
            command, params = parse(payload)
            if command == "infer_response":
                responses.put(decode_swag(params[1]))

        client_process.add_message_handler(handler, "mqtrace/resp")
        prompt = np.arange(1, 9, dtype=np.int32)
        client_process.message.publish(
            replica.topic_in,
            generate("infer", ["mq1", "mqtrace/resp",
                               encode_swag({"tokens": prompt,
                                            "max_new_tokens": 3,
                                            "trace":
                                            trace.inject(root)})]))
        outputs = responses.get(timeout=120)
        tracer.finish(root)
        assert "error" not in outputs
        spans = [root] + trace.decode_spans(outputs["trace_spans"])
        tree_root = _connected_tree(spans)
        assert tree_root is root
        assert {"replica", "queue", "prefill", "decode"} <= \
            {span.name for span in spans}
    finally:
        for process in (replica_process, client_process):
            if process is not None:
                process.terminate()
        engine.terminate()
        thread.join(timeout=5)
        broker.stop()


# ---------------------------------------------------------------- #
# Loadgen: per-phase report, fleet merge, trace dumps
# ---------------------------------------------------------------- #

def test_load_report_phase_table():
    from aiko_services_tpu.tools.loadgen import LoadReport

    empty = LoadReport(sent=0, completed=0, errors=0, timeouts=0,
                       elapsed_s=0.0, latencies_ms=[])
    assert empty.phase_table() == "(no per-phase latency samples)"
    report = LoadReport(
        sent=3, completed=3, errors=0, timeouts=0, elapsed_s=1.0,
        latencies_ms=[10.0, 20.0, 30.0],
        phase_ms={"queue": [5.0, 7.0, 9.0], "decode": [1.0, 2.0, 3.0]})
    table = report.phase_table()
    lines = table.splitlines()
    assert lines[0].split() == ["phase", "p50_ms", "p95_ms", "p99_ms",
                                "n"]
    assert lines[1].startswith("queue") and lines[1].rstrip()
    assert lines[2].startswith("decode")
    assert "prefill" not in table          # no samples → no row


def test_fleet_latency_merges_server_histograms():
    from aiko_services_tpu.tools.loadgen import fleet_latency

    class _Server:
        def __init__(self, values):
            self.latency_hists = {"ttft": Histogram(name="ttft")}
            for value in values:
                self.latency_hists["ttft"].observe(value)

    a, b = _Server([10.0, 20.0]), _Server([30.0, 40.0])
    fleet = fleet_latency([a, b])
    assert fleet["ttft"]["count"] == 4
    combined = Histogram()
    for value in (10.0, 20.0, 30.0, 40.0):
        combined.observe(value)
    assert fleet["ttft"]["p95_ms"] == round(combined.quantile(0.95), 1)
    assert fleet_latency([]) == {}


def test_loadgen_shared_prefix_dumps_slowest_traces(tmp_path):
    """The end-to-end satellite: a traced shared-prefix run against
    the in-process router + 2 paged replicas produces per-phase
    fleet latency AND Perfetto-loadable span trees for the slowest
    requests — and leaves no tracer installed after."""
    from aiko_services_tpu.tools.loadgen import run_shared_prefix

    out = tmp_path / "traces"
    report = run_shared_prefix(n_requests=4, rate_hz=100.0,
                               n_conversations=2, turns=2,
                               trace_out=str(out), trace_top=2)
    assert report.completed == 4 and report.errors == 0
    assert report.fleet_latency_ms
    assert report.fleet_latency_ms["ttft"]["count"] == 4
    assert "queue" in report.phase_table()
    dumps = sorted(out.glob("trace_*.json"))
    assert len(dumps) == 2
    for dump in dumps:
        with open(dump, encoding="utf-8") as handle:
            events = json.load(handle)["traceEvents"]
        names = {event["name"] for event in events}
        assert {"infer", "replica", "decode"} <= names
    assert trace.TRACER is None            # run() cleans up after itself


# ---------------------------------------------------------------- #
# Dashboard panes
# ---------------------------------------------------------------- #

def test_dashboard_replica_obs_panes():
    from aiko_services_tpu.tools.dashboard_plugins import (
        model_replica_plugin,
    )

    class Fields:
        name = "replica_0"
        protocol = "model_replica"
        topic_path = "test/h/1/1"

    histogram = Histogram(name="ttft")
    for value in (12.0, 20.0, 31.0):
        histogram.observe(value)
    text = "\n".join(model_replica_plugin(Fields, {
        "lifecycle": "ready", "requests_served": 9,
        "hist": {"ttft": histogram.encode()},
        "slow_requests": "lg1_5:2923.9:decode=12.0,prefill=13.0,"
                         "queue=2898.9",
    }))
    assert "phase latency" in text and "ttft" in text
    assert "n=3" in text
    assert "slowest requests" in text
    assert "lg1_5" in text and "2923.9" in text
    assert "queue=2899" in text
    # Bar is proportional: queue dominates this request.
    bar = text[text.index("["):text.index("]")]
    assert bar.count("q") > 15


def test_dashboard_router_fleet_pane():
    from aiko_services_tpu.tools.dashboard_plugins import (
        replica_router_plugin,
    )

    class Fields:
        name = "router"
        protocol = "replica_router"
        topic_path = "test/h/9/1"

    text = "\n".join(replica_router_plugin(Fields, {
        "lifecycle": "ready", "replicas": 2, "requests_routed": 7,
        "fleet_ttft_p50_ms": 21.1, "fleet_ttft_p95_ms": 44.7,
        "fleet_ttft_p99_ms": 44.7,
    }))
    assert "fleet latency" in text
    assert "21.1" in text and "44.7" in text
    bare = "\n".join(replica_router_plugin(Fields, {"replicas": 0}))
    assert "fleet latency" not in bare
