"""Serving load generator: drive replicas over the wire, report tails.

The reference's only load harness is multitude (pipelines at a fixed
frame rate, ``examples/pipeline/multitude``); the serving stack
(ModelReplica / ContinuousReplica / ReplicaRouter) needs its own:
open-loop request injection at a target rate with latency tails, the
standard way to expose queueing behavior that a closed loop hides.

    generator = LoadGenerator(process, target_topic="ns/h/1/0/in",
                              payload_fn=make_payload, rate_hz=50)
    report = generator.run(n_requests=500)
    report.p50_ms, report.p99_ms, report.throughput_rps, report.errors

Open-loop: requests are posted on schedule regardless of completions
(late responses still count; missing ones surface as ``timeouts``).
Works over any transport the process speaks (loopback in tests, the
built-in MQTT broker cross-process).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import statistics
import time
import uuid
from typing import Callable, Dict, List, Optional, Tuple

from ..obs import trace
from ..pipeline.codec import encode_swag
from ..utils.sexpr import generate, parse

__all__ = ["LoadGenerator", "LoadReport", "service_scale_sweep",
           "chaos_schedule", "run_chaos", "shared_prefix_payloads",
           "run_shared_prefix", "fleet_latency", "diurnal_trace",
           "elastic_chaos_schedule", "run_elastic",
           "run_elastic_chaos", "run_longtail", "run_restart",
           "run_restart_ab", "run_compile_cache_ab", "main"]

#: Per-phase latency keys the replicas stamp on responses, in report
#: order (``kv_restore`` is the cross-replica transfer phase).
PHASES = ("queue", "prefill", "decode", "kv_restore")


@dataclasses.dataclass
class LoadReport:
    sent: int
    completed: int
    errors: int
    timeouts: int
    elapsed_s: float
    latencies_ms: List[float]
    tokens_total: int = 0
    #: Server-reported time-to-first-token per completed request (the
    #: replica stamps ``ttft_ms`` on the wire response) — what SLOs
    #: watch; wire p50/p99 above includes full generation time.
    ttfts_ms: List[float] = dataclasses.field(default_factory=list)
    #: Optional server-side counters snapshot (``server.stats()`` or
    #: :func:`~..orchestration.serving.serving_telemetry` payload)
    #: attached by the harness after the run — ties the wire-level
    #: tails to the decode-attention path that produced them.
    server_stats: Optional[Dict] = None
    #: error string -> count.  ``errors`` alone can't distinguish a
    #: healthy shed (``overloaded``/``deadline_exceeded`` — the
    #: backpressure design working) from real failures.
    error_kinds: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: Fleet prefix-cache hit fraction over the run
    #: (``Σ prefix_hits / Σ (prefix_hits + prefix_misses)`` across
    #: replicas; None when the fleet has no prefix caches) — attached
    #: by the harness from server stats, like ``server_stats``.
    prefix_hit_rate: Optional[float] = None
    #: Fraction of prefix HITS that adopted blocks restored from the
    #: host tier (``Σ prefix_hits_host / Σ prefix_hits``; None when
    #: the fleet has no host tier or took no hits) — the tiered-KV
    #: number the longtail workload reports: hits the HBM pool alone
    #: would have lost.
    prefix_hit_rate_host: Optional[float] = None
    #: Total cross-replica KV bytes moved during the run (Σ replica
    #: ``kv_transfer_bytes`` deltas).
    kv_transfer_bytes: int = 0
    #: phase -> per-request latencies (ms) as stamped by the replicas
    #: (``queue_ms``/``prefill_ms``/``decode_ms``/``kv_restore_ms``)
    #: — the per-phase breakdown :meth:`phase_table` renders.
    phase_ms: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict)
    #: Fleet-level quantiles from EXACT merges of the replicas'
    #: fixed-bucket histograms (phase -> {p50_ms, p95_ms, p99_ms,
    #: count}); attached by the harness via :func:`fleet_latency`.
    fleet_latency_ms: Optional[Dict[str, Dict[str, float]]] = None
    #: TTFT SLO (ms) goodput is judged against; None = goodput is raw
    #: throughput.  Attached by the harness (``run_elastic``).
    slo_ttft_ms: Optional[float] = None
    #: ∫ replica-count dt over the run — the denominator of
    #: :attr:`goodput_per_replica` (autoscaler share delta, or
    #: ``N * elapsed_s`` for a static fleet).
    replica_seconds: float = 0.0
    #: Final ``infer_response`` arriving for an already-completed
    #: request id — the double-delivery a drain/re-dispatch chaos run
    #: asserts is ZERO.
    duplicate_finals: int = 0
    #: replica topic/name -> TP degree (chips per replica), attached
    #: by the harness from fleet telemetry — per-chip efficiency needs
    #: the chip count, not the replica count, as denominator.
    replica_tp: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: request id -> final token list as delivered on the wire,
    #: attached by the harness — lets A/B runs over the same seeded
    #: payload sequence assert BIT-EXACT outputs (e.g. tier-on chaos
    #: vs tier-off chaos must produce identical greedy tokens).
    final_tokens: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict)
    #: request id -> per-spec-round accepted-token counts as stamped
    #: by draft-enabled replicas; empty when the fleet runs no draft.
    #: An A/B run reports the acceptance distribution per request —
    #: the number that explains WHERE speculative decoding paid off
    #: (long accepted runs) and where it degraded to plain decode.
    spec_accept_hist: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict)
    #: Fleet speculative counters (Σ over replicas of the server
    #: ``spec_*`` stats); None when no replica runs a draft.
    spec_stats: Optional[Dict] = None
    #: Warmup-vs-steady split (PR 14): the first-step compile tax
    #: reported SEPARATELY from steady throughput.  ``warmup_s`` is
    #: the harness-measured window before the compile ledger's fence
    #: dropped; ``warmup_compiles`` is what XLA compiled inside it;
    #: ``compiles_steady_state`` is what compiled AFTER it — the chaos
    #: gate asserts this stays ZERO on the paged path (any steady
    #: compile is a pow2 bucket-discipline regression).
    warmup_s: float = 0.0
    warmup_compiles: int = 0
    compiles_steady_state: int = 0
    #: Tokens/s measured over the steady window only (completed-token
    #: throughput with the warmup window excluded from the clock);
    #: 0.0 when the harness ran no ledger.
    steady_tokens_per_sec: float = 0.0
    #: Persistent compilation-cache counters over the run
    #: (hits/misses/saved_ms; None when no ledger was installed).
    compile_cache: Optional[Dict] = None
    #: tier -> high-water-mark bytes over the run, from the memory
    #: accountant's flow-integrated occupancy (PR 15) — a TRUE peak,
    #: not an end-of-run sample.  Empty when no ``pool_audit.AUDITOR``
    #: was installed for the run.
    peak_kv_bytes: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    #: End-of-run pool census from the (first paged) server —
    #: ``PagedContinuousServer.pool_census()``; None on non-paged
    #: fleets.  :meth:`pool_census` renders it.
    census: Optional[Dict] = None
    #: Multi-tenant adapters: client-observed cold starts — an
    #: ``unknown_adapter`` rejection is a request that landed on a
    #: replica without the tenant's factors and would force a factor
    #: re-upload before retry.  The adapter-aware arm of the
    #: multitenant A/B asserts this is ZERO whenever the adapter is
    #: warm anywhere in the fleet.
    adapter_cold_starts: int = 0
    #: Router's warm/cold split over adapter-tagged routes (mirrors
    #: ``router.counters``; both 0 under the adapter-blind baseline,
    #: which never inspects the adapter field).
    adapter_warm_routes: int = 0
    adapter_cold_routes: int = 0

    def pool_census(self) -> str:
        """Readable end-of-run memory summary: per-tier blocks/bytes
        (with the run's peak when the accountant tracked one) plus the
        pool state histogram."""
        if not self.census:
            return "(no pool census attached)"
        lines = [f"{'tier':<6}{'blocks':>9}{'bytes':>13}{'peak':>13}"]
        for tier in ("hbm", "host", "disk"):
            info = self.census.get("tiers", {}).get(tier, {})
            peak = self.peak_kv_bytes.get(tier)
            lines.append(
                f"{tier:<6}{int(info.get('blocks', 0)):>9}"
                f"{int(info.get('bytes', 0)):>13}"
                f"{peak if peak is not None else '-':>13}")
        states = self.census.get("states", {})
        if states:
            lines.append("states: " + ", ".join(
                f"{state}={count}" for state, count
                in sorted(states.items()) if count))
        return "\n".join(lines)

    @property
    def lost(self) -> int:
        """Requests neither completed nor error-terminal (hung or
        dropped) — the number a chaos run asserts is ZERO."""
        return self.sent - self.completed - self.errors

    @property
    def throughput_rps(self) -> float:
        return self.completed / self.elapsed_s if self.elapsed_s else 0.0

    @property
    def throughput_tps(self) -> float:
        """Generated tokens per second — the number the device-resident
        serving work moves; req/s alone hides per-request length."""
        return (self.tokens_total / self.elapsed_s
                if self.elapsed_s else 0.0)

    @property
    def good_completions(self) -> int:
        """Completions WITHIN the TTFT SLO (DistServe's goodput
        numerator).  Completions without a ``ttft_ms`` stamp count as
        good — only a measured breach disqualifies."""
        if self.slo_ttft_ms is None:
            return self.completed
        within = sum(1 for ttft in self.ttfts_ms
                     if ttft <= self.slo_ttft_ms)
        unstamped = self.completed - len(self.ttfts_ms)
        return within + max(0, unstamped)

    @property
    def goodput_rps(self) -> float:
        """SLO-attaining completions per second."""
        return (self.good_completions / self.elapsed_s
                if self.elapsed_s else 0.0)

    @property
    def avg_replicas(self) -> float:
        """Time-averaged fleet size over the run."""
        return (self.replica_seconds / self.elapsed_s
                if self.elapsed_s else 0.0)

    @property
    def goodput_per_replica(self) -> float:
        """Goodput divided by average fleet size — the efficiency
        number an autoscaled fleet must beat a static-peak fleet on
        (serving the valleys with fewer replicas is the whole
        point)."""
        average = self.avg_replicas
        return self.goodput_rps / average if average else 0.0

    @staticmethod
    def _quantile(values: List[float], q: float) -> float:
        if not values:
            return 0.0
        ordered = sorted(values)
        index = min(len(ordered) - 1, int(q * len(ordered)))
        return ordered[index]

    @property
    def p50_ms(self) -> float:
        return (statistics.median(self.latencies_ms)
                if self.latencies_ms else 0.0)

    @property
    def p99_ms(self) -> float:
        return self._quantile(self.latencies_ms, 0.99)

    @property
    def ttft_p50_ms(self) -> float:
        return (statistics.median(self.ttfts_ms)
                if self.ttfts_ms else 0.0)

    @property
    def ttft_p95_ms(self) -> float:
        return self._quantile(self.ttfts_ms, 0.95)

    def phase_table(self) -> str:
        """Per-phase latency breakdown (queue/prefill/decode/
        kv_restore) — WHERE a slow run spent its time, one line per
        phase with nearest-rank quantiles over this run's samples."""
        if not self.phase_ms:
            return "(no per-phase latency samples)"
        lines = [f"{'phase':<12}{'p50_ms':>9}{'p95_ms':>9}"
                 f"{'p99_ms':>9}{'n':>7}"]
        for phase in PHASES:
            values = self.phase_ms.get(phase)
            if not values:
                continue
            lines.append(
                f"{phase:<12}"
                f"{self._quantile(values, 0.5):>9.1f}"
                f"{self._quantile(values, 0.95):>9.1f}"
                f"{self._quantile(values, 0.99):>9.1f}"
                f"{len(values):>7}")
        return "\n".join(lines)

    def __repr__(self):
        attn = ""
        if self.server_stats and "decode_attention_path" in \
                self.server_stats:
            attn = (f", attn={self.server_stats['decode_attention_path']}"
                    f"/{self.server_stats.get('blocks_read_per_step', 0)}"
                    f" blk/step")
        ttft = (f", ttft_p50={self.ttft_p50_ms:.1f}/"
                f"p95={self.ttft_p95_ms:.1f} ms"
                if self.ttfts_ms else "")
        kinds = (", kinds=" + "/".join(
            f"{k}:{n}" for k, n in sorted(self.error_kinds.items()))
            if self.error_kinds else "")
        prefix = (f", prefix_hit={self.prefix_hit_rate:.0%}"
                  if self.prefix_hit_rate is not None else "")
        if self.prefix_hit_rate_host is not None:
            prefix += f" ({self.prefix_hit_rate_host:.0%} via host tier)"
        kv = (f", kv_xfer={self.kv_transfer_bytes}B"
              if self.kv_transfer_bytes else "")
        adapters = ""
        if (self.adapter_cold_starts or self.adapter_warm_routes
                or self.adapter_cold_routes):
            adapters = (f", adapters={self.adapter_warm_routes} warm"
                        f"/{self.adapter_cold_routes} cold routes, "
                        f"{self.adapter_cold_starts} cold starts")
        tp = ""
        if any(degree > 1 for degree in self.replica_tp.values()):
            tp = ", tp=" + "/".join(
                f"{name}:{degree}" for name, degree
                in sorted(self.replica_tp.items()))
        goodput = ""
        if self.slo_ttft_ms is not None:
            goodput = (f", goodput={self.goodput_rps:.1f} req/s"
                       f"@{self.slo_ttft_ms:g}ms")
            if self.replica_seconds:
                goodput += (f", {self.goodput_per_replica:.2f} "
                            f"req/s/replica (avg "
                            f"{self.avg_replicas:.2f})")
        compile_note = ""
        if self.warmup_compiles or self.compiles_steady_state:
            compile_note = (
                f", compiles={self.warmup_compiles} warmup"
                f"/{self.compiles_steady_state} steady"
                f" (warmup {self.warmup_s:.1f}s")
            if self.steady_tokens_per_sec:
                compile_note += (f", steady "
                                 f"{self.steady_tokens_per_sec:.1f} "
                                 f"tok/s")
            compile_note += ")"
        return (f"LoadReport(sent={self.sent}, done={self.completed}, "
                f"errors={self.errors}{kinds}, "
                f"timeouts={self.timeouts}, "
                f"{self.throughput_rps:.1f} req/s, "
                f"{self.throughput_tps:.1f} tok/s, "
                f"p50={self.p50_ms:.1f} ms, p99={self.p99_ms:.1f} ms"
                f"{ttft}{goodput}{prefix}{kv}{adapters}{tp}{attn}"
                f"{compile_note})")


class LoadGenerator:
    """Open-loop ``(infer …)`` load against a replica or router topic."""

    def __init__(self, process, target_topic: str,
                 payload_fn: Callable[[int], Dict], rate_hz: float = 50.0,
                 response_topic: Optional[str] = None,
                 clock=None, sleep=None):
        self.process = process
        self.target_topic = target_topic
        self.payload_fn = payload_fn
        self.rate_hz = rate_hz
        self.response_topic = response_topic or (
            f"loadgen/{uuid.uuid4().hex[:8]}/response")
        self._clock = clock or time.perf_counter
        self._sleep = sleep or time.sleep
        self._sent_at: Dict[str, float] = {}
        self._latencies: List[float] = []
        self._ttfts: List[float] = []
        self._phases: Dict[str, List[float]] = {}
        self._errors = 0
        self._error_kinds: Dict[str, int] = {}
        self._tokens = 0
        self._run_index = 0
        #: request_id -> concatenated streaming increments as
        #: delivered (``infer_partial``); public so chaos tests can
        #: assert partials == final tokens with no double-delivery.
        self.partial_tokens: Dict[str, List[int]] = {}
        #: request_id -> the final response's token list.
        self.final_tokens: Dict[str, List[int]] = {}
        #: request_id -> per-spec-round accepted-token counts as
        #: stamped by draft-enabled replicas (absent otherwise).
        self.spec_accept_hist: Dict[str, List[int]] = {}
        self._completed_ids: set = set()
        self._duplicate_finals = 0
        # Tracing (rides the global trace.TRACER switchboard): root
        # span per request, full ride-back tree kept per request id
        # for dump_traces().
        self._root_spans: Dict[str, object] = {}
        self._traces: List[Tuple[float, str, List]] = []
        process.add_message_handler(self._on_response,
                                    self.response_topic)

    def close(self):
        """Deregister the response handler (and its subscription) —
        required in long-lived processes doing rate sweeps, or dead
        generators keep receiving."""
        self.process.remove_message_handler(self._on_response,
                                            self.response_topic)

    def _on_response(self, _topic: str, payload: str):
        command, params = parse(payload)
        if command == "infer_partial" and len(params) > 1:
            self._on_partial(str(params[0]), params[1])
            return
        if command != "infer_response" or not params:
            return
        request_id = str(params[0])
        # Look up (don't pop yet): the drain loop in run_trace exits
        # the moment _sent_at goes empty, so the request must stay in
        # it until its latency/error is recorded — popping first lets
        # the report snapshot race ahead of the append and under-count
        # completions.  The pop happens at the end of this handler.
        started = self._sent_at.get(request_id)
        if started is None:
            if request_id in self._completed_ids:
                # A second FINAL for a finished request: the
                # double-delivery chaos runs must never see.
                self._duplicate_finals += 1
            return
        self._completed_ids.add(request_id)
        outputs = params[1] if len(params) > 1 else {}
        self._record_final_tokens(request_id, outputs)
        if isinstance(outputs, dict) and "spec_accepted_rounds" in outputs:
            try:
                from ..pipeline.codec import decode_value
                import numpy as np
                self.spec_accept_hist[request_id] = [
                    int(count) for count in np.asarray(decode_value(
                        outputs["spec_accepted_rounds"])).reshape(-1)]
            except Exception:  # noqa: BLE001 - telemetry only
                pass
        self._collect_trace(request_id, started, outputs)
        if isinstance(outputs, dict) and "error" in outputs:
            self._errors += 1
            # Values on the wire are codec-tagged ("s:overloaded") —
            # decode, so error_kinds keys match the error strings the
            # replicas publish.
            try:
                from ..pipeline.codec import decode_value
                kind = str(decode_value(outputs["error"]))
            except Exception:  # noqa: BLE001 - count it regardless
                kind = str(outputs["error"])
            self._error_kinds[kind] = \
                self._error_kinds.get(kind, 0) + 1
        else:
            self._latencies.append((self._clock() - started) * 1e3)
            if isinstance(outputs, dict) and "ttft_ms" in outputs:
                try:
                    from ..pipeline.codec import decode_value
                    self._ttfts.append(
                        float(decode_value(outputs["ttft_ms"])))
                except Exception:  # noqa: BLE001 - telemetry only
                    pass
            if isinstance(outputs, dict) and "tokens_out" in outputs:
                try:
                    from ..pipeline.codec import decode_value
                    import numpy as np
                    self._tokens += int(np.asarray(
                        decode_value(outputs["tokens_out"])).size)
                except Exception:  # noqa: BLE001 - telemetry only
                    pass
            if isinstance(outputs, dict):
                for phase in PHASES:
                    if f"{phase}_ms" not in outputs:
                        continue
                    try:
                        from ..pipeline.codec import decode_value
                        self._phases.setdefault(phase, []).append(
                            float(decode_value(outputs[f"{phase}_ms"])))
                    except Exception:  # noqa: BLE001 - telemetry only
                        pass
        # Everything recorded — only now mark the request finished so
        # run_trace cannot observe "done" before the stats landed.
        self._sent_at.pop(request_id, None)

    def _on_partial(self, request_id: str, outputs) -> None:
        """Accumulate a streaming increment (chaos tests assert the
        concatenation equals the final token list — a drained replica
        finishing in place must never re-stream)."""
        if not isinstance(outputs, dict) or "tokens_out" not in outputs:
            return
        try:
            from ..pipeline.codec import decode_value
            import numpy as np
            increment = [int(t) for t in
                         np.asarray(decode_value(outputs["tokens_out"]))
                         .reshape(-1)]
        except Exception:  # noqa: BLE001 - telemetry only
            return
        self.partial_tokens.setdefault(request_id, []).extend(increment)

    def _record_final_tokens(self, request_id: str, outputs) -> None:
        if not isinstance(outputs, dict) or "tokens_out" not in outputs:
            return
        try:
            from ..pipeline.codec import decode_value
            import numpy as np
            self.final_tokens[request_id] = [
                int(t) for t in
                np.asarray(decode_value(outputs["tokens_out"]))
                .reshape(-1)]
        except Exception:  # noqa: BLE001 - telemetry only
            pass

    def _collect_trace(self, request_id: str, started: float,
                       outputs) -> None:
        """Close this request's root span and keep the full ride-back
        tree (root + router + replica + kv source spans), keyed by
        wire latency so :meth:`dump_traces` can rank by slowest."""
        span = self._root_spans.pop(request_id, None)
        if span is None:
            return
        if trace.TRACER is not None:
            trace.TRACER.finish(span)
        elif span.end is None:
            span.end = span.start
        spans = [span]
        if isinstance(outputs, dict) and "trace_spans" in outputs:
            try:
                from ..pipeline.codec import decode_value
                spans.extend(trace.decode_spans(
                    str(decode_value(outputs["trace_spans"]))))
            except Exception:  # noqa: BLE001 - telemetry only
                pass
        self._traces.append(((self._clock() - started) * 1e3,
                             request_id, spans))

    def dump_traces(self, directory: str, top_k: int = 5) -> List[str]:
        """Export the ``top_k`` SLOWEST traced requests' span trees as
        Chrome trace-event JSON files (Perfetto-loadable), one file
        per request, named ``trace_<rank>_<request_id>.json``.
        Returns the written paths (empty when tracing was off)."""
        if not self._traces:
            return []
        os.makedirs(directory, exist_ok=True)
        ranked = sorted(self._traces,
                        key=lambda entry: -entry[0])[:top_k]
        paths = []
        for rank, (_total_ms, request_id, spans) in enumerate(ranked):
            path = os.path.join(
                directory, f"trace_{rank:02d}_{request_id}.json")
            trace.export_chrome(path, spans)
            paths.append(path)
        return paths

    def run(self, n_requests: int, drain_timeout_s: float = 30.0,
            pump: Optional[Callable[[], None]] = None) -> LoadReport:
        """Send ``n_requests`` at ``rate_hz``, then wait for stragglers.
        ``pump`` (optional) is called between waits — pass
        ``engine.drain`` when driving a VirtualClock engine in tests."""
        interval = 1.0 / self.rate_hz if self.rate_hz > 0 else 0.0
        return self.run_trace(
            [index * interval for index in range(n_requests)],
            drain_timeout_s=drain_timeout_s, pump=pump)

    def run_trace(self, send_offsets_s: List[float],
                  drain_timeout_s: float = 30.0,
                  pump: Optional[Callable[[], None]] = None
                  ) -> LoadReport:
        """Open-loop injection on an explicit schedule: request ``i``
        is sent ``send_offsets_s[i]`` seconds after the run starts
        (:func:`diurnal_trace` generates such schedules).  ``run()``
        is the constant-rate special case."""
        # Per-run state: runs are re-runnable (rate sweeps), and ids
        # are unique per run so a run-1 straggler cannot satisfy a
        # run-2 request.
        self._sent_at.clear()
        self._latencies = []
        self._ttfts = []
        self._phases = {}
        self._errors = 0
        self._error_kinds = {}
        self._tokens = 0
        self._root_spans.clear()
        self._traces = []
        self.partial_tokens = {}
        self.final_tokens = {}
        self.spec_accept_hist = {}
        self._completed_ids = set()
        self._duplicate_finals = 0
        self._run_index += 1
        run_tag = self._run_index
        started = self._clock()
        for index, offset in enumerate(send_offsets_s):
            delay = started + offset - self._clock()
            if delay > 0:
                self._sleep(delay)
            request_id = f"lg{run_tag}_{index}"
            swag = self.payload_fn(index)
            if trace.TRACER is not None:
                span = trace.TRACER.start_span(
                    "infer", attrs={"request_id": request_id,
                                    "target": self.target_topic})
                swag = dict(swag, trace=trace.inject(span))
                self._root_spans[request_id] = span
            self._sent_at[request_id] = self._clock()
            self.process.message.publish(
                self.target_topic,
                generate("infer",
                         [request_id, self.response_topic,
                          encode_swag(swag)]))
            if pump is not None:
                pump()
        deadline = self._clock() + drain_timeout_s
        while self._sent_at and self._clock() < deadline:
            if pump is not None:
                pump()
            self._sleep(0.01)
        elapsed = self._clock() - started
        return LoadReport(sent=len(send_offsets_s),
                          completed=len(self._latencies),
                          errors=self._errors,
                          timeouts=len(self._sent_at),
                          elapsed_s=elapsed,
                          latencies_ms=list(self._latencies),
                          tokens_total=self._tokens,
                          ttfts_ms=list(self._ttfts),
                          error_kinds=dict(self._error_kinds),
                          phase_ms={phase: list(values) for phase,
                                    values in self._phases.items()},
                          duplicate_finals=self._duplicate_finals)


def service_scale_sweep(services: int, broker: str = "scale-sweep",
                        namespace: str = "scale",
                        create_timeout_s: float = 120.0,
                        rpc_timeout_s: float = 120.0) -> dict:
    """Demonstrate the reference's aspirational service density
    (1,000-10,000 services/process, reference main/process.py:45-48,
    an untested TODO there): N actors in ONE process, all discovered
    by a registrar, one RPC each through the full parse→mailbox→
    dispatch path.  Raises AssertionError if discovery or any RPC is
    incomplete within its own (separate) timeout budget.

    Used by ``tests/test_scale.py``."""
    import time as time_module

    from ..registry import Registrar
    from ..runtime import Process, actor_args, compose_instance
    from ..runtime.actor import Actor
    from ..runtime.event import EventEngine

    class Echo(Actor):
        def echo(self, value):
            self.share["last"] = value

    engine = EventEngine()
    thread = engine.run_in_thread()
    process = Process(namespace=namespace, hostname="h", pid="1",
                      engine=engine, broker=broker)
    registrar = Registrar(process=process)
    deadline = time_module.time() + 15
    while registrar.state != "primary" \
            and time_module.time() < deadline:
        time_module.sleep(0.02)
    if registrar.state != "primary":
        # Fail HERE, not as a misleading discovery-count assertion
        # 2 minutes later: nothing registers without a primary.
        process.terminate()
        engine.terminate()
        thread.join(timeout=5)
        raise TimeoutError("scale sweep: registrar never went primary")
    try:
        t0 = time_module.perf_counter()
        actors = [compose_instance(Echo, actor_args(f"svc{i}"),
                                   process=process)
                  for i in range(services)]
        create_dt = time_module.perf_counter() - t0
        deadline = time_module.time() + create_timeout_s
        while len(registrar.services) < services + 1 \
                and time_module.time() < deadline:
            time_module.sleep(0.05)
        discovered = len(registrar.services) - 1
        assert discovered == services, \
            f"registrar discovered {discovered}/{services}"

        # RPC sweep gets its OWN budget — slow discovery must not
        # starve it into a flaky delivery failure.
        t0 = time_module.perf_counter()
        for i, actor in enumerate(actors):
            process.message.publish(actor.topic_in, f"(echo {i})")
        deadline = time_module.time() + rpc_timeout_s
        while any("last" not in a.share for a in actors) \
                and time_module.time() < deadline:
            time_module.sleep(0.05)
        rpc_dt = time_module.perf_counter() - t0
        assert all(a.share.get("last") == str(i)
                   for i, a in enumerate(actors)), "RPCs missing"
        return {
            "services": services,
            "create_per_sec": round(services / create_dt),
            "registrar_discovered": discovered,
            "rpc_sweep_per_sec": round(services / rpc_dt),
            "exact_indexed_topics": len(process._exact_handlers),
            "wildcard_patterns": len(process._wildcard_handlers),
        }
    finally:
        process.terminate()
        engine.terminate()
        thread.join(timeout=5)


def shared_prefix_payloads(n_conversations: int = 4, turns: int = 4,
                           system_len: int = 48, turn_len: int = 8,
                           max_new_tokens: int = 6, vocab: int = 1024,
                           seed: int = 0, stream: bool = True
                           ) -> Callable[[int], Dict]:
    """Multi-turn chat-style workload: ``n_conversations`` interleaved
    conversations of ``turns`` turns, ALL sharing one
    ``system_len``-token system prompt, each turn re-sending the
    conversation so far plus ``turn_len`` fresh tokens — the workload
    shape where a cluster-wide prefix cache pays (every request's
    prompt head is either the shared system prompt or a prior turn's
    whole prompt).

    ``payload_fn(index)``: conversation ``index % n_conversations``,
    turn ``(index // n_conversations) % turns`` — so concurrent
    requests hit DIFFERENT conversations (interleaving, like real
    traffic) while turn order within a conversation is preserved by
    send order.  Deterministic from ``seed``."""
    import numpy as np

    rng = np.random.RandomState(seed)
    system = rng.randint(1, vocab, size=system_len).astype(np.int32)
    turn_tokens = [[rng.randint(1, vocab,
                                size=turn_len).astype(np.int32)
                    for _ in range(turns)]
                   for _ in range(n_conversations)]

    def payload_fn(index: int) -> Dict:
        conversation = index % n_conversations
        turn = (index // n_conversations) % turns
        prompt = np.concatenate(
            [system] + turn_tokens[conversation][:turn + 1])
        payload = {"tokens": prompt, "max_new_tokens": max_new_tokens}
        if stream:
            payload["stream"] = 1
        return payload

    return payload_fn


def fleet_latency(servers) -> Dict[str, Dict[str, float]]:
    """Fleet-level latency quantiles by EXACTLY merging the replicas'
    fixed-bucket phase histograms (element-wise bucket adds — the
    same numbers a router derives from the ``hist.<phase>`` EC shares
    it watches).  phase -> {p50_ms, p95_ms, p99_ms, count}."""
    from ..obs.metrics import Histogram
    out: Dict[str, Dict[str, float]] = {}
    by_phase: Dict[str, List[Histogram]] = {}
    for server in servers:
        for phase, histogram in getattr(server, "latency_hists",
                                        {}).items():
            by_phase.setdefault(phase, []).append(histogram)
    for phase, histograms in sorted(by_phase.items()):
        merged = Histogram.merged(histograms)
        if merged.count:
            out[phase] = {"p50_ms": round(merged.quantile(0.5), 1),
                          "p95_ms": round(merged.quantile(0.95), 1),
                          "p99_ms": round(merged.quantile(0.99), 1),
                          "count": merged.count}
    return out


def _fleet_kv_stats(servers) -> Dict:
    """Aggregate the kvstore + tier counters a shared-prefix or
    longtail run reports."""
    totals = dict(prefix_hits=0, prefix_misses=0, kv_transfer_bytes=0,
                  prefix_remote_hits=0, kv_transfer_failures=0,
                  kv_demotions=0, kv_restores=0, kv_host_blocks=0,
                  kv_host_bytes=0, restore_queue_depth=0,
                  prefix_hits_host=0, kv_spills=0, kv_disk_blocks=0,
                  kv_disk_bytes=0, kv_disk_restores=0,
                  kv_checksum_failures=0, kv_adopted_chains=0,
                  kv_prefetch_promotions=0)
    for server in servers:
        stats = server.stats()
        for key in totals:
            totals[key] += int(stats.get(key, 0))
    return totals


def _attach_kv_rates(report: LoadReport, totals: Dict) -> None:
    """Derive the report's hit-rate fields from fleet totals."""
    lookups = totals["prefix_hits"] + totals["prefix_misses"]
    if lookups:
        report.prefix_hit_rate = totals["prefix_hits"] / lookups
    if totals["prefix_hits"] and (totals["kv_demotions"]
                                  or totals["prefix_hits_host"]):
        report.prefix_hit_rate_host = \
            totals["prefix_hits_host"] / totals["prefix_hits"]
    report.kv_transfer_bytes = totals["kv_transfer_bytes"]


def _attach_pool_census(report: LoadReport, servers) -> None:
    """Attach the end-of-run pool census (first paged server) and,
    when a memory accountant is installed, the flow-integrated per-tier
    peak bytes (PR 15)."""
    for server in servers:
        if hasattr(server, "pool_census"):
            try:
                report.census = server.pool_census()
            except Exception:  # noqa: BLE001 - census is best-effort
                pass
            break
    from ..obs import pool_audit
    if pool_audit.AUDITOR is not None:
        report.peak_kv_bytes = {
            tier: entry["bytes"] for tier, entry
            in pool_audit.AUDITOR.accountant.peak.items()}


def _fleet_spec_stats(servers) -> Optional[Dict]:
    """Σ the per-replica speculative counters (None when no replica
    runs a draft).  Rates are recomputed from the summed raw counts —
    averaging per-replica rates would weight idle replicas equally."""
    totals: Dict[str, float] = {}
    modes: set = set()
    k_effs: list = []
    for server in servers:
        stats = server.stats()
        if "spec_rounds" not in stats:
            continue
        for key in ("spec_rounds", "spec_proposed", "spec_accepted",
                    "spec_rollback_blocks", "spec_jump_forward_tokens",
                    "spec_ngram_hits"):
            totals[key] = totals.get(key, 0) + int(stats.get(key, 0))
        modes.add(str(stats.get("spec_draft_mode", "model")))
        k_eff = stats.get("spec_k_effective", "-")
        if k_eff not in (None, "-"):
            k_effs.append(str(k_eff))
    if not totals:
        return None
    totals["spec_draft_mode"] = "|".join(sorted(modes))
    totals["spec_k_effective"] = ";".join(k_effs) if k_effs else "-"
    proposed = totals["spec_proposed"]
    rounds = totals["spec_rounds"]
    totals["spec_acceptance_rate"] = round(
        totals["spec_accepted"] / proposed, 4) if proposed else 0.0
    totals["spec_tokens_per_target_pass"] = round(
        (totals["spec_accepted"] + rounds) / rounds, 4) \
        if rounds else 0.0
    return totals


def _enable_paired_draft(server, spec_k: int) -> None:
    """Alias the target weights in as the draft (the 'paired toy'):
    on the tiny CPU configs a real small draft is meaningless, and an
    identical draft gives the HIGH-acceptance regime — multi-token
    commits every round — while greedy outputs stay bitwise equal to
    the plain server by the verify construction (what the A/B run
    asserts).  Counters and histograms then show the mechanism at
    full stretch instead of degenerating to acceptance ≈ 0."""
    server._draft["params"] = server.params
    server._draft["config"] = server.config


def run_shared_prefix(n_requests: int = 24, rate_hz: float = 50.0,
                      n_conversations: int = 3, turns: int = 4,
                      system_len: int = 48,
                      prefix_routing: bool = True,
                      kv_transfer: bool = True,
                      drain_timeout_s: float = 90.0,
                      seed: int = 0,
                      trace_out: Optional[str] = None,
                      trace_top: int = 5,
                      spec_k: int = 0) -> LoadReport:
    """In-process 2-replica PAGED serving rig (prefix caches on)
    driven by :func:`shared_prefix_payloads` through a ReplicaRouter.
    ``prefix_routing=False`` degrades the router to pure
    least-loaded P2C (``prefix_alpha=0``), the CLI's
    ``--no-prefix-routing`` baseline.  The report carries
    ``prefix_hit_rate``,
    ``kv_transfer_bytes`` and histogram-merged ``fleet_latency_ms``
    aggregated across the fleet.  ``trace_out`` enables distributed
    tracing for the run and dumps the ``trace_top`` slowest requests'
    span trees as Chrome trace-event JSON into that directory."""
    from ..orchestration.continuous import ContinuousReplica
    from ..orchestration.paged import PagedContinuousServer
    from ..orchestration.serving import ReplicaRouter
    from ..registry import Registrar
    from ..runtime import Process, actor_args, compose_instance
    from ..runtime.event import EventEngine

    def wait_for(predicate, timeout_s: float, what: str):
        deadline = time.time() + timeout_s
        while not predicate():
            if time.time() > deadline:
                raise TimeoutError(f"shared-prefix rig: {what}")
            time.sleep(0.02)

    tracing = trace_out is not None and trace.TRACER is None
    if tracing:
        # One in-process rig → one tracer covers loadgen root spans
        # AND router spans; replicas synthesize theirs from the
        # propagated context without needing any tracer at all.
        trace.install(service="loadgen")
    engine = EventEngine()
    thread = engine.run_in_thread()
    broker = f"sharedpfx-{uuid.uuid4().hex[:6]}"
    processes = []

    def make_process(pid):
        process = Process(namespace="sharedpfx", hostname="h",
                          pid=str(pid), engine=engine, broker=broker)
        processes.append(process)
        return process

    generator = None
    servers = []
    try:
        registrar = Registrar(process=make_process(1))
        wait_for(lambda: registrar.state == "primary", 10,
                 "registrar primary")
        for index, name in enumerate(("replica_a", "replica_b")):
            server = PagedContinuousServer(
                config_name="tiny", slots=2, chunk_steps=4, seed=0,
                enable_prefix_cache=True, max_queue=256,
                watchdog_s=5.0,
                draft_config_name="tiny" if spec_k else None,
                spec_k=spec_k or 4)
            if spec_k:
                _enable_paired_draft(server, spec_k)
            servers.append(server)
            compose_instance(ContinuousReplica, actor_args(name),
                             process=make_process(2 + index),
                             server=server)
        router = compose_instance(
            ReplicaRouter, actor_args("router"),
            process=make_process(8),
            prefix_alpha=1.0 if prefix_routing else 0.0,
            kv_transfer=kv_transfer)
        wait_for(lambda: router.share["replicas"] == 2, 30,
                 "router discovery")
        generator = LoadGenerator(
            make_process(9), f"{router.topic_path}/in",
            payload_fn=shared_prefix_payloads(
                n_conversations=n_conversations, turns=turns,
                system_len=system_len, seed=seed),
            rate_hz=rate_hz)
        report = generator.run(n_requests,
                               drain_timeout_s=drain_timeout_s)
        totals = _fleet_kv_stats(servers)
        _attach_kv_rates(report, totals)
        _attach_pool_census(report, servers)
        report.fleet_latency_ms = fleet_latency(servers)
        report.final_tokens = dict(generator.final_tokens)
        report.spec_stats = _fleet_spec_stats(servers)
        report.spec_accept_hist = dict(generator.spec_accept_hist)
        report.server_stats = dict(
            router.counters, **totals,
            kv_directory_size=router.share.get("kv_directory_size", 0))
        if trace_out is not None:
            generator.dump_traces(trace_out, top_k=trace_top)
        return report
    finally:
        if tracing:
            trace.uninstall()
        if generator is not None:
            generator.close()
        for process in reversed(processes):
            try:
                process.terminate()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        engine.terminate()
        thread.join(timeout=5)


def longtail_payloads(n_prefixes: int = 8, prefix_len: int = 96,
                      tail_len: int = 8, max_new_tokens: int = 4,
                      vocab: int = 1024, seed: int = 0,
                      stream: bool = True) -> Callable[[int], Dict]:
    """Long-tail prefix workload: ``n_prefixes`` DISTINCT shared
    prefixes visited round-robin, each request re-sending its prefix
    plus ``tail_len`` fresh tokens.  The reuse distance is therefore
    ``n_prefixes`` requests — size the prefix working set
    (``n_prefixes × prefix_len/block_size`` blocks) past the HBM pool
    and an HBM-only cache thrashes (every hit evicted before its
    reuse), while a host tier holds the whole tail and serves it back
    through restores.  Deterministic from ``seed``."""
    import numpy as np

    rng = np.random.RandomState(seed)
    prefixes = [rng.randint(1, vocab, size=prefix_len).astype(np.int32)
                for _ in range(n_prefixes)]

    def payload_fn(index: int) -> Dict:
        which = index % n_prefixes
        tail = np.asarray(
            [1 + (7919 * (index + 1) + 31 * position) % (vocab - 1)
             for position in range(tail_len)], np.int32)
        payload = {"tokens": np.concatenate([prefixes[which], tail]),
                   "max_new_tokens": max_new_tokens}
        if stream:
            payload["stream"] = 1
        return payload

    return payload_fn


def run_longtail(n_requests: int = 36, rate_hz: float = 25.0,
                 n_prefixes: int = 6, prefix_len: int = 384,
                 tail_len: int = 8,
                 total_blocks: int = 52,
                 host_tier_blocks: int = 160,
                 restore_blocks_per_step: int = 24,
                 chunk_prefill_tokens: int = 64,
                 warmup_requests: int = 12,
                 drain_timeout_s: float = 180.0,
                 seed: int = 0,
                 spill_dir: Optional[str] = None,
                 spill_blocks: int = 1024) -> LoadReport:
    """Capacity A/B rig for the tiered KV cache: ONE paged replica
    whose HBM pool (``total_blocks``) is deliberately smaller than the
    longtail workload's prefix working set, behind a prefix-aware
    router.  ``host_tier_blocks=0`` is the HBM-only baseline — same
    pool, same workload, eviction deletes.  The tier-on run must beat
    it on BOTH ``prefix_hit_rate`` and mean TTFT (the capacity gate in
    tests/test_kv_tier.py).  The report's ``prefix_hit_rate_host`` says
    how many of
    the hits only existed because demotion preserved them.

    Default sizing makes restore beat recompute in STEPS, which is
    what TTFT measures on any backend: a 384-token prefix is 24
    blocks, so a miss re-prefills 6 chunks of ``chunk_prefill_tokens``
    = 64 while a host hit defers one step, lands the whole chain in
    one batched scatter (``restore_blocks_per_step=24``) and prefills
    only the tail.

    ``spill_dir`` enables the SSD spill tier under the host tier
    (loadgen ``--disk-blocks``): host-RAM overflow demotes to disk
    instead of purging, so the comparison becomes a FOUR-way ladder —
    HBM hit, host restore, disk restore, recompute — and the report's
    ``kv_spills`` / ``kv_disk_restores`` counters say how much of the
    working set only survived on disk."""
    from ..orchestration.continuous import ContinuousReplica
    from ..orchestration.paged import PagedContinuousServer
    from ..orchestration.serving import ReplicaRouter
    from ..registry import Registrar
    from ..runtime import Process, actor_args, compose_instance
    from ..runtime.event import EventEngine

    def wait_for(predicate, timeout_s: float, what: str):
        deadline = time.time() + timeout_s
        while not predicate():
            if time.time() > deadline:
                raise TimeoutError(f"longtail rig: {what}")
            time.sleep(0.02)

    engine = EventEngine()
    thread = engine.run_in_thread()
    broker = f"longtail-{uuid.uuid4().hex[:6]}"
    processes = []

    def make_process(pid):
        process = Process(namespace="longtail", hostname="h",
                          pid=str(pid), engine=engine, broker=broker)
        processes.append(process)
        return process

    generator = None
    try:
        registrar = Registrar(process=make_process(1))
        wait_for(lambda: registrar.state == "primary", 10,
                 "registrar primary")
        prompt_len = prefix_len + tail_len
        max_seq = ((prompt_len + 8 + 15) // 16) * 16
        server = PagedContinuousServer(
            config_name="tiny", slots=2, max_seq=max_seq,
            chunk_steps=4, seed=0, enable_prefix_cache=True,
            total_blocks=total_blocks,
            host_tier_blocks=host_tier_blocks,
            restore_blocks_per_step=restore_blocks_per_step,
            chunk_prefill_tokens=chunk_prefill_tokens,
            spill_dir=spill_dir, spill_blocks=spill_blocks,
            max_queue=256, watchdog_s=10.0)
        compose_instance(ContinuousReplica, actor_args("replica_a"),
                         process=make_process(2), server=server)
        router = compose_instance(ReplicaRouter, actor_args("router"),
                                  process=make_process(8))
        wait_for(lambda: router.share["replicas"] == 1, 30,
                 "router discovery")
        generator = LoadGenerator(
            make_process(9), f"{router.topic_path}/in",
            payload_fn=longtail_payloads(
                n_prefixes=n_prefixes, prefix_len=prefix_len,
                tail_len=tail_len, seed=seed),
            rate_hz=rate_hz)
        if warmup_requests:
            # Same payload sequence both arms see in the measured
            # run: compiles every serve/gather/scatter shape and
            # brings each arm to ITS steady state (tier-on: working
            # set demoted to host; tier-off: pool thrashed) so the
            # A/B measures serving, not first-touch compilation.
            generator.run(warmup_requests,
                          drain_timeout_s=drain_timeout_s)
        report = generator.run(n_requests,
                               drain_timeout_s=drain_timeout_s)
        totals = _fleet_kv_stats([server])
        _attach_kv_rates(report, totals)
        _attach_pool_census(report, [server])
        report.fleet_latency_ms = fleet_latency([server])
        report.server_stats = dict(router.counters, **totals)
        return report
    finally:
        if generator is not None:
            generator.close()
        for process in reversed(processes):
            try:
                process.terminate()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        engine.terminate()
        thread.join(timeout=5)


def multitenant_payloads(n_adapters: int = 4, zipf_s: float = 1.2,
                         prompt_len: int = 12,
                         max_new_tokens: int = 4, vocab: int = 1024,
                         seed: int = 0, schedule_len: int = 4096
                         ) -> Callable[[int], Dict]:
    """Multi-tenant workload: every request names one of
    ``n_adapters`` tenants' adapters, drawn from a zipf-shaped
    popularity distribution (``weight ∝ 1/rank^zipf_s`` — a few hot
    tenants, a long tail of cold ones, the shape S-LoRA serves).
    Prompts are per-request random (NO shared prefix), so the A/B
    isolates ADAPTER locality from prefix locality.  Deterministic
    from ``seed``."""
    import numpy as np

    rng = np.random.RandomState(seed)
    weights = 1.0 / np.arange(1, n_adapters + 1) ** zipf_s
    weights /= weights.sum()
    schedule = rng.choice(n_adapters, size=schedule_len, p=weights)

    def payload_fn(index: int) -> Dict:
        which = int(schedule[index % schedule_len])
        prompt = np.asarray(
            [1 + (7919 * (index + 1) + 31 * position) % (vocab - 1)
             for position in range(prompt_len)], np.int32)
        return {"tokens": prompt, "max_new_tokens": max_new_tokens,
                "adapter": f"tenant-{which}"}

    return payload_fn


def _noisy_loadgen_adapter(config, lora_config, seed: int):
    """A host-side random adapter whose B factors are non-zero (a
    fresh-initialized adapter is an exact no-op) — numpy only, so the
    rig can mint tenants without touching the device."""
    import numpy as np

    rng = np.random.RandomState(seed)
    from ..models.lora import factor_dims
    in_dims, out_dims = factor_dims(config)
    layers = []
    for _ in range(config.n_layers):
        layer = {}
        for target in lora_config.targets:
            layer[target] = {
                "a": (rng.randn(in_dims[target], lora_config.rank)
                      * in_dims[target] ** -0.5).astype(np.float32),
                "b": (rng.randn(lora_config.rank, out_dims[target])
                      * 0.05).astype(np.float32)}
        layers.append(layer)
    return {"layers": layers}


def run_multitenant(n_requests: int = 32, rate_hz: float = 25.0,
                    n_adapters: int = 4, zipf_s: float = 1.2,
                    adapter_aware: bool = True,
                    warmup_requests: int = 8,
                    drain_timeout_s: float = 120.0,
                    seed: int = 0) -> LoadReport:
    """Warm-adapter-routing A/B rig: TWO paged replicas, each holding
    HALF the tenants' adapters (evens on A, odds on B — every adapter
    is warm on exactly one replica), behind either the adapter-aware
    router (``adapter_affinity=1``) or the adapter-blind baseline
    (``adapter_affinity=0`` — PR-4 P2C, never inspects the adapter
    field).  The blind router lands ~half the zipf-distributed
    requests on the WRONG replica, each an ``unknown_adapter``
    rejection the client must answer with a factor re-upload
    (``adapter_cold_starts``); the aware router reads adapter
    residency off the SAME prefix digests and must take ZERO cold
    starts — a warm adapter anywhere in the fleet is a warm adapter
    for every request that names it."""
    from ..kvstore.adapters import adapter_hex
    from ..models import llama
    from ..models.lora import LoRAConfig
    from ..orchestration.continuous import ContinuousReplica
    from ..orchestration.paged import PagedContinuousServer
    from ..orchestration.serving import ReplicaRouter
    from ..registry import Registrar
    from ..runtime import Process, actor_args, compose_instance
    from ..runtime.event import EventEngine

    def wait_for(predicate, timeout_s: float, what: str):
        deadline = time.time() + timeout_s
        while not predicate():
            if time.time() > deadline:
                raise TimeoutError(f"multitenant rig: {what}")
            time.sleep(0.02)

    engine = EventEngine()
    thread = engine.run_in_thread()
    broker = f"mtenant-{uuid.uuid4().hex[:6]}"
    processes = []

    def make_process(pid):
        process = Process(namespace="mtenant", hostname="h",
                          pid=str(pid), engine=engine, broker=broker)
        processes.append(process)
        return process

    lora_config = LoRAConfig(rank=4, alpha=8.0, targets=("wq", "wv"))
    config = llama.CONFIGS["tiny"]
    generator = None
    try:
        registrar = Registrar(process=make_process(1))
        wait_for(lambda: registrar.state == "primary", 10,
                 "registrar primary")
        servers = []
        for index, name in enumerate(("replica_a", "replica_b")):
            server = PagedContinuousServer(
                config_name="tiny", slots=4, max_seq=64,
                chunk_steps=4, seed=0, enable_prefix_cache=True,
                total_blocks=96, max_queue=256, watchdog_s=10.0)
            # Home placement: evens on A, odds on B — each tenant's
            # factors are paged (and digest-advertised) on exactly
            # one replica, so routing is the ONLY thing that decides
            # warm vs cold.
            for tenant in range(index, n_adapters, 2):
                server.load_adapter(
                    f"tenant-{tenant}",
                    _noisy_loadgen_adapter(config, lora_config,
                                           seed=100 + tenant),
                    lora_config)
            compose_instance(ContinuousReplica, actor_args(name),
                             process=make_process(2 + index),
                             server=server)
            servers.append(server)
        router = compose_instance(
            ReplicaRouter, actor_args("router"),
            process=make_process(8),
            adapter_affinity=1.0 if adapter_aware else 0.0)
        wait_for(lambda: router.share["replicas"] == 2, 30,
                 "router discovery")
        hexes = [adapter_hex(f"tenant-{t}") for t in range(n_adapters)]
        wait_for(lambda: all(
            router.directory.adapter_owners(
                h, router.process.event.now()) for h in hexes),
            30, "adapter residency in fleet digests")
        generator = LoadGenerator(
            make_process(9), f"{router.topic_path}/in",
            payload_fn=multitenant_payloads(
                n_adapters=n_adapters, zipf_s=zipf_s, seed=seed),
            rate_hz=rate_hz)
        if warmup_requests:
            generator.run(warmup_requests,
                          drain_timeout_s=drain_timeout_s)
            for counter in ("adapter_warm_routes",
                            "adapter_cold_routes"):
                router.counters[counter] = 0
        report = generator.run(n_requests,
                               drain_timeout_s=drain_timeout_s)
        report.adapter_cold_starts = \
            report.error_kinds.get("unknown_adapter", 0)
        report.adapter_warm_routes = \
            router.counters.get("adapter_warm_routes", 0)
        report.adapter_cold_routes = \
            router.counters.get("adapter_cold_routes", 0)
        totals = _fleet_kv_stats(servers)
        _attach_kv_rates(report, totals)
        _attach_pool_census(report, servers)
        report.server_stats = dict(router.counters, **{
            key: sum(server.stats().get(key, 0) for server in servers)
            for key in ("adapter_warm_loads", "adapter_cold_loads",
                        "adapter_pages_hbm", "adapter_pages_host",
                        "adapter_pages_disk")})
        return report
    finally:
        if generator is not None:
            generator.close()
        for process in reversed(processes):
            try:
                process.terminate()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        engine.terminate()
        thread.join(timeout=5)


def run_restart(n_requests: int = 12, rate_hz: float = 40.0,
                n_prefixes: int = 3, prefix_len: int = 192,
                tail_len: int = 8,
                total_blocks: int = 20,
                restore_blocks_per_step: int = 16,
                chunk_prefill_tokens: int = 64,
                warmup_requests: int = 6,
                recovery_batch: int = 4,
                hit_rate_floor: float = 0.34,
                drain_timeout_s: float = 180.0,
                seed: int = 0,
                spill_dir: Optional[str] = None,
                spill_blocks: int = 1024,
                adopt: bool = True) -> LoadReport:
    """Warm-replica-restart rig (loadgen ``--restart-replica``): ONE
    paged replica with ``host_tier_blocks=0`` and an SSD spill dir, so
    every demotion lands straight on disk — the durable working set.
    After a warmup phase that spills the longtail prefixes, the
    replica's PROCESS is killed mid-run (the LWT fires, the router
    sees it leave) and a fresh replica is composed on the same broker.
    ``adopt=True`` hands the respawn the same ``spill_dir`` (warm
    restart: the ctor scan re-adopts every intact chain and advertises
    tier 2); ``adopt=False`` is the cold-restart A/B baseline — same
    death, same respawn, same spill CONFIG (an empty sibling
    directory, so both arms pay the durability tax on eviction), but
    the pre-crash state is lost.  Adoption is the only variable.

    The measured phase runs in ``recovery_batch``-request sub-batches;
    per batch the rig computes the respawned replica's prefix hit rate
    from counter deltas and stamps ``restart_recovery_ms`` — time from
    respawn to the END of the first batch at or above
    ``hit_rate_floor`` — into ``report.server_stats`` (alongside the
    per-batch ``restart_hit_rates`` curve, ``None`` recovery when the
    floor is never reached).  :func:`run_restart_ab` asserts warm
    beats cold on hit rate AND mean TTFT with bit-exact greedy
    outputs."""
    from ..orchestration.continuous import ContinuousReplica
    from ..orchestration.paged import PagedContinuousServer
    from ..orchestration.serving import ReplicaRouter
    from ..registry import Registrar
    from ..runtime import Process, actor_args, compose_instance
    from ..runtime.event import EventEngine

    def wait_for(predicate, timeout_s: float, what: str):
        deadline = time.time() + timeout_s
        while not predicate():
            if time.time() > deadline:
                raise TimeoutError(f"restart rig: {what}")
            time.sleep(0.02)

    if spill_dir is None:
        raise ValueError("run_restart needs a spill_dir — the rig "
                         "exists to measure spill adoption")
    engine = EventEngine()
    thread = engine.run_in_thread()
    broker = f"restart-{uuid.uuid4().hex[:6]}"
    processes = []

    def make_process(pid):
        process = Process(namespace="restart", hostname="h",
                          pid=str(pid), engine=engine, broker=broker)
        processes.append(process)
        return process

    def make_server(directory: str):
        prompt_len = prefix_len + tail_len
        max_seq = ((prompt_len + 8 + 15) // 16) * 16
        return PagedContinuousServer(
            config_name="tiny", slots=2, max_seq=max_seq,
            chunk_steps=4, seed=0, enable_prefix_cache=True,
            total_blocks=total_blocks, host_tier_blocks=0,
            restore_blocks_per_step=restore_blocks_per_step,
            chunk_prefill_tokens=chunk_prefill_tokens,
            spill_dir=directory, spill_blocks=spill_blocks,
            max_queue=256, watchdog_s=10.0)

    generator = None
    try:
        registrar = Registrar(process=make_process(1))
        wait_for(lambda: registrar.state == "primary", 10,
                 "registrar primary")
        server_a = make_server(spill_dir)
        process_a = make_process(2)
        compose_instance(ContinuousReplica, actor_args("replica_a"),
                         process=process_a, server=server_a)
        router = compose_instance(ReplicaRouter, actor_args("router"),
                                  process=make_process(8))
        wait_for(lambda: router.share["replicas"] == 1, 30,
                 "router discovery")
        payloads = longtail_payloads(
            n_prefixes=n_prefixes, prefix_len=prefix_len,
            tail_len=tail_len, seed=seed)
        generator = LoadGenerator(
            make_process(9), f"{router.topic_path}/in",
            payload_fn=payloads, rate_hz=rate_hz)
        sent_total = 0
        if warmup_requests:
            generator.run(warmup_requests,
                          drain_timeout_s=drain_timeout_s)
            sent_total += warmup_requests
        spilled = int(server_a.stats().get("kv_spills", 0))

        # --- the restart: CRASH the only replica (LWT fires, the
        # registrar evicts it), then respawn it fresh ---
        process_a.kill()
        wait_for(lambda: router.share["replicas"] == 0, 30,
                 "dead replica leaving the fleet")
        server_b = make_server(spill_dir if adopt
                               else spill_dir + "-cold")
        respawned_at = time.time()
        compose_instance(ContinuousReplica, actor_args("replica_b"),
                         process=make_process(3), server=server_b)
        wait_for(lambda: router.share["replicas"] == 1, 30,
                 "respawn discovery")

        # --- measured phase: sub-batched so the hit-rate RECOVERY
        # curve is observable, payload index offset so batches keep
        # walking the same longtail instead of replaying batch one ---
        batches: List[LoadReport] = []
        final_tokens: Dict[str, List[int]] = {}
        hit_rates: List[float] = []
        recovery_ms: Optional[float] = None
        remaining = n_requests
        while remaining > 0:
            batch_n = min(recovery_batch, remaining)
            before = server_b.stats()
            generator.payload_fn = \
                lambda i, base=sent_total: payloads(base + i)
            batch = generator.run(batch_n,
                                  drain_timeout_s=drain_timeout_s)
            for request_id, tokens in generator.final_tokens.items():
                final_tokens[f"r{sent_total}_{request_id}"] = tokens
            after = server_b.stats()
            hits = int(after["prefix_hits"]) - int(before["prefix_hits"])
            lookups = hits + (int(after["prefix_misses"])
                              - int(before["prefix_misses"]))
            rate = hits / lookups if lookups else 0.0
            hit_rates.append(round(rate, 4))
            if recovery_ms is None and rate >= hit_rate_floor:
                recovery_ms = round(
                    (time.time() - respawned_at) * 1000.0, 1)
            batches.append(batch)
            sent_total += batch_n
            remaining -= batch_n
        report = LoadReport(
            sent=sum(b.sent for b in batches),
            completed=sum(b.completed for b in batches),
            errors=sum(b.errors for b in batches),
            timeouts=sum(b.timeouts for b in batches),
            elapsed_s=sum(b.elapsed_s for b in batches),
            latencies_ms=[v for b in batches for v in b.latencies_ms],
            tokens_total=sum(b.tokens_total for b in batches),
            ttfts_ms=[v for b in batches for v in b.ttfts_ms],
            duplicate_finals=sum(b.duplicate_finals for b in batches))
        for batch in batches:
            for phase, values in batch.phase_ms.items():
                report.phase_ms.setdefault(phase, []).extend(values)
            for kind, count in batch.error_kinds.items():
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + count
        report.final_tokens = final_tokens
        totals = _fleet_kv_stats([server_b])
        _attach_kv_rates(report, totals)
        report.fleet_latency_ms = fleet_latency([server_b])
        report.server_stats = dict(
            router.counters, **totals,
            warmup_spills=spilled,
            restart_recovery_ms=recovery_ms,
            restart_hit_rates=hit_rates)
        return report
    finally:
        if generator is not None:
            generator.close()
        for process in reversed(processes):
            try:
                process.terminate()
            except Exception:  # noqa: BLE001 - replica_a is already
                pass           # dead by design
        engine.terminate()
        thread.join(timeout=5)


def run_restart_ab(n_requests: int = 18, rate_hz: float = 25.0,
                   seed: int = 0,
                   drain_timeout_s: float = 180.0,
                   **kwargs) -> Tuple[LoadReport, LoadReport]:
    """Warm-restart A/B gate: the SAME seeded longtail sequence
    through :func:`run_restart` twice — cold (respawn spills to an
    empty sibling directory) then warm (respawn adopts the dead
    replica's) — each arm rooted in its own fresh temp dir so the
    warmup phases are identical.  Asserts
    the greedy outputs are BIT-EXACT request for request (a restored
    block may never change a token), then returns ``(cold, warm)``;
    the caller (tests/test_kv_spill.py) checks warm strictly beats
    cold on measured-phase hit rate and
    mean TTFT."""
    import tempfile

    reports = []
    for adopt in (False, True):
        with tempfile.TemporaryDirectory(prefix="kvspill-ab-") as root:
            reports.append(run_restart(
                n_requests=n_requests, rate_hz=rate_hz, seed=seed,
                drain_timeout_s=drain_timeout_s,
                spill_dir=os.path.join(root, "spill"),
                adopt=adopt, **kwargs))
    cold, warm = reports
    both = set(cold.final_tokens) & set(warm.final_tokens)
    mismatched = [request_id for request_id in sorted(both)
                  if cold.final_tokens[request_id]
                  != warm.final_tokens[request_id]]
    if mismatched:
        raise AssertionError(
            f"restart A/B not bit-exact (seed={seed}): "
            f"{len(mismatched)}/{len(both)} requests diverged, first "
            f"{mismatched[0]}")
    if not both:
        raise AssertionError(
            "restart A/B compared zero requests — the gate proved "
            "nothing")
    return cold, warm


def run_compile_cache_ab(cache_dir: Optional[str] = None,
                         prompt_len: int = 24,
                         max_new_tokens: int = 4, seed: int = 0,
                         config_name: str = "tiny"
                         ) -> Tuple[LoadReport, LoadReport]:
    """Persistent-compilation-cache A/B gate — the PR-12 warm-restart
    gate extended to COMPILE time.  The same single-request greedy
    decode through two freshly constructed paged engines sharing ONE
    persistent cache directory: arm 1 COLD (empty directory — every
    program really compiles and populates the cache), then
    ``jax.clear_caches()`` drops the in-memory jit caches (the honest
    in-process stand-in for a process restart), arm 2 WARM (same
    directory — every lookup should retrieve instead of compile).
    Asserts the warm arm strictly beats the cold arm on
    time-to-first-compiled-step, saw > 0 persistent-cache hits, and
    produced bit-exact greedy tokens.  Returns ``(cold, warm)``
    LoadReports whose ``compile_cache`` dict carries the per-arm
    ledger deltas; ``elapsed_s`` IS the time-to-first-compiled-step.

    ``cache_dir=None`` (the default) uses a fresh temp directory —
    pass a directory only if you can guarantee it starts empty, or
    the cold arm is not cold and the gate proves nothing.  A CPU test
    rig: it leaves the cache setting as it found it, and where
    ``JAX_COMPILATION_CACHE_DIR`` pins the cache to a directory the
    rig cannot empty it refuses to run."""
    import tempfile

    import jax
    import numpy as np

    from ..obs import compiles
    from ..orchestration.continuous import DecodeRequest
    from ..orchestration.paged import PagedContinuousServer

    ledger_owned = compiles.LEDGER is None
    ledger = compiles.install(service="cache-ab")
    rng = np.random.RandomState(seed)
    prompt = rng.randint(1, 256, size=prompt_len).astype(np.int32)
    reports = []
    with contextlib.ExitStack() as scope:
        if ledger_owned:
            scope.callback(compiles.uninstall)
        if cache_dir is None:
            cache_dir = scope.enter_context(tempfile.TemporaryDirectory(
                prefix="compile-cache-ab-"))
        if scope.enter_context(
                compiles.persistent_cache(cache_dir)) != cache_dir:
            raise RuntimeError(
                f"cache A/B: {compiles.CACHE_DIR_ENV} pins the cache "
                "elsewhere, so the cold arm cannot start empty")
        for arm in ("cold", "warm"):
            jax.clear_caches()
            base = ledger.snapshot()
            began = time.monotonic()
            server = PagedContinuousServer(
                config_name=config_name, slots=2, chunk_steps=4,
                seed=0, compilation_cache_dir=cache_dir)
            server.submit(DecodeRequest(
                request_id=f"ab_{arm}", prompt=prompt,
                max_new_tokens=max_new_tokens))
            done = []
            for _ in range(512):
                done.extend(server.step())
                if done:
                    break
            else:
                raise AssertionError(
                    f"cache A/B: {arm} arm request never completed")
            ttfs_s = time.monotonic() - began
            if done[0].error is not None:
                raise AssertionError(
                    f"cache A/B: {arm} arm errored: {done[0].error}")
            after = ledger.snapshot()
            delta = {key: round(after[key] - base[key], 3)
                     for key in ("compiles", "cache_hits",
                                 "cache_misses", "compile_wall_ms_total",
                                 "cache_load_ms_total")}
            delta["time_to_first_step_s"] = round(ttfs_s, 4)
            report = LoadReport(
                sent=1, completed=1, errors=0, timeouts=0,
                elapsed_s=ttfs_s, latencies_ms=[ttfs_s * 1e3],
                tokens_total=len(done[0].tokens or []),
                compile_cache=delta)
            report.final_tokens = {
                done[0].request_id:
                [int(t) for t in (done[0].tokens or [])]}
            reports.append(report)
    cold, warm = reports
    cold_tokens = next(iter(cold.final_tokens.values()))
    warm_tokens = next(iter(warm.final_tokens.values()))
    if cold_tokens != warm_tokens:
        raise AssertionError(
            f"cache A/B not bit-exact (seed={seed}): a cached program "
            f"may never change a token — cold {cold_tokens} vs warm "
            f"{warm_tokens}")
    if warm.compile_cache["cache_hits"] <= 0:
        raise AssertionError(
            "cache A/B: warm arm saw ZERO persistent-cache hits — the "
            "cache directory wiring is dead")
    if not warm.elapsed_s < cold.elapsed_s:
        raise AssertionError(
            f"cache A/B: warm restart must strictly beat cold on "
            f"time-to-first-compiled-step, got cold "
            f"{cold.elapsed_s:.3f}s vs warm {warm.elapsed_s:.3f}s")
    return cold, warm


def chaos_schedule(seed: int):
    """The canonical seeded fault schedule for ``loadgen --chaos``:
    one replica death mid-decode, streaming-increment message drops,
    and a device-step stall — the three failure classes the serving
    robustness machinery covers (re-dispatch, dedup-tolerant
    streaming, watchdog/latency).  Deriving the plan purely from
    ``seed`` is what makes a chaos run reproducible."""
    from ..runtime import faults
    return (
        faults.FaultPlan(seed=seed)
        # replica_a dies on its Nth pump — mid-decode under load.
        .add("kill_replica", nth=6 + seed % 5, match="replica_a")
        # Streamed increments are droppable by design (the final
        # response is authoritative); finals are NOT dropped — nothing
        # retries a silently-eaten terminal response.
        .add("drop_message", nth=4, match="infer_partial")
        .add("drop_message", nth=9, match="infer_partial")
        # Latency blip well under the watchdog threshold: chaos runs
        # exercise the stall POINT; the watchdog trip itself is
        # unit-tested deterministically.
        .add("stall_step", nth=7 + seed % 3, ms=40))


def run_chaos(seed: int = 0, n_requests: int = 40,
              rate_hz: float = 100.0,
              drain_timeout_s: float = 90.0,
              total_blocks: Optional[int] = None,
              host_tier_blocks: int = 0,
              restore_blocks_per_step: int = 2,
              spill_dir: Optional[str] = None,
              spill_blocks: int = 1024,
              spec_k: int = 0,
              compile_gate: bool = False,
              warmup_requests: Optional[int] = None) -> LoadReport:
    """Run an in-process 2-replica serving rig (loopback broker, real
    event engine, Registrar + router) under :func:`chaos_schedule` and
    return the LoadReport.  The invariant a chaos run checks:
    ``report.lost == 0 and report.timeouts == 0`` — every request
    reaches a terminal state (completed, or an explicit error like
    ``deadline_exceeded``/``overloaded``) no matter which replica died
    or which messages vanished.  CPU-friendly (tiny config); set
    ``JAX_PLATFORMS=cpu`` when no accelerator is wanted.

    Replicas run the PAGED backend with prefix caches on and the
    router routes prefix-aware with KV transfer enabled — the chaos
    gate covers the kvstore path too: killing a directory-advertised
    prefix owner mid-stream must still lose ZERO requests (directory
    eviction + fetch-timeout fallback to local prefill).

    ``spill_dir`` gives each replica its OWN subdirectory of it as an
    SSD spill tier (spill dirs are single-owner by design — the
    signature/lease story is per-replica), so a chaos kill lands
    mid-spill: the crash gate in tests/test_chaos.py asserts zero
    lost requests AND that a fresh server adopting the dead replica's
    directory serves bit-exact tokens — torn writes never surface.

    ``compile_gate=True`` adds the compile-ledger steady-state gate:
    a warmup wave of ``warmup_requests`` (default 12 = one full
    period of the shared-prefix payload cycle, so every distinct
    prompt shape the measured wave will send compiles once) runs
    BEFORE the fault plan is armed, the ledger's warmup fence drops,
    and the measured chaos wave must then record ZERO steady-state
    compiles — a replica dying mid-decode and re-dispatching its work
    may never cost the fleet a recompile.  Two mechanisms make that
    true together: pow2 bucketing keeps the survivor's shapes a
    subset of the warmed set, and the replicas SHARE one persistent
    compilation cache directory — prefix-aware routing concentrates
    warmup on the prefix owner, so the failover target can be
    compile-COLD when the kill lands, and its first-touch programs
    must come back as ~ms cache retrievals (booked as hits, never as
    steady compiles).  The report carries the warmup/steady split
    (``warmup_s``, ``warmup_compiles``, ``compiles_steady_state``,
    ``steady_tokens_per_sec``)."""
    import tempfile

    from ..obs import compiles
    from ..orchestration.continuous import ContinuousReplica
    from ..orchestration.paged import PagedContinuousServer
    from ..orchestration.serving import ReplicaRouter
    from ..registry import Registrar
    from ..runtime import (Process, actor_args, compose_instance,
                           faults)
    from ..runtime.event import EventEngine

    def wait_for(predicate, timeout_s: float, what: str):
        deadline = time.time() + timeout_s
        while not predicate():
            if time.time() > deadline:
                raise TimeoutError(f"chaos rig: {what}")
            time.sleep(0.02)

    warmup_began = time.time()
    ledger = None
    ledger_owned = False
    cache_scope = contextlib.ExitStack()
    cache_dir = None
    if compile_gate:
        ledger_owned = compiles.LEDGER is None
        ledger = compiles.install(service="chaos-gate")
        cache_dir = cache_scope.enter_context(compiles.persistent_cache(
            cache_scope.enter_context(tempfile.TemporaryDirectory(
                prefix="chaos-compile-cache-"))))
    # The fault plan arms AFTER the warmup wave when gating compiles —
    # warmup pumps must not consume the schedule's nth counters, or
    # the kill would land mid-warmup instead of mid-measured-decode.
    plan = faults.install(chaos_schedule(seed)) \
        if not compile_gate else None
    engine = EventEngine()
    thread = engine.run_in_thread()
    broker = f"chaos-{uuid.uuid4().hex[:6]}"
    processes = []

    def make_process(pid):
        process = Process(namespace="chaos", hostname="h",
                          pid=str(pid), engine=engine, broker=broker)
        processes.append(process)
        return process

    generator = None
    servers = []
    try:
        registrar = Registrar(process=make_process(1))
        wait_for(lambda: registrar.state == "primary", 10,
                 "registrar primary")
        for index, name in enumerate(("replica_a", "replica_b")):
            # Same config+seed on purpose: greedy decode is replica-
            # independent, so re-dispatched requests finish with the
            # exact tokens the dead replica would have produced.
            server = PagedContinuousServer(
                config_name="tiny", slots=2, chunk_steps=4, seed=0,
                enable_prefix_cache=True, max_queue=256,
                watchdog_s=5.0, total_blocks=total_blocks,
                host_tier_blocks=host_tier_blocks,
                restore_blocks_per_step=restore_blocks_per_step,
                spill_dir=(os.path.join(spill_dir, name)
                           if spill_dir else None),
                spill_blocks=spill_blocks,
                draft_config_name="tiny" if spec_k else None,
                spec_k=spec_k or 4,
                compilation_cache_dir=cache_dir)
            if spec_k:
                # Kill-mid-spec-round coverage: greedy determinism +
                # idempotent replay must hold through rejected-tail
                # rollbacks exactly as through plain decode.
                _enable_paired_draft(server, spec_k)
            servers.append(server)
            compose_instance(ContinuousReplica, actor_args(name),
                             process=make_process(2 + index),
                             server=server,
                             # Dead-owner fallback must fire well
                             # inside the drain budget.
                             kv_fetch_timeout_s=2.0)
        router = compose_instance(ReplicaRouter, actor_args("router"),
                                  process=make_process(8),
                                  kv_transfer=True)
        wait_for(lambda: router.share["replicas"] == 2, 30,
                 "router discovery")
        generator = LoadGenerator(
            make_process(9), f"{router.topic_path}/in",
            # Shared 32-token system prefix: the fault schedule then
            # kills a replica the directory advertises as an owner.
            payload_fn=shared_prefix_payloads(
                n_conversations=3, turns=4, system_len=32,
                seed=seed),
            rate_hz=rate_hz)
        warmup_s = 0.0
        warmup_compiles = 0
        if compile_gate:
            # One full payload period: every distinct prompt the
            # measured wave will send compiles (or cache-hits) here.
            generator.run(12 if warmup_requests is None
                          else int(warmup_requests),
                          drain_timeout_s=drain_timeout_s)
            warmup_compiles = ledger.compiles
            ledger.fence()
            warmup_s = time.time() - warmup_began
            plan = faults.install(chaos_schedule(seed))
        report = generator.run(n_requests,
                               drain_timeout_s=drain_timeout_s)
        totals = _fleet_kv_stats(servers)
        _attach_kv_rates(report, totals)
        report.final_tokens = dict(generator.final_tokens)
        report.fleet_latency_ms = fleet_latency(servers)
        report.spec_stats = _fleet_spec_stats(servers)
        report.spec_accept_hist = dict(generator.spec_accept_hist)
        report.server_stats = dict(
            router.counters, **totals,
            replicas_live=router.share["replicas"],
            faults_fired=len(plan.fired))
        if compile_gate:
            report.warmup_s = round(warmup_s, 3)
            report.warmup_compiles = warmup_compiles
            report.compiles_steady_state = ledger.steady_compiles
            if report.elapsed_s > 0:
                report.steady_tokens_per_sec = round(
                    report.tokens_total / report.elapsed_s, 2)
            if ledger.steady_compiles:
                offenders = sorted({
                    (entry["program"], entry["signature"])
                    for entry in ledger.snapshot()["records"]
                    if entry["steady"]})
                raise AssertionError(
                    f"chaos compile gate: {ledger.steady_compiles} "
                    f"steady-state compile(s) after the warmup fence "
                    f"— pow2 bucket discipline regressed: {offenders}")
        return report
    finally:
        faults.uninstall()
        if generator is not None:
            generator.close()
        for process in reversed(processes):
            try:
                process.terminate()
            except Exception:  # noqa: BLE001 - the chaos run may have
                pass           # already killed this process
        engine.terminate()
        thread.join(timeout=5)
        if ledger is not None:
            ledger.lift_fence()
            if ledger_owned:
                compiles.uninstall()
        cache_scope.close()


def run_spec_ab(spec_k: int = 4, n_requests: int = 24,
                rate_hz: float = 50.0, seed: int = 0,
                chaos: bool = False,
                drain_timeout_s: float = 90.0
                ) -> Tuple[LoadReport, LoadReport]:
    """A/B gate for speculative decoding on the serving path: the SAME
    seeded payload sequence through the same 2-replica paged rig, once
    plain and once with a ``spec_k``-token paired draft, asserting the
    greedy outputs are BIT-EXACT request for request.  ``chaos=True``
    runs both sides under :func:`chaos_schedule` instead — a replica
    dying mid-spec-round must re-dispatch idempotently (zero lost,
    zero duplicate finals) and still match the plain side token for
    token, which rules out half-committed speculative state leaking
    across the replay.  Returns ``(base_report, spec_report)``; the
    spec report carries the fleet ``spec_stats`` counters and the
    per-request ``spec_accept_hist`` acceptance histograms."""
    if chaos:
        base = run_chaos(seed=seed, n_requests=n_requests,
                         rate_hz=rate_hz,
                         drain_timeout_s=drain_timeout_s)
        spec = run_chaos(seed=seed, n_requests=n_requests,
                         rate_hz=rate_hz,
                         drain_timeout_s=drain_timeout_s,
                         spec_k=spec_k)
    else:
        base = run_shared_prefix(n_requests=n_requests,
                                 rate_hz=rate_hz, seed=seed,
                                 drain_timeout_s=drain_timeout_s)
        spec = run_shared_prefix(n_requests=n_requests,
                                 rate_hz=rate_hz, seed=seed,
                                 drain_timeout_s=drain_timeout_s,
                                 spec_k=spec_k)
    both = set(base.final_tokens) & set(spec.final_tokens)
    mismatched = [request_id for request_id in sorted(both)
                  if base.final_tokens[request_id]
                  != spec.final_tokens[request_id]]
    if mismatched:
        raise AssertionError(
            f"spec A/B not bit-exact (spec_k={spec_k}, seed={seed}): "
            f"{len(mismatched)}/{len(both)} requests diverged, first "
            f"{mismatched[0]}")
    if not both:
        raise AssertionError(
            "spec A/B compared zero requests — both runs completed "
            "disjoint id sets, the gate proved nothing")
    return base, spec


def command_automaton(vocab: int = 1024):
    """Token grammar for the structured workload's agentic "tool
    call" — a JSON-shaped command ``{ "action" : VERB , "args" : [
    ARG{0..2} ] }`` where every skeleton token (braces, key names,
    colons, commas) is the SOLE legal token in its state.  Those
    single-token states chain into deterministic segments the
    jump-forward path drafts for free: of the 8-11 generated tokens
    only the verb and args are model choices."""
    from ..models.constrained import automaton_from_rules

    LBRACE, KEY_ACTION, COLON, COMMA = 10, 11, 12, 13
    KEY_ARGS, LBRACK, RBRACK, RBRACE = 14, 15, 16, 17
    VERBS, ARGS = (3, 4, 5), (6, 7, 8, 9)
    return automaton_from_rules(
        vocab=vocab,
        rules={
            0: [((LBRACE,), 1)],
            1: [((KEY_ACTION,), 2)],      # ── forced: "action"
            2: [((COLON,), 3)],           # ── forced: :
            3: [(VERBS, 4)],              #    model picks the verb
            4: [((COMMA,), 5)],           # ── forced: ,
            5: [((KEY_ARGS,), 6)],        # ── forced: "args"
            6: [((COLON,), 7)],           # ── forced: :
            7: [((LBRACK,), 8)],          # ── forced: [
            8: [(ARGS, 9), ((RBRACK,), 10)],
            9: [(ARGS, 11), ((RBRACK,), 10)],
            11: [((RBRACK,), 10)],        # ── forced: ] (args capped)
            10: [((RBRACE,), 12)],        # ── forced: }
            12: [],                       # terminal
        },
        accepting=[12])


def structured_payloads(n_contexts: int = 3, context_len: int = 32,
                        tail_len: int = 8, max_new_tokens: int = 16,
                        vocab: int = 1024, seed: int = 0,
                        constrained: bool = True
                        ) -> Callable[[int], Dict]:
    """Agentic structured-output traffic: ``n_contexts`` shared "tool
    context" prefixes (the agent scaffold every turn re-sends — prefix
    cache food) each followed by a fresh per-request observation tail,
    answered with a grammar-constrained command (``automaton="cmd"``).
    Greedy on purpose: the constrained-vs-unconstrained A/B compares
    goodput over IDENTICAL deterministic payloads.  ``constrained=
    False`` emits the same sequence without the automaton field — the
    B side of the goodput A/B."""
    import numpy as np

    rng = np.random.RandomState(seed)
    contexts = [rng.randint(1, vocab, size=context_len)
                .astype(np.int32) for _ in range(n_contexts)]

    def payload_fn(index: int) -> Dict:
        context = contexts[index % n_contexts]
        tail = np.asarray(
            [1 + (7451 * (index + 1) + 17 * position) % (vocab - 1)
             for position in range(tail_len)], np.int32)
        payload = {"tokens": np.concatenate([context, tail]),
                   "max_new_tokens": max_new_tokens,
                   "temperature": 0.0}
        if constrained:
            payload["automaton"] = "cmd"
        return payload

    return payload_fn


def run_structured(n_requests: int = 24, rate_hz: float = 50.0,
                   spec_k: int = 4, draft_mode: str = "ngram",
                   chaos: bool = False,
                   drain_timeout_s: float = 90.0,
                   seed: int = 0
                   ) -> Tuple[LoadReport, LoadReport]:
    """Structured-output workload gate: the SAME seeded agentic
    payload sequence through an automaton-equipped 2-replica paged
    rig, once grammar-constrained and once free-running, returning
    ``(constrained_report, unconstrained_report)``.  Three checks ride
    on it: every constrained final is accepted by the grammar (chaos
    replays included — half-committed automaton state leaking across a
    re-dispatch would surface here as an ungrammatical final), the
    fleet counters carry non-zero ``spec_jump_forward_tokens`` (the
    skeleton segments really were drafted, not decoded), and the pair
    of reports gives the constrained-vs-unconstrained goodput A/B
    (``tokens_total / elapsed_s``; constrained wins when jump-forward
    commits the skeleton in bulk).  ``chaos=True`` arms the standard
    :func:`chaos_schedule` for BOTH sides.  ``draft_mode="ngram"``
    (default) runs model-free — the structured gate composes with
    self-drafting and needs no second model."""
    from ..orchestration.continuous import ContinuousReplica
    from ..orchestration.paged import PagedContinuousServer
    from ..orchestration.serving import ReplicaRouter
    from ..registry import Registrar
    from ..runtime import (Process, actor_args, compose_instance,
                           faults)
    from ..runtime.event import EventEngine

    automaton = command_automaton()

    def one_pass(constrained: bool) -> LoadReport:
        def wait_for(predicate, timeout_s: float, what: str):
            deadline = time.time() + timeout_s
            while not predicate():
                if time.time() > deadline:
                    raise TimeoutError(f"structured rig: {what}")
                time.sleep(0.02)

        plan = faults.install(chaos_schedule(seed)) if chaos else None
        engine = EventEngine()
        thread = engine.run_in_thread()
        broker = f"structured-{uuid.uuid4().hex[:6]}"
        processes = []

        def make_process(pid):
            process = Process(namespace="structured", hostname="h",
                              pid=str(pid), engine=engine,
                              broker=broker)
            processes.append(process)
            return process

        generator = None
        servers = []
        try:
            registrar = Registrar(process=make_process(1))
            wait_for(lambda: registrar.state == "primary", 10,
                     "registrar primary")
            for index, name in enumerate(("replica_a", "replica_b")):
                server = PagedContinuousServer(
                    config_name="tiny", slots=2, chunk_steps=4,
                    seed=0, enable_prefix_cache=True, max_queue=256,
                    watchdog_s=5.0,
                    draft_mode=draft_mode,
                    draft_config_name=("tiny" if draft_mode == "model"
                                       else None),
                    spec_k=spec_k,
                    automata={"cmd": automaton})
                if draft_mode == "model":
                    _enable_paired_draft(server, spec_k)
                servers.append(server)
                compose_instance(ContinuousReplica, actor_args(name),
                                 process=make_process(2 + index),
                                 server=server)
            router = compose_instance(
                ReplicaRouter, actor_args("router"),
                process=make_process(8), kv_transfer=True)
            wait_for(lambda: router.share["replicas"] == 2, 30,
                     "router discovery")
            generator = LoadGenerator(
                make_process(9), f"{router.topic_path}/in",
                payload_fn=structured_payloads(
                    seed=seed, constrained=constrained),
                rate_hz=rate_hz)
            report = generator.run(n_requests,
                                   drain_timeout_s=drain_timeout_s)
            report.final_tokens = dict(generator.final_tokens)
            report.fleet_latency_ms = fleet_latency(servers)
            report.spec_stats = _fleet_spec_stats(servers)
            report.spec_accept_hist = dict(generator.spec_accept_hist)
            report.server_stats = dict(router.counters)
            if plan is not None:
                report.server_stats["faults_fired"] = len(plan.fired)
            return report
        finally:
            if chaos:
                faults.uninstall()
            if generator is not None:
                generator.close()
            for process in reversed(processes):
                try:
                    process.terminate()
                except Exception:  # noqa: BLE001 - teardown (chaos may
                    pass           # have killed this process already)
            engine.terminate()
            thread.join(timeout=5)

    cons = one_pass(constrained=True)
    free = one_pass(constrained=False)
    bad = [request_id for request_id, tokens
           in sorted(cons.final_tokens.items())
           if not automaton.accepts(list(tokens))]
    if bad:
        raise AssertionError(
            f"structured workload: {len(bad)}/{len(cons.final_tokens)}"
            f" constrained finals ungrammatical (seed={seed}, "
            f"chaos={chaos}), first {bad[0]}")
    if not cons.final_tokens:
        raise AssertionError(
            "structured workload: zero constrained finals — the "
            "grammar gate proved nothing")
    return cons, free


def diurnal_trace(duration_s: float, base_hz: float = 2.0,
                  peak_hz: float = 12.0, period_s: float = 8.0,
                  burst_hz: float = 0.0, burst_every_s: float = 0.0,
                  burst_len_s: float = 1.0,
                  seed: int = 0) -> List[float]:
    """Seeded diurnal arrival schedule: send offsets (seconds) for a
    sinusoidal base rate — ``base_hz`` in the valley, ``peak_hz`` at
    the crest, period ``period_s`` — with optional Poisson-arriving
    bursts (``burst_hz`` extra for ``burst_len_s``, mean gap
    ``burst_every_s``).  Arrivals are a non-homogeneous Poisson
    process generated by thinning, fully deterministic per ``seed`` —
    the workload shape an autoscaler must track (valleys are where a
    static peak-sized fleet wastes replicas; bursts are what hysteresis
    must not overreact to).  Feed to :meth:`LoadGenerator.run_trace`."""
    import math
    import random

    rng = random.Random(seed)
    bursts: List[Tuple[float, float]] = []
    if burst_hz > 0 and burst_every_s > 0:
        t = rng.expovariate(1.0 / burst_every_s)
        while t < duration_s:
            bursts.append((t, t + burst_len_s))
            t += burst_len_s + rng.expovariate(1.0 / burst_every_s)

    def rate_at(t: float) -> float:
        wave = 0.5 * (1.0 - math.cos(2.0 * math.pi * t / period_s))
        rate = base_hz + (peak_hz - base_hz) * wave
        if any(start <= t < end for start, end in bursts):
            rate += burst_hz
        return rate

    rate_max = max(base_hz, peak_hz) + burst_hz
    times: List[float] = []
    t = 0.0
    while True:
        t += rng.expovariate(rate_max)
        if t >= duration_s:
            return times
        if rng.random() * rate_max < rate_at(t):
            times.append(t)


def elastic_chaos_schedule(seed: int):
    """The seeded fault schedule gating elastic scale-down: during a
    scripted ``scale_target`` 3→2 scale-down (victim: the idlest
    replica — lexicographically ``decode1`` in the early-run valley),
    ``decode3`` is killed outright, its replacement's first spawn
    attempt fails, and the retry is slow-started.  The invariant: the
    fleet still converges to the target with zero lost and zero
    double-delivered requests.  The rig installs this plan AFTER its
    warmup phase, so ``nth`` counts start with the measured run."""
    from ..runtime import faults
    return (
        faults.FaultPlan(seed=seed)
        # In-process kill (no hard=1: os._exit would take the whole
        # rig); pump count puts it mid-load, after the scale-down.
        .add("kill_replica", nth=6 + seed % 5, match="decode3")
        # The post-kill REPLACEMENT spawn fails outright (bootstrap
        # spawns happened before the plan was installed).
        .add("fail_spawn", nth=1, match="decode3")
        # The retry after the failed replacement announces late
        # (pending-spawn accounting covers the gap — no spawn storm).
        .add("slow_start", nth=1, match="decode3", ms=300))


def _elastic_payloads(seed: int = 0, prompt_len: int = 12,
                      max_new_tokens: int = 4, vocab: int = 1024,
                      stream: bool = False) -> Callable[[int], Dict]:
    """Independent random prompts (no shared prefix — elasticity, not
    cache locality, is under test)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    prompts = rng.randint(1, vocab,
                          size=(32, prompt_len)).astype(np.int32)

    def payload_fn(index: int) -> Dict:
        payload = {"tokens": prompts[index % len(prompts)],
                   "max_new_tokens": max_new_tokens}
        if stream:
            payload["stream"] = 1
        return payload

    return payload_fn


def run_elastic(duration_s: float = 10.0, seed: int = 0,
                base_hz: float = 2.0, peak_hz: float = 12.0,
                period_s: float = 8.0, burst_hz: float = 0.0,
                burst_every_s: float = 0.0, burst_len_s: float = 1.0,
                slo_ttft_ms: float = 500.0,
                static_replicas: Optional[int] = None,
                policy=None, stream: bool = False,
                max_new_tokens: int = 4,
                drain_timeout_s: float = 90.0,
                fault_plan=None,
                scale_script: Tuple[Tuple[float, int], ...] = (),
                command_script: Tuple[Tuple[float, str], ...] = (),
                converge_s: float = 0.0,
                warmup: int = 0) -> LoadReport:
    """In-process ELASTIC serving rig: a :class:`FleetAutoscaler`
    owns the replica fleet (in-process spawner building tiny PAGED
    servers on background threads; terminator kills the replica's
    Process so the Registrar LWT path runs for real) behind a
    ReplicaRouter, driven by a :func:`diurnal_trace` schedule.

    ``static_replicas=N`` instead pins a fixed N-replica fleet with no
    autoscaler — the A/B baseline: the autoscaled fleet must beat the
    static PEAK-sized fleet on ``goodput_per_replica`` over a diurnal
    day (the slow gate in tests/test_autoscaler.py).

    ``scale_script`` is a sequence of ``(delay_s, target)`` operator
    ``(scale_target …)`` commands fired mid-run (the chaos gate's
    scripted scale-down); ``command_script`` fires arbitrary raw
    operator s-exprs at the autoscaler (e.g. ``(rolling_upgrade)``
    for the zero-downtime upgrade rig); ``fault_plan`` installs a
    :mod:`~..runtime.faults` plan for the run; ``converge_s`` waits
    after the load for the fleet to settle (live == target, nothing
    pending or draining) and records ``converged`` in
    ``server_stats``.

    ``warmup`` sends that many throwaway requests BEFORE the measured
    run (and before the fault plan installs): the first decode step
    JIT-compiles on the engine thread, a multi-second stall that would
    otherwise smear the scale/fault timeline into one wakeup."""
    import threading

    from ..orchestration.autoscaler import (AutoscalerPolicy,
                                            FleetAutoscaler)
    from ..orchestration.continuous import ContinuousReplica
    from ..orchestration.paged import PagedContinuousServer
    from ..orchestration.serving import ReplicaRouter
    from ..registry import Registrar
    from ..runtime import (Process, actor_args, compose_instance,
                           faults)
    from ..runtime.event import EventEngine

    def wait_for(predicate, timeout_s: float, what: str):
        deadline = time.time() + timeout_s
        while not predicate():
            if time.time() > deadline:
                raise TimeoutError(f"elastic rig: {what}")
            time.sleep(0.02)

    engine = EventEngine()
    thread = engine.run_in_thread()
    broker = f"elastic-{uuid.uuid4().hex[:6]}"
    processes: List = []
    pid_lock = threading.Lock()
    next_pid = [1]

    def make_process():
        with pid_lock:
            pid = next_pid[0]
            next_pid[0] += 1
        process = Process(namespace="elastic", hostname="h",
                          pid=str(pid), engine=engine, broker=broker)
        processes.append(process)
        return process

    #: slot -> {"process", "server"} for every replica ever built.
    fleet: Dict[str, Dict] = {}
    fleet_lock = threading.Lock()
    servers: List = []

    def build_replica(slot: str):
        # Heavy JAX construction runs OFF the engine thread (the
        # autoscaler calls the spawner from its tick timer; blocking
        # the engine would stall every announcement and drain).
        server = PagedContinuousServer(
            config_name="tiny", slots=2, chunk_steps=4, seed=0,
            enable_prefix_cache=True, max_queue=256, watchdog_s=5.0)
        process = make_process()
        compose_instance(ContinuousReplica, actor_args(slot),
                         process=process, server=server)
        with fleet_lock:
            fleet[slot] = {"process": process, "server": server}
            servers.append(server)

    def spawner(slot: str, _role: str):
        threading.Thread(target=build_replica, args=(slot,),
                         daemon=True).start()

    def terminator(slot: str, _mode: str):
        with fleet_lock:
            entry = fleet.get(slot)
        if entry is None:
            return
        # Non-graceful: the LWT (absent) fires, exactly the eviction
        # path a real dead OS process takes.  Off the engine thread —
        # terminate pumps the transport.
        threading.Thread(target=entry["process"].terminate,
                         kwargs=dict(graceful=False),
                         daemon=True).start()

    generator = None
    autoscaler = None
    timers: List = []
    try:
        registrar = Registrar(process=make_process())
        wait_for(lambda: registrar.state == "primary", 10,
                 "registrar primary")
        router = compose_instance(ReplicaRouter, actor_args("router"),
                                  process=make_process(),
                                  kv_transfer=True)
        if static_replicas is not None:
            expected = static_replicas
            for index in range(static_replicas):
                build_replica(f"static{index + 1}")
        else:
            if policy is None:
                policy = AutoscalerPolicy(
                    target=1, max_replicas=3, ttft_slo_ms=slo_ttft_ms,
                    breach_windows=2, clear_windows=8,
                    cooldown_s=2.0, spawn_timeout_s=60.0,
                    drain_timeout_s=15.0)
            expected = policy.initial_targets().get("decode", 1)
            autoscaler = compose_instance(
                FleetAutoscaler, actor_args("autoscaler"),
                process=make_process(), spawner=spawner,
                terminator=terminator, policy=policy, tick_s=0.25)
        wait_for(lambda: router.share["replicas"] >= expected, 90,
                 f"router discovery of {expected} replicas")
        generator = LoadGenerator(
            make_process(), f"{router.topic_path}/in",
            payload_fn=_elastic_payloads(
                seed=seed, max_new_tokens=max_new_tokens,
                stream=stream),
            rate_hz=0)
        if warmup:
            # Throwaway compile-warming burst; spacing gives P2C a
            # chance to touch every replica.
            generator.run_trace([0.1 * i for i in range(warmup)],
                                drain_timeout_s=30.0)
        if fault_plan is not None:
            faults.install(fault_plan)
        commands = [(delay_s, f"(scale_target {target})")
                    for delay_s, target in scale_script]
        commands += [(delay_s, command)
                     for delay_s, command in command_script]
        for delay_s, command in (commands if autoscaler is not None
                                 else ()):
            timer = threading.Timer(
                delay_s,
                lambda c=command: autoscaler.process.message.publish(
                    f"{autoscaler.topic_path}/in", c))
            timer.daemon = True
            timer.start()
            timers.append(timer)
        times = diurnal_trace(
            duration_s, base_hz=base_hz, peak_hz=peak_hz,
            period_s=period_s, burst_hz=burst_hz,
            burst_every_s=burst_every_s, burst_len_s=burst_len_s,
            seed=seed)
        replica_seconds_0 = (
            float(autoscaler.share["replica_seconds"])
            if autoscaler is not None else 0.0)
        report = generator.run_trace(times,
                                     drain_timeout_s=drain_timeout_s)
        report.slo_ttft_ms = slo_ttft_ms
        # Stream-consistency audit: for every streamed request the
        # concatenated partials must equal the final token sequence —
        # a drain/kill/re-dispatch that re-streams or drops a token
        # shows up here as a mismatch.
        stream_mismatches = sum(
            1 for request_id, partials in generator.partial_tokens.items()
            if request_id in generator.final_tokens
            and partials != generator.final_tokens[request_id])
        converged = None
        if autoscaler is not None:
            if converge_s:
                want = sum(autoscaler.state.targets.values())

                def settled():
                    return (autoscaler.share["replicas_live"] == want
                            and autoscaler.share["replicas_pending"]
                            == 0
                            and autoscaler.share["replicas_draining"]
                            == 0)

                deadline = time.time() + converge_s
                while not settled() and time.time() < deadline:
                    time.sleep(0.05)
                converged = settled()
            report.replica_seconds = (
                float(autoscaler.share["replica_seconds"])
                - replica_seconds_0)
            report.server_stats = dict(
                autoscaler.stats(),
                router_shed=router.counters["shed"],
                redispatches=router.counters["redispatches"],
                migrations_started=router.counters[
                    "migrations_started"],
                migrations_completed=router.counters[
                    "migrations_completed"],
                migrations_aborted=router.counters[
                    "migrations_aborted"],
                migration_cutover_ms=list(router.migration.cutover_ms),
                stream_mismatches=stream_mismatches,
                faults_fired=(len(fault_plan.fired)
                              if fault_plan is not None else 0))
            if converged is not None:
                report.server_stats["converged"] = converged
        else:
            report.replica_seconds = static_replicas * report.elapsed_s
            report.server_stats = dict(
                replicas_live=router.share["replicas"],
                router_shed=router.counters["shed"],
                redispatches=router.counters["redispatches"],
                stream_mismatches=stream_mismatches)
        report.fleet_latency_ms = fleet_latency(servers)
        return report
    finally:
        if fault_plan is not None:
            faults.uninstall()
        for timer in timers:
            timer.cancel()
        if generator is not None:
            generator.close()
        for process in reversed(processes):
            try:
                process.terminate()
            except Exception:  # noqa: BLE001 - the chaos run may have
                pass           # already killed this process
        engine.terminate()
        thread.join(timeout=5)


def run_elastic_chaos(seed: int = 0, duration_s: float = 8.0,
                      **kwargs) -> LoadReport:
    """The chaos gate for elastic scale-down (ISSUE acceptance): a
    3-replica autoscaled fleet under streaming load gets a scripted
    ``scale_target 2`` (graceful drain) while
    :func:`elastic_chaos_schedule` kills a NON-draining replica and
    fails its replacement's first spawn.  The run must converge to the
    target with ``lost == 0`` and ``duplicate_finals == 0`` — the
    hard invariant of the drain design.  SLO-driven scaling is frozen
    (huge windows) so only the scripted scale-in and self-healing
    move the fleet."""
    from ..orchestration.autoscaler import AutoscalerPolicy

    policy = AutoscalerPolicy(
        target=3, min_replicas=1, max_replicas=4,
        breach_windows=10 ** 6, clear_windows=10 ** 6,
        cooldown_s=3600.0, spawn_timeout_s=60.0,
        drain_timeout_s=10.0, backoff_base_s=0.5,
        crash_loop_threshold=3, crash_loop_window_s=60.0)
    kwargs.setdefault("scale_script", ((max(0.6, duration_s * 0.1),
                                        2),))
    kwargs.setdefault("converge_s", 30.0)
    kwargs.setdefault("stream", True)
    kwargs.setdefault("warmup", 6)
    return run_elastic(duration_s=duration_s, seed=seed,
                       policy=policy,
                       fault_plan=elastic_chaos_schedule(seed),
                       **kwargs)


def migration_chaos_schedule(seed: int, phase: str = "none"):
    """Seeded fault schedule for the live-migration chaos gate — one
    fault class per ``phase`` so a run exercises exactly one migration
    failure point (each phase is a separate loadgen invocation / test
    parametrization):

    * ``transfer``  — ``drop_migration_block``: the source drops the
      last exported KV block; the destination resumes one block colder
      and recomputes the tail (still bit-exact).
    * ``cutover``   — ``stall_cutover``: wedge the router inside the
      double-delivery window, forcing the token-offset dedup to earn
      its keep.
    * ``source``    — ``kill_source_mid_migration``: the source
      replica dies while migrations are in flight; TRANSFER-phase
      requests promote to the destination, earlier phases abort into
      the normal re-dispatch path.
    * ``none``      — no faults: the clean-migration control.
    """
    from ..runtime import faults
    plan = faults.FaultPlan(seed=seed)
    if phase == "transfer":
        plan.add("drop_migration_block", nth=1)
    elif phase == "cutover":
        plan.add("stall_cutover", nth=1, ms=60)
    elif phase == "source":
        plan.add("kill_source_mid_migration", nth=2,
                 match="replica_a")
    elif phase != "none":
        raise ValueError(f"unknown migration chaos phase: {phase}")
    return plan


def run_migration_chaos(seed: int = 0, n_requests: int = 10,
                        rate_hz: float = 60.0,
                        phase: str = "none",
                        migrate_delay_s: float = 0.05,
                        max_new_tokens: int = 48,
                        drain_timeout_s: float = 90.0
                        ) -> Tuple[LoadReport, LoadReport]:
    """Drain-free live-migration chaos gate: an in-process 2-replica
    rig streams requests while a mid-run ``(migrate replica_a)``
    operator command evacuates replica_a's whole in-flight population
    to replica_b mid-decode, under the :func:`migration_chaos_schedule`
    fault ``phase``.  Returns ``(control, migrated)`` where control is
    the identical seeded run WITHOUT the migration.

    The invariants (asserted by tests/test_migration.py and the CLI):
    zero lost, zero hung, zero duplicated finals, zero stream
    mismatches (concatenated partials == final sequence, i.e. the
    double-delivery window deduped exactly), and BIT-EXACT final
    tokens against the unmigrated control for every request both runs
    completed — migration must be invisible to the token stream."""

    from ..orchestration.continuous import ContinuousReplica
    from ..orchestration.paged import PagedContinuousServer
    from ..orchestration.serving import ReplicaRouter
    from ..registry import Registrar
    from ..runtime import (Process, actor_args, compose_instance,
                           faults)
    from ..runtime.event import EventEngine

    import threading

    def wait_for(predicate, timeout_s: float, what: str):
        deadline = time.time() + timeout_s
        while not predicate():
            if time.time() > deadline:
                raise TimeoutError(f"migration rig: {what}")
            time.sleep(0.02)

    def one_run(migrate: bool) -> LoadReport:
        plan = None
        engine = EventEngine()
        thread = engine.run_in_thread()
        broker = f"migrate-{uuid.uuid4().hex[:6]}"
        processes = []

        def make_process(pid):
            process = Process(namespace="migrate", hostname="h",
                              pid=str(pid), engine=engine,
                              broker=broker)
            processes.append(process)
            return process

        generator = None
        timer = None
        try:
            registrar = Registrar(process=make_process(1))
            wait_for(lambda: registrar.state == "primary", 10,
                     "registrar primary")
            replicas = {}
            for index, name in enumerate(("replica_a", "replica_b")):
                # Same config+seed: greedy decode is replica-
                # independent, so a migrated request's destination
                # continues the exact sequence the source started.
                server = PagedContinuousServer(
                    config_name="tiny", slots=4, chunk_steps=2,
                    seed=0, enable_prefix_cache=True, max_queue=256,
                    watchdog_s=5.0)
                replicas[name] = compose_instance(
                    ContinuousReplica, actor_args(name),
                    process=make_process(2 + index), server=server,
                    kv_fetch_timeout_s=2.0)
            router = compose_instance(
                ReplicaRouter, actor_args("router"),
                process=make_process(8), kv_transfer=True)
            wait_for(lambda: router.share["replicas"] == 2, 30,
                     "router discovery")
            generator = LoadGenerator(
                make_process(9), f"{router.topic_path}/in",
                payload_fn=_elastic_payloads(
                    seed=seed, prompt_len=18,
                    max_new_tokens=max_new_tokens, stream=True),
                rate_hz=rate_hz)
            # Warm the decode programs first (both arms identically):
            # the measured wave then runs at steady speed, so the
            # migration trigger really lands mid-decode instead of
            # after a compile-stretched drain.
            generator.run(2, drain_timeout_s=drain_timeout_s)
            if migrate:
                plan = faults.install(migration_chaos_schedule(
                    seed, phase))
                source_topic = replicas["replica_a"].topic_path

                def fire_when_mid_decode():
                    # Deterministic trigger: wait until the source
                    # owns a request that has already streamed at
                    # least one token, then evacuate the source.
                    deadline = time.time() + migrate_delay_s + 30.0
                    time.sleep(migrate_delay_s)
                    while time.time() < deadline:
                        inflight = list(router._inflight.values())
                        if any(entry.get("replica") == source_topic
                               and entry.get("delivered", 0) > 0
                               for entry in inflight):
                            router.process.message.publish(
                                f"{router.topic_path}/in",
                                f"(migrate {source_topic})")
                            return
                        time.sleep(0.002)

                timer = threading.Thread(target=fire_when_mid_decode,
                                         daemon=True)
                timer.start()
            report = generator.run(n_requests,
                                   drain_timeout_s=drain_timeout_s)
            report.final_tokens = dict(generator.final_tokens)
            stream_mismatches = sum(
                1 for request_id, partials
                in generator.partial_tokens.items()
                if request_id in generator.final_tokens
                and partials != generator.final_tokens[request_id])
            report.server_stats = dict(
                router.counters,
                stream_mismatches=stream_mismatches,
                migration_cutover_ms=list(
                    router.migration.cutover_ms),
                faults_fired=(len(plan.fired) if plan else 0),
                replicas_live=router.share["replicas"])
            return report
        finally:
            faults.uninstall()
            if generator is not None:
                generator.close()
            for process in reversed(processes):
                try:
                    process.terminate()
                except Exception:  # noqa: BLE001 - the kill phase may
                    pass           # have taken this process already
            engine.terminate()
            thread.join(timeout=5)

    control = one_run(migrate=False)
    migrated = one_run(migrate=True)
    return control, migrated


def run_rolling_upgrade(duration_s: float = 10.0, seed: int = 0,
                        replicas: int = 4,
                        drain_based: bool = False,
                        **kwargs) -> LoadReport:
    """Zero-downtime rolling upgrade goodput trace: a ``replicas``-
    strong autoscaled fleet under streaming diurnal load receives a
    mid-run ``(rolling_upgrade)`` — every replica is replaced one at a
    time with its in-flight population LIVE-MIGRATED onto the
    successor.  ``drain_based=True`` is the A/B control: the same
    replacement loop but each predecessor drains its tail instead of
    migrating it (``policy.migrate_drains`` off); the CLI's
    ``--rolling-upgrade`` prints both arms' goodput."""
    from ..orchestration.autoscaler import AutoscalerPolicy

    policy = AutoscalerPolicy(
        target=replicas, min_replicas=1, max_replicas=replicas + 2,
        breach_windows=10 ** 6, clear_windows=10 ** 6,
        cooldown_s=3600.0, spawn_timeout_s=60.0,
        drain_timeout_s=20.0,
        migrate_drains=not drain_based)
    kwargs.setdefault("command_script",
                      ((max(0.6, duration_s * 0.15),
                        "(rolling_upgrade)"),))
    kwargs.setdefault("converge_s", 60.0)
    kwargs.setdefault("stream", True)
    kwargs.setdefault("warmup", 6)
    # Dense enough that every replica holds live streams at any
    # instant: each handoff then really carries an in-flight
    # population instead of landing in a gap between requests.
    kwargs.setdefault("base_hz", 8.0)
    kwargs.setdefault("peak_hz", 12.0)
    kwargs.setdefault("max_new_tokens", 48)
    return run_elastic(duration_s=duration_s, seed=seed,
                       policy=policy, **kwargs)


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m aiko_services_tpu.tools.loadgen --chaos`` (seeded
    fault schedule; exit 1 if any request was lost or hung) or
    ``--workload shared_prefix`` (multi-turn shared-system-prompt
    profile through the prefix-aware router)."""
    import argparse

    parser = argparse.ArgumentParser(
        description="Serving load generator (chaos mode: seeded "
                    "fault-injection run asserting zero lost "
                    "requests; shared_prefix workload: multi-turn "
                    "conversations against the prefix-aware router)")
    parser.add_argument("--chaos", action="store_true",
                        help="run the seeded fault schedule against "
                             "an in-process 2-replica rig")
    parser.add_argument("--elastic-chaos", action="store_true",
                        help="run the elastic scale-down chaos gate "
                             "(drain + kill-during-drain + failed "
                             "replacement spawn; exit 1 unless zero "
                             "lost/duplicated and converged)")
    parser.add_argument("--migrate-mid-stream", action="store_true",
                        help="live-migration chaos gate: evacuate one "
                             "replica's in-flight population to the "
                             "other mid-decode under a seeded fault "
                             "phase; exit 1 unless zero lost/"
                             "duplicated/mismatched and bit-exact vs "
                             "the unmigrated control")
    parser.add_argument("--migration-phase", default="none",
                        choices=["none", "transfer", "cutover",
                                 "source"],
                        help="--migrate-mid-stream: which migration "
                             "phase the seeded fault hits")
    parser.add_argument("--rolling-upgrade", action="store_true",
                        help="zero-downtime rolling upgrade trace: "
                             "replace every replica one at a time "
                             "with live migration, vs the drain-based "
                             "control")
    parser.add_argument("--workload",
                        choices=["shared_prefix", "diurnal",
                                 "longtail", "structured",
                                 "multitenant"],
                        help="named workload profile (in-process rig)")
    parser.add_argument("--tenants", type=int, default=4,
                        help="multitenant: distinct LoRA adapters "
                             "(zipf-popular tenants split across two "
                             "replicas)")
    parser.add_argument("--zipf-s", type=float, default=1.2,
                        help="multitenant: zipf exponent of adapter "
                             "popularity (higher = hotter head)")
    parser.add_argument("--draft-mode", default="ngram",
                        choices=["ngram", "model"],
                        help="structured workload: proposer for the "
                             "speculative path (ngram = model-free "
                             "self-drafting)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--requests", type=int, default=40)
    parser.add_argument("--rate-hz", type=float, default=100.0)
    parser.add_argument("--duration", type=float, default=12.0,
                        help="diurnal/elastic: run length (seconds)")
    parser.add_argument("--base-hz", type=float, default=2.0,
                        help="diurnal: valley request rate")
    parser.add_argument("--peak-hz", type=float, default=12.0,
                        help="diurnal: crest request rate")
    parser.add_argument("--period", type=float, default=8.0,
                        help="diurnal: sinusoid period (seconds)")
    parser.add_argument("--slo-ttft-ms", type=float, default=500.0,
                        help="diurnal: TTFT SLO goodput is judged "
                             "against")
    parser.add_argument("--static-replicas", type=int, default=None,
                        help="diurnal: pin a fixed fleet (A/B "
                             "baseline) instead of autoscaling")
    parser.add_argument("--conversations", type=int, default=3,
                        help="shared_prefix: interleaved conversations")
    parser.add_argument("--turns", type=int, default=4,
                        help="shared_prefix: turns per conversation")
    parser.add_argument("--system-len", type=int, default=48,
                        help="shared_prefix: shared system prompt "
                             "tokens")
    parser.add_argument("--no-prefix-routing", action="store_true",
                        help="shared_prefix: disable prefix-aware "
                             "scoring (A/B baseline)")
    parser.add_argument("--prefixes", type=int, default=6,
                        help="longtail: distinct shared prefixes "
                             "(working set = prefixes x prefix-len "
                             "blocks)")
    parser.add_argument("--prefix-len", type=int, default=384,
                        help="longtail: tokens per shared prefix")
    parser.add_argument("--hbm-blocks", type=int, default=52,
                        help="longtail: HBM pool size in blocks "
                             "(deliberately smaller than the prefix "
                             "working set)")
    parser.add_argument("--host-blocks", type=int, default=160,
                        help="longtail: host-RAM tier capacity in "
                             "blocks (0 = tier off, the A/B baseline)")
    parser.add_argument("--tier-off", action="store_true",
                        help="longtail: shorthand for --host-blocks 0")
    parser.add_argument("--disk-blocks", type=int, default=0,
                        help="longtail: SSD spill tier capacity in "
                             "blocks under a fresh temp directory "
                             "(0 = no spill tier); host overflow "
                             "demotes to disk instead of purging")
    parser.add_argument("--restart-replica", action="store_true",
                        help="warm-restart chaos A/B: kill the only "
                             "replica mid-run and respawn it cold vs "
                             "spill-adopting; exit 1 unless greedy "
                             "outputs are bit-exact and the warm arm "
                             "beats cold on hit rate and mean TTFT")
    parser.add_argument("--trace-out", metavar="DIR",
                        help="enable distributed tracing and dump the "
                             "slowest requests' span trees as Chrome "
                             "trace-event JSON (Perfetto-loadable) "
                             "into DIR")
    parser.add_argument("--trace-top", type=int, default=5,
                        help="how many slowest requests --trace-out "
                             "dumps")
    parser.add_argument("--spec-k", type=int, default=0,
                        help="speculative A/B gate: run the seeded "
                             "payload sequence plain AND with a "
                             "k-token paired draft, assert BIT-EXACT "
                             "outputs, report acceptance histograms "
                             "(composes with --chaos: both sides run "
                             "the fault schedule)")
    args = parser.parse_args(argv)
    if args.migrate_mid_stream:
        control, migrated = run_migration_chaos(
            seed=args.seed,
            n_requests=args.requests if args.requests != 40 else 10,
            rate_hz=args.rate_hz, phase=args.migration_phase)
        print("control: ", control)
        print("migrated:", migrated)
        stats = migrated.server_stats
        print(f"router counters: {stats}")
        both = set(control.final_tokens) & set(migrated.final_tokens)
        mismatched = [request_id for request_id in both
                      if control.final_tokens[request_id]
                      != migrated.final_tokens[request_id]]
        ok = (not migrated.lost and not migrated.timeouts
              and not migrated.duplicate_finals
              and not stats.get("stream_mismatches")
              and stats.get("migrations_started", 0) > 0
              and not mismatched and both)
        if not ok:
            print(f"MIGRATION CHAOS FAIL (seed={args.seed}, "
                  f"phase={args.migration_phase}): {migrated.lost} "
                  f"lost, {migrated.timeouts} hung, "
                  f"{migrated.duplicate_finals} duplicated, "
                  f"{stats.get('stream_mismatches')} stream "
                  f"mismatches, {len(mismatched)} diverged vs "
                  f"control")
            return 1
        cutovers = stats.get("migration_cutover_ms", [])
        print(f"MIGRATION CHAOS OK (seed={args.seed}, "
              f"phase={args.migration_phase}): "
              f"{stats.get('migrations_completed')} migrated / "
              f"{stats.get('migrations_aborted')} aborted, "
              f"{len(cutovers)} cutovers, bit-exact vs control")
        return 0
    if args.rolling_upgrade:
        migrated = run_rolling_upgrade(duration_s=args.duration,
                                       seed=args.seed)
        drained = run_rolling_upgrade(duration_s=args.duration,
                                      seed=args.seed,
                                      drain_based=True)
        for label, report in (("live-migrated", migrated),
                              ("drain-based ", drained)):
            stats = report.server_stats
            print(f"{label}: goodput={report.goodput_rps:.2f} req/s, "
                  f"upgrades={stats.get('upgrades_completed')}, "
                  f"migrations={stats.get('migrations_completed')}, "
                  f"lost={report.lost}")
        stats = migrated.server_stats
        ok = (not migrated.lost and not migrated.timeouts
              and not migrated.duplicate_finals
              and not stats.get("stream_mismatches")
              and stats.get("upgrades_completed", 0) > 0
              and stats.get("converged"))
        if not ok:
            print(f"ROLLING UPGRADE FAIL (seed={args.seed}): "
                  f"{migrated.lost} lost, {migrated.timeouts} hung, "
                  f"{migrated.duplicate_finals} duplicated, "
                  f"converged={stats.get('converged')}")
            return 1
        print(f"ROLLING UPGRADE OK (seed={args.seed}): fleet "
              f"replaced with zero lost/duplicated tokens")
        return 0
    if args.workload == "structured":
        cons, free = run_structured(
            n_requests=args.requests, rate_hz=args.rate_hz,
            spec_k=args.spec_k or 4, draft_mode=args.draft_mode,
            chaos=args.chaos, seed=args.seed)
        print("constrained:  ", cons)
        print("unconstrained:", free)
        stats = cons.spec_stats or {}
        cons_tps = (cons.tokens_total / cons.elapsed_s
                    if cons.elapsed_s else 0.0)
        free_tps = (free.tokens_total / free.elapsed_s
                    if free.elapsed_s else 0.0)
        print(f"fleet spec counters: {stats}")
        print(f"goodput A/B: constrained {cons_tps:.1f} tok/s "
              f"({stats.get('spec_jump_forward_tokens', 0)} "
              f"jump-forward tok) vs unconstrained {free_tps:.1f} "
              f"tok/s")
        failed = (cons.lost or cons.timeouts or free.lost
                  or free.timeouts
                  or (args.chaos and (cons.duplicate_finals
                                      or free.duplicate_finals)))
        if failed:
            print(f"STRUCTURED FAIL (seed={args.seed}): "
                  f"{cons.lost}+{free.lost} lost, "
                  f"{cons.timeouts}+{free.timeouts} hung, "
                  f"{cons.duplicate_finals}+{free.duplicate_finals} "
                  f"duplicated")
            return 1
        mode = "chaos" if args.chaos else "steady"
        print(f"STRUCTURED OK ({mode}, seed={args.seed}): all "
              f"constrained finals grammatical, "
              f"{stats.get('spec_jump_forward_tokens', 0)} skeleton "
              f"tokens jump-forwarded")
        return 0
    if args.spec_k:
        base, spec = run_spec_ab(
            spec_k=args.spec_k, n_requests=args.requests,
            rate_hz=args.rate_hz, seed=args.seed, chaos=args.chaos)
        print("base:", base)
        print("spec:", spec)
        print(f"fleet spec counters: {spec.spec_stats}")
        lengths = sorted(len(hist) for hist
                         in spec.spec_accept_hist.values())
        accepted = [count for hist in spec.spec_accept_hist.values()
                    for count in hist]
        mean_accept = (statistics.fmean(accepted) if accepted else 0.0)
        print(f"accept histograms: {len(lengths)} requests, "
              f"rounds/request p50="
              f"{lengths[len(lengths) // 2] if lengths else 0}, "
              f"mean accepted/round={mean_accept:.2f}")
        if args.chaos and (spec.lost or spec.timeouts
                           or spec.duplicate_finals):
            print(f"SPEC CHAOS FAIL (seed={args.seed}): "
                  f"{spec.lost} lost, {spec.timeouts} hung, "
                  f"{spec.duplicate_finals} duplicated")
            return 1
        mode = "chaos" if args.chaos else "shared_prefix"
        print(f"SPEC A/B OK (k={args.spec_k}, {mode}, "
              f"seed={args.seed}): bit-exact, "
              f"tokens/target-pass="
              f"{(spec.spec_stats or {}).get('spec_tokens_per_target_pass')}")
        return 0
    if args.elastic_chaos:
        report = run_elastic_chaos(seed=args.seed,
                                   duration_s=args.duration,
                                   base_hz=args.base_hz,
                                   peak_hz=args.peak_hz,
                                   period_s=args.period)
        print(report)
        print(f"autoscaler: {report.server_stats}")
        ok = (not report.lost and not report.timeouts
              and not report.duplicate_finals
              and not report.server_stats.get("stream_mismatches")
              and report.server_stats.get("converged"))
        if not ok:
            print(f"ELASTIC CHAOS FAIL (seed={args.seed}): "
                  f"{report.lost} lost, {report.timeouts} hung, "
                  f"{report.duplicate_finals} duplicated, "
                  f"{report.server_stats.get('stream_mismatches')} "
                  f"stream mismatches, "
                  f"converged={report.server_stats.get('converged')}")
            return 1
        print(f"ELASTIC CHAOS OK (seed={args.seed}): drain + kill + "
              f"failed respawn, nothing lost, fleet converged")
        return 0
    if args.workload == "diurnal":
        report = run_elastic(duration_s=args.duration, seed=args.seed,
                             base_hz=args.base_hz,
                             peak_hz=args.peak_hz,
                             period_s=args.period,
                             slo_ttft_ms=args.slo_ttft_ms,
                             static_replicas=args.static_replicas)
        print(report)
        print(f"fleet: {report.server_stats}")
        print(f"goodput {report.goodput_rps:.2f} req/s over avg "
              f"{report.avg_replicas:.2f} replicas = "
              f"{report.goodput_per_replica:.2f} req/s/replica")
        return 1 if (report.lost or report.timeouts) else 0
    if args.restart_replica:
        cold, warm = run_restart_ab(n_requests=args.requests
                                    if args.requests != 40 else 18,
                                    seed=args.seed)
        for label, report in (("cold", cold), ("warm", warm)):
            stats = report.server_stats or {}
            mean_ttft = (statistics.fmean(report.ttfts_ms)
                         if report.ttfts_ms else 0.0)
            print(f"{label}: hit_rate={report.prefix_hit_rate}, "
                  f"mean TTFT={mean_ttft:.1f}ms, "
                  f"recovery={stats.get('restart_recovery_ms')}ms, "
                  f"batch hit rates="
                  f"{stats.get('restart_hit_rates')}, "
                  f"adopted={stats.get('kv_adopted_chains')}, "
                  f"disk restores={stats.get('kv_disk_restores')}")
        cold_ttft = statistics.fmean(cold.ttfts_ms or [0.0])
        warm_ttft = statistics.fmean(warm.ttfts_ms or [0.0])
        ok = (not cold.lost and not warm.lost
              and not cold.timeouts and not warm.timeouts
              and (warm.prefix_hit_rate or 0.0)
              > (cold.prefix_hit_rate or 0.0)
              and warm_ttft < cold_ttft)
        if not ok:
            print(f"RESTART A/B FAIL (seed={args.seed}): warm must "
                  f"beat cold on hit rate and mean TTFT with zero "
                  f"lost")
            return 1
        print(f"RESTART A/B OK (seed={args.seed}): bit-exact, warm "
              f"restart adopted the spill tier and recovered first")
        return 0
    if args.workload == "longtail":
        import contextlib
        import tempfile

        host_blocks = 0 if args.tier_off else args.host_blocks
        with contextlib.ExitStack() as stack:
            spill_dir = None
            if args.disk_blocks:
                spill_dir = os.path.join(stack.enter_context(
                    tempfile.TemporaryDirectory(prefix="kvspill-")),
                    "spill")
            report = run_longtail(
                n_requests=args.requests, rate_hz=args.rate_hz,
                n_prefixes=args.prefixes, prefix_len=args.prefix_len,
                total_blocks=args.hbm_blocks,
                host_tier_blocks=host_blocks, seed=args.seed,
                spill_dir=spill_dir,
                spill_blocks=args.disk_blocks or 1024)
        print(report)
        print(report.phase_table())
        print(f"fleet counters: {report.server_stats}")
        tier = f"host tier {host_blocks} blocks" if host_blocks \
            else "host tier OFF"
        if args.disk_blocks:
            tier += f" + disk tier {args.disk_blocks} blocks"
        mean_ttft = (statistics.fmean(report.ttfts_ms)
                     if report.ttfts_ms else 0.0)
        print(f"longtail ({args.prefixes} prefixes x "
              f"{args.prefix_len} tok over {args.hbm_blocks} HBM "
              f"blocks, {tier}): "
              f"prefix_hit_rate={report.prefix_hit_rate}, "
              f"host share={report.prefix_hit_rate_host}, "
              f"mean TTFT={mean_ttft:.1f}ms")
        return 1 if (report.lost or report.timeouts) else 0
    if args.workload == "multitenant":
        aware = run_multitenant(
            n_requests=args.requests, rate_hz=args.rate_hz,
            n_adapters=args.tenants, zipf_s=args.zipf_s,
            adapter_aware=True, seed=args.seed)
        blind = run_multitenant(
            n_requests=args.requests, rate_hz=args.rate_hz,
            n_adapters=args.tenants, zipf_s=args.zipf_s,
            adapter_aware=False, seed=args.seed)
        print("adapter-aware:", aware)
        print("adapter-blind:", blind)
        print(f"fleet counters (aware): {aware.server_stats}")
        print(f"warm-routing A/B ({args.tenants} tenants, zipf "
              f"s={args.zipf_s}): aware {aware.adapter_cold_starts} "
              f"cold starts ({aware.adapter_warm_routes} warm "
              f"routes) vs blind {blind.adapter_cold_starts} cold "
              f"starts")
        failed = (aware.adapter_cold_starts or aware.lost
                  or aware.timeouts
                  or aware.adapter_warm_routes < aware.completed
                  or blind.adapter_cold_starts == 0)
        if failed:
            print(f"MULTITENANT FAIL (seed={args.seed}): aware arm "
                  f"{aware.adapter_cold_starts} cold starts / "
                  f"{aware.lost} lost / {aware.timeouts} hung; blind "
                  f"arm {blind.adapter_cold_starts} cold starts "
                  f"(expected > 0)")
            return 1
        print(f"MULTITENANT OK (seed={args.seed}): every warm "
              f"adapter routed warm; adapter-blind baseline paid "
              f"{blind.adapter_cold_starts} re-uploads")
        return 0
    if args.workload == "shared_prefix":
        report = run_shared_prefix(
            n_requests=args.requests, rate_hz=args.rate_hz,
            n_conversations=args.conversations, turns=args.turns,
            system_len=args.system_len,
            prefix_routing=not args.no_prefix_routing,
            seed=args.seed, trace_out=args.trace_out,
            trace_top=args.trace_top)
        print(report)
        print(report.phase_table())
        if report.fleet_latency_ms:
            print(f"fleet latency (merged histograms): "
                  f"{report.fleet_latency_ms}")
        print(f"fleet counters: {report.server_stats}")
        if args.trace_out:
            print(f"trace-event JSON for the {args.trace_top} slowest "
                  f"requests written to {args.trace_out}")
        return 1 if (report.lost or report.timeouts) else 0
    if not args.chaos:
        parser.error("API runs use LoadGenerator directly; the CLI "
                     "wires --chaos and --workload shared_prefix")
    report = run_chaos(seed=args.seed, n_requests=args.requests,
                       rate_hz=args.rate_hz)
    print(report)
    print(report.phase_table())
    print(f"router counters: {report.server_stats}")
    if report.lost or report.timeouts:
        print(f"CHAOS FAIL (seed={args.seed}): {report.lost} lost, "
              f"{report.timeouts} hung")
        return 1
    print(f"CHAOS OK (seed={args.seed}): {report.sent}/{report.sent} "
          "requests terminal under kill + drop + stall schedule")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
