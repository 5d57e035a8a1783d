#!/usr/bin/env python3
"""Runs ONE cell of BENCHMARK.json once, as a process that holds the
chip, and prints the result line of the benchmark's contract.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Set-up (counted in ``setup_s``): weights from the seed on the device,
the paged server behind a ``ContinuousReplica`` on a real event-engine
thread, an ``InferClient`` over the loopback transport, the warm-up
scenes of the traffic mix, and the mix's ramp.  Then the window: load
from the mix at its fixed rate or client count for ``--seconds``,
timed from the client's side.  After the window the run drains the
requests that were due in it, frees the program's state, and checks a
seeded sample of what was served against the plain reference.

Without a TPU (or with fewer chips than the cell asks) it exits 2 and
prints no result.  ``--rehearsal`` is the explicit CPU run of the test
suite: interpreted kernels, and a result line whose device says
``cpu``; it is never chosen for the caller.

``--control-bits 4`` is the lower-precision control of "How correct is
decided": the served weights are coarsened to 4 bits inside their int8
containers (same programs, same speed) while the reference keeps the
stated 8; such a run must print ``"correct": false``.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import (cells, check, layers, stats,  # noqa: E402
                       traffic as traffic_mod)

#: Polling period of the open-loop load generator (seconds).
TICK = 0.001
#: How often a sleeping closed-loop generator looks whether the event
#: loop is still alive (seconds).
ALIVE_S = 0.25
WARM_TIMEOUT_S = 1100.0


def log(message: str):
    print(f"[{time.monotonic() - PROCESS_START:8.2f}] {message}",
          flush=True)


class Record:
    """One request, as the client saw it."""

    __slots__ = ("request", "due", "sent", "first", "last", "n_first",
                 "future")

    def __init__(self, request, due):
        self.request, self.due = request, due
        self.sent = self.first = self.last = None
        self.n_first = 0
        self.future = None

    def on_partial(self, increment, arrivals):
        now = time.monotonic()
        if self.first is None:
            self.first, self.n_first = now, len(increment)
        self.last = now
        arrivals.append((now, len(increment)))


class Served:
    """The system under test: server, replica, client, engine thread."""

    def __init__(self, cell, params, program_name):
        import uuid

        from aiko_services_tpu.orchestration.client import InferClient
        from aiko_services_tpu.orchestration.continuous import (
            ContinuousReplica)
        from aiko_services_tpu.orchestration.paged import (
            PagedContinuousServer)
        from aiko_services_tpu.runtime import (Process, actor_args,
                                               compose_instance)
        from aiko_services_tpu.runtime.event import EventEngine

        serving = cell.config["serving"]
        mix = cell.traffic
        self.server = PagedContinuousServer(
            config_name=program_name, slots=mix["slots"],
            max_seq=mix["max_seq"], chunk_steps=serving["chunk_steps"],
            quantize=True, quantize_kv=serving["kv_dtype"] == "int8",
            params=params, block_size=serving["block_size"],
            total_blocks=serving["pool_blocks"],
            enable_prefix_cache=serving["prefix_cache"])
        self.loop_errors = []
        threading.excepthook = lambda args: self.loop_errors.append(
            "".join(traceback.format_exception(
                args.exc_type, args.exc_value, args.exc_traceback)))
        self.engine = EventEngine()
        self.thread = self.engine.run_in_thread()
        broker = f"bench-{uuid.uuid4().hex[:6]}"
        self.processes = [
            Process(namespace="bench", hostname="chip", pid=str(pid),
                    engine=self.engine, broker=broker)
            for pid in (2, 9)]
        self.replica = compose_instance(
            ContinuousReplica, actor_args("replica"),
            process=self.processes[0], server=self.server)
        self.client = InferClient(self.processes[1],
                                  self.replica.topic_in)

    def alive(self):
        if self.loop_errors or not self.thread.is_alive():
            raise RuntimeError("event loop died:\n"
                               + "\n".join(self.loop_errors))

    def send(self, record, arrivals):
        record.sent = time.monotonic()
        record.future = self.client.submit(
            record.request.prompt, max_new_tokens=record.request.max_new,
            stream=True,
            on_partial=lambda inc, r=record: r.on_partial(inc, arrivals))

    def counters(self):
        """Program counters the per-layer readers use, as one dict."""
        out = dict(self.server.counters)
        for name in ("prefix_hits", "prefix_misses",
                     "prefix_blocks_reused", "prefix_evictions"):
            out[name] = getattr(self.server, name)
        return out

    def stop(self):
        for process in reversed(self.processes):
            process.terminate()
        self.engine.terminate()
        self.thread.join(timeout=10)
        return not self.thread.is_alive()

    def free(self):
        """Drop the program's device state so that the reference has
        the chip (its peak is read before this)."""
        import jax
        server = self.server
        for tree in (server.params, server.pool, server._state):
            for leaf in jax.tree_util.tree_leaves(tree):
                if hasattr(leaf, "delete"):
                    leaf.delete()
        self.server = self.replica.server = None


def warm_up(served, mix, vocab, seed):
    """The mix's warm-up scenes: every program shape its traffic can
    reach is run once, from the cache after a checkout's first run.

    A scene is ``{"prompt": n, "output": k, "when": "idle"|"decoding",
    "shares": [scene, tokens]}``: ``idle`` scenes wait for everything
    before them to finish (their prefill runs standalone), ``decoding``
    scenes wait until an earlier scene is streaming tokens (their
    prefill rides the mixed prefill+decode program).  ``shares`` takes
    its first ``tokens`` tokens from an earlier scene's prompt, which
    the prefix cache then holds, so the slice widths behind a hit at
    that offset compile too.  ``ladder_buckets`` has the program walk
    its own prefill ladder for those prompt buckets first.  Last, the
    dirty-row upload of every power-of-two row count the slots
    allow."""
    import numpy as np
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
    arrivals, records = [], []
    deadline = time.monotonic() + WARM_TIMEOUT_S

    def wait(condition, what):
        while not condition():
            served.alive()
            if time.monotonic() > deadline:
                raise TimeoutError(f"warm-up: {what}")
            time.sleep(0.005)

    buckets = mix["warm"].get("ladder_buckets")
    if buckets:
        # Whole-bucket admission behind a prefix hit runs the uncached
        # tail in power-of-two pieces: the program's own ladder walk
        # compiles every piece width of these buckets.
        served.server.warm_prefill_ladder(buckets=buckets)
    for scene in mix["warm"]["scenes"]:
        prompt = rng.integers(1, vocab, scene["prompt"]).astype(np.int32)
        if "shares" in scene:
            source, count = scene["shares"]
            prompt[:count] = records[source].request.prompt[:count]
        if scene["when"] == "idle":
            wait(lambda: all(r.future.done for r in records),
                 "earlier scenes to finish")
        else:
            wait(lambda: any(r.first is not None and not r.future.done
                             for r in records),
                 "an earlier scene to be decoding")
        record = Record(traffic_mod.Request(len(records), prompt,
                                            scene["output"]), 0.0)
        served.send(record, arrivals)
        records.append(record)
        log(f"warm-up scene {len(records)} sent: {scene}")
    wait(lambda: all(r.future.done for r in records), "scenes to finish")
    log("warm-up scenes done")
    errors = [r.future.error for r in records if r.future.error]
    if errors:
        raise RuntimeError(f"warm-up requests failed: {errors}")
    # Idle now: mark 1, 2, 4, ... slots dirty and upload.  The mirrors
    # equal the resident state, so the scatter changes nothing.
    server = served.server
    rows = 1
    while rows <= server.slots:
        server._dirty[:rows] = True
        server._sync_dirty()
        rows *= 2
    import jax
    jax.block_until_ready(server._state)


def offer_load(served, cell, mix_stream, seconds, tracer):
    """Ramp, window and drain.  Returns (records, arrivals, (t0, t1),
    marks)."""
    mix = cell.traffic
    source = mix_stream.requests()
    records, arrivals = [], []
    start = time.monotonic()
    t0 = start + mix["ramp_s"]
    t1 = t0 + seconds
    marks = {}
    tracer.start(t0)
    offer = closed_loop if mix["loop"] == "closed" else open_loop
    offer(served, mix, source, records, arrivals, tracer, marks,
          (start, t0, t1))
    tracer.finish()
    return records, arrivals, (t0, t1), marks


def open_loop(served, mix, source, records, arrivals, tracer, marks,
              times):
    """Arrivals on the mix's schedule: the generator wakes every
    ``TICK`` (what ``gen_late_p90_ms`` reads) and sends what is due."""
    start, t0, t1 = times
    drain = mix["drain_s"]
    # Nothing reads ``inflight`` since the closed loop left this
    # function; its walk stays, as part of what a tick costs.
    inflight = []

    def launch(request, due):
        record = Record(request, due)
        records.append(record)
        served.send(record, arrivals)
        inflight.append(record)

    upcoming = next(source)
    while True:
        now = time.monotonic()
        if "t0" not in marks and now >= t0:
            marks["t0"] = served.counters()
            marks["ledger0"] = tracer.ledger()
        if "t1" not in marks and now >= t1:
            marks["t1"] = served.counters()
            marks["ledger1"] = tracer.ledger()
        for record in [r for r in inflight if r.future.done]:
            inflight.remove(record)
        while start + upcoming.due <= now:
            if now < t1 + drain:
                launch(upcoming, start + upcoming.due)
            upcoming = next(source)
        if now >= t1:
            pending = [r for r in records
                       if t0 <= r.due < t1 and not r.future.done]
            if not pending or now >= t1 + drain:
                break
        served.alive()
        time.sleep(max(0.0, min(TICK, start + upcoming.due
                                - time.monotonic())))


def closed_loop(served, mix, source, records, arrivals, tracer, marks,
                times):
    """``clients`` callers, each sending its next request the moment
    its last completes: the launch runs FROM the completion, on the
    thread that delivered the response, so nothing polls.  This
    thread only sleeps to the window's edges to take the marks, and
    then waits for the window's requests to finish."""
    _, t0, t1 = times
    stop = t1 + mix["drain_s"]
    finished = threading.Event()

    def launch():
        record = Record(next(source), time.monotonic())
        records.append(record)
        served.send(record, arrivals)
        future = record.future
        future._event = Completion(future._event, completed)
        if future.done:
            # Resolved between the send and the line above.
            future._event.set()

    def completed():
        try:
            if time.monotonic() < stop:
                launch()
        except Exception:  # noqa: BLE001 - alive() must hear of it
            served.loop_errors.append(traceback.format_exc())
        finished.set()

    def sleep_until(moment):
        while (left := moment - time.monotonic()) > 0:
            served.alive()
            time.sleep(min(left, ALIVE_S))

    for _ in range(mix["clients"]):
        launch()
    sleep_until(t0)
    marks["t0"] = served.counters()
    marks["ledger0"] = tracer.ledger()
    sleep_until(t1)
    marks["t1"] = served.counters()
    marks["ledger1"] = tracer.ledger()
    while time.monotonic() < stop:
        finished.clear()
        if all(r.future.done for r in list(records)
               if t0 <= r.due < t1):
            break
        served.alive()
        finished.wait(ALIVE_S)
    stop = 0.0      # the callers still out send nothing more


class Completion:
    """Stands in for the event an ``InferFuture`` sets when it
    resolves: setting it also tells the caller, once, on the thread
    that resolved the future (the client has no done-callback)."""

    def __init__(self, event, callback):
        self.event, self.callback = event, callback

    def set(self):
        self.event.set()
        callback, self.callback = self.callback, None
        if callback is not None:
            callback()

    def __getattr__(self, name):
        return getattr(self.event, name)


class Tracer:
    """The profiler bracket of a ``--trace 1`` run: ``trace_s`` seconds
    starting ``trace_after_s`` into the window.  While it is on, the
    program's step recorder (``obs/steplog``) is installed, and a
    ``TraceAnnotation`` marker stamped with the host's clock lets the
    reduction put the recorder's events on the profiler's clock."""

    MARKER = "bench_clock_marker"

    def __init__(self, enabled, mix, out_dir, counters):
        self.after = mix.get("trace_after_s", 2.0)
        self.length = mix.get("trace_s", 3.0)
        self.out_dir = str(out_dir)
        self.counters = counters
        self.state = "armed" if enabled else "off"
        self.span = None
        self.marks = {}
        self.marker_unix_s = None
        self.steps = []

    def ledger(self):
        from aiko_services_tpu.obs import compiles
        return compiles.LEDGER.snapshot()

    def start(self, t0):
        """Runs the bracket on a thread of its own: starting and
        stopping the profiler takes seconds, which the load generator
        must not spend."""
        if self.state == "armed":
            self.thread = threading.Thread(target=self._bracket,
                                           args=(t0,), daemon=True)
            self.thread.start()

    def _bracket(self, t0):
        import jax
        from aiko_services_tpu.obs import steplog
        time.sleep(max(0.0, t0 + self.after - time.monotonic()))
        # No Python-function events: they slow the host that is being
        # measured and swell the trace; TraceAnnotations still land.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(self.MARKER):
            self.marker_unix_s = time.time()
            time.sleep(0.001)
        steplog.install(capacity=1 << 16)
        self.marks["begin"] = self.counters()
        began = time.monotonic()
        time.sleep(self.length)
        self.marks["end"] = self.counters()
        self.steps = steplog.RECORDER.events()
        steplog.uninstall()
        self.span = (began, time.monotonic())
        jax.profiler.stop_trace()
        self.state = "done"

    def finish(self):
        if self.state != "off":
            self.thread.join()


def end_to_end(records, arrivals, window, setup_s):
    t0, t1 = window
    due = [r for r in records if t0 <= r.due < t1]
    good = [r for r in due if r.future.done and r.future.error is None
            and r.first is not None]
    ttft = [(r.first - r.due) * 1e3 for r in good]
    total = [(r.last - r.due) * 1e3 for r in good]
    tpot = [(r.last - r.first) * 1e3 / (len(r.future.tokens) - r.n_first)
            for r in good if len(r.future.tokens) > r.n_first]
    delivered = sum(n for at, n in arrivals if t0 <= at < t1)
    values = {
        "ttft_p50_ms": stats.quantile(ttft, 0.50),
        "tpot_p50_ms": stats.quantile(tpot, 0.50),
        "req_p50_ms": stats.quantile(total, 0.50),
        "out_tokens_per_s": delivered / (t1 - t0),
        "setup_s": setup_s,
    }
    return values, due, good


def request_lines(due, window):
    """One line per request due in the window, as the client saw it:
    what a reader of a run's log needs to see WHICH request moved when
    a median did (the result line carries only the medians)."""
    t0 = window[0]
    for r in due:
        seen = "unfinished" if r.first is None or r.last is None else (
            f"ttft {(r.first - r.due) * 1e3:.1f} ms, last token "
            f"{(r.last - r.due) * 1e3:.1f} ms after it was due")
        yield (f"request: #{r.request.index} due {r.due - t0:.3f} s into "
               f"the window, prompt {len(r.request.prompt)}, answer "
               f"{r.request.max_new}: {seen}")


def sweep(served, cell, vocab, args, tracer) -> int:
    """Windows at several fixed rates, to find the highest the system
    sustains; defines a cell's rate once, judges nothing."""
    for rate in (float(r) for r in args.rate.split(",")):
        cell.traffic["rate_per_s"] = rate
        mix_stream = traffic_mod.Mix(cell.traffic, vocab, args.seed,
                                     args.seconds)
        records, arrivals, window, _ = offer_load(
            served, cell, mix_stream, args.seconds, tracer)
        values, due, good = end_to_end(records, arrivals, window, 0.0)
        late = [r for r in records if not r.future.done]
        print(f"sweep: rate {rate}/s: due {len(due)} finished "
              f"{len(good)} still running at the end {len(late)}; "
              + json.dumps({k: round(v, 2) for k, v in values.items()
                            if v}), flush=True)
        deadline = time.monotonic() + 120
        while any(not r.future.done for r in records) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
    return 0


def readings(cell, args, program_name, program_config) -> int:
    """The readings a limit is set from ("How correct is decided",
    steps 4 and 5), many seeds in ONE process because set-up is long:
    for each ``seed:bits`` a fresh server, the warm-up, a short window
    at the cell's own load, and the comparison.  Prints one line per
    seed and no result line."""
    for item in args.readings.split(","):
        seed, bits = (int(part) for part in item.split(":"))
        params = cell.builder.build_params(cell.config, seed, bits)
        served = Served(cell, params, program_name)
        del params
        tracer = Tracer(False, cell.traffic, "", served.counters)
        try:
            warm_up(served, cell.traffic, program_config.vocab_size, seed)
            mix_stream = traffic_mod.Mix(
                cell.traffic, program_config.vocab_size, seed,
                args.seconds)
            records, arrivals, window, _ = offer_load(
                served, cell, mix_stream, args.seconds, tracer)
        finally:
            served.stop()
        served.free()
        _, due, good = end_to_end(records, arrivals, window, 0.0)
        verdicts = check.served_against_reference(cell, good, seed)
        print(f"reading: seed {seed} bits {bits}: due {len(due)} "
              f"finished {len(good)}; "
              + "; ".join(f"{what.split(' of ')[0]} {value:.5f}"
                          for _, what, value, _ in verdicts), flush=True)
    return 0


def run_cell(args) -> int:
    cell = cells.Cell(ROOT, args.benchmark, args.workload)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["AIKO_DECODE_ATTENTION"] = "interpret"
        os.environ["AIKO_PREFILL_ATTENTION"] = "interpret"
    try:
        from aiko_services_tpu.obs import compiles
    except ImportError as error:
        print(f"benchmark: the program is not in this checkout: {error}")
        return 2
    cache_dir = compiles.entry_point_cache()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"cell {cell.name}: platform={device['platform']} "
        f"kind={device['kind']!r} count={device['count']} "
        f"jax={jax.__version__} cache={cache_dir}")
    if args.rehearsal:
        log("REHEARSAL on the CPU with interpreted kernels: no number "
            "of this run is a device number")
    elif device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {device['count']} x {device['platform']} - "
              "not run", flush=True)
        return 2
    compiles.install(service="benchmark")

    program_name = f"bench_{cell.config_name}"
    program_config = cell.builder.program_config(program_name,
                                                 cell.config)
    if args.readings:
        return readings(cell, args, program_name, program_config)
    params = cell.builder.build_params(cell.config, args.seed,
                                       args.control_bits)
    jax.block_until_ready(params)
    log(f"weights on the device ({args.control_bits}-bit draws)")
    served = Served(cell, params, program_name)
    del params
    log("server, replica and client are up")
    tracer = Tracer(bool(args.trace), cell.traffic,
                    ROOT / "chiprun_out" / "trace" / cell.name,
                    served.counters)
    stopped = True
    try:
        warm_up(served, cell.traffic, program_config.vocab_size,
                args.seed)
        ledger = compiles.LEDGER.snapshot()
        log(f"warm: compiles {ledger['compiles']} cache hits "
            f"{ledger['cache_hits']} misses {ledger['cache_misses']}; "
            "backend compile or cache load took "
            f"{sum(e['wall_ms'] for e in ledger['records']) / 1e3:.1f} s "
            f"over {len(ledger['records'])} programs")
        if args.rate:
            return sweep(served, cell, program_config.vocab_size, args,
                         tracer)
        mix_stream = traffic_mod.Mix(cell.traffic,
                                     program_config.vocab_size, args.seed,
                                     args.seconds)
        load_began = time.monotonic()
        setup_s = load_began + cell.traffic["ramp_s"] - PROCESS_START
        records, arrivals, window, marks = offer_load(
            served, cell, mix_stream, args.seconds, tracer)
        log(f"window closed; {len(records)} requests sent in all")
        final_stats = served.server.stats()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell.chips])
    finally:
        stopped = served.stop()
    served.free()

    values, due, good = end_to_end(records, arrivals, window, setup_s)
    for line in request_lines(due, window):
        print(line, flush=True)
    # A program loaded from the persistent cache inside the window is
    # as much a hole in the warm-up as one compiled there.
    window_compiles = sum(marks["ledger1"][key] - marks["ledger0"][key]
                          for key in ("compiles", "cache_hits"))
    paths = (final_stats["decode_attention_path"],
             final_stats["prefill_attention_path"])
    streamed_ok = all(list(r.future.partial_tokens) == r.future.tokens
                      and len(r.future.tokens) == r.request.max_new
                      for r in good)
    failed = len(due) - len(good)
    verdicts = [
        ("failed", "requests due in the window that failed or did not "
         "finish", failed, 0),
        ("window_compiles", "compiles inside the window",
         window_compiles, 0),
        ("partials_differ", "streamed partials equal final tokens, full "
         "length", int(not streamed_ok), 0),
        ("loop_left_running", "event loop stopped cleanly",
         int(not stopped), 0),
    ]
    if not args.rehearsal:
        verdicts.append(("paths_not_kernel", "attention paths not "
                         "'kernel'", sum(p != "kernel" for p in paths),
                         0))
    log(f"checking {len(good)} served requests against the reference")
    check_began = time.monotonic()
    verdicts += check.served_against_reference(cell, good, args.seed)
    log(f"reference check took {time.monotonic() - check_began:.1f} s")
    correct = True
    for _, what, value, limit in verdicts:
        ok = value <= limit
        correct &= ok
        print(f"check: {what}: {value} (limit {limit}) "
              f"{'ok' if ok else 'FAIL'}", flush=True)
    if window_compiles:
        for entry in compiles.LEDGER.snapshot()["records"][-8:]:
            print(f"check: compiled late: {entry}", flush=True)

    device["memory_peak_bytes"] = int(peak)
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": failed, "device": device}
    if args.trace:
        run = layers.RunView(cell, records, arrivals, window, marks,
                            tracer, device, final_stats)
        result["metrics"] = run.per_layer_metrics()
        device.update(run.device_times())
        breakdown = run.breakdown()
        if breakdown:
            result["breakdown"] = breakdown
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {name: {"value": values[name],
                                    "unit": units[name]}
                             for name in units if values.get(name)
                             is not None}
    # Each number compared beside its limit: last in the line, and
    # the last lines of standard error.
    result["checks"] = {key: {"value": value, "limit": limit}
                        for key, _, value, limit in verdicts}
    print(json.dumps(result), flush=True)
    for key, _, value, limit in verdicts:
        print(f"check {key}: {value} (limit {limit})", file=sys.stderr,
              flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, default=0, choices=(0, 1))
    parser.add_argument("--benchmark", default="BENCHMARK.json",
                        help="benchmark file, relative to the checkout")
    parser.add_argument("--rehearsal", action="store_true",
                        help="CPU, interpreted kernels (tests only)")
    parser.add_argument("--rate", default=None,
                        help="open-loop rates, comma-separated: the "
                             "one-off sweep for the knee.  One window "
                             "per rate in this one process; prints a "
                             "line per rate and no result line")
    parser.add_argument("--readings", default=None,
                        help="seed:bits,seed:bits,...: compare many "
                             "seeds (bits 8) and controls (bits 4) in "
                             "one process; prints readings only")
    parser.add_argument("--control-bits", type=int, default=8,
                        help="coarsen the served weights (the control)")
    args = parser.parse_args(argv)
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
