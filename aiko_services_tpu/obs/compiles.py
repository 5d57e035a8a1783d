"""Compile ledger: every XLA compilation, observed and attributed.

The whole serving stack leans on one unmeasured invariant: pow2 shape
bucketing keeps compile counts log-bounded, because a compile in the
serving loop stalls every slot for seconds
(``orchestration/continuous.py`` prefill loop,
``kvstore/transfer.py``).  This module makes that invariant observable
at runtime instead of only in jaxpr tests:

* :class:`CompileLedger` subscribes to ``jax.monitoring`` compilation
  events and records every program that reaches the backend — program
  label, shape-bucket signature, the jitted function's name, and each
  host phase it paid for: Python tracing (``trace_ms``), lowering to
  MLIR (``lower_ms``) and the backend's compile or, on a
  persistent-cache hit, the load (``wall_ms``) — into REGISTRY
  counters/histograms, engine ``stats()`` (and from there EC shares,
  the dashboard pane, and ``LoadReport``).  Set-up is the sum of
  those phases over a process's programs; the totals a
  :meth:`CompileLedger.snapshot` carries are what the benchmark's
  ``setup_*`` per-layer metrics read.
* A **steady-state compile detector**: once the harness drops the
  warmup fence (:meth:`CompileLedger.fence`), ANY further real compile
  is a bucket-discipline regression — the ledger bumps
  ``aiko_compiles_steady_state_total`` and fires a flight capture
  (trigger ``"compile"``) with the ledger attached, so the pathology
  is caught in production, not just in tests.
* The **collector's pauses**: a full (generation 2) garbage collection
  walks every long-lived object thirty traced programs leave, with
  the interpreter lock held, and so stops the engine loop.  While the
  ledger is installed one ``gc.callbacks`` entry times them: two
  totals, and a line with a time and the label then set for each
  pause over :data:`GC_PAUSE_LINE_MS`.
* :func:`enable_persistent_cache` turns on JAX's persistent
  compilation cache — in the directory ``JAX_COMPILATION_CACHE_DIR``
  names when the environment sets it, else a caller's directory or
  the fixed in-checkout :func:`default_cache_dir` — so a warm restart
  skips recompilation entirely; the ledger's hit/miss counters and
  the measured ``cache_load_ms_total`` against
  ``compile_wall_ms_total`` quantify it
  (``tools/loadgen.run_compile_cache_ab`` gates on it).

Event semantics (jax 0.9.0; re-checked on CPU and on a TPU v5e by
``chip_smoke.py``'s two-run cache check): the duration events carry
only the jitted function's name (``fun_name=``) and the hit/miss
events carry nothing, so attribution — which also needs the shape
bucket — uses a **per-thread label** set by the engine at each
dispatch site (:func:`label` / :func:`set_label`).  On a
persistent-cache HIT the
``…/backend_compile_duration`` event STILL fires (it times the cache
retrieval, not a real compile) — the ledger pairs a same-thread
preceding ``cache_hits`` event with the next duration event and books
it as a load, never as a compile.

**Each second is booked once.**  A duration event arrives when its
phase ENDS, on the thread that ran it, and trace events nest: every
inner ``jax.jit`` (each Pallas kernel here sits behind one) and every
``jnp`` wrapper fires its own inside the outer program's, and a
lowering rule that calls ``jnp`` fires trace events inside the
lowering's.  So a program's ``trace_ms`` is the UNION of its trace
intervals ``[end − duration, end]``, not their sum, and ``lower_ms``
is the lowering less the tracing inside it.  Children end before
their parent: a per-thread stack of the intervals no later event has
covered yet does it in O(1) an event, with no lock and no record
until the backend event closes the program.  A trace that no
lowering follows (``eval_shape``, a program whose executable is
already held) waits on the stack — it cannot be told from an earlier
child of a trace still open — and goes into the totals, under no
record, when the thread next closes a program, or when the stack
passes :data:`_STACK_CAP` entries.  The handler reads the clock when
it is called, a few µs after the phase ended, so an interval's edges
are late by that much (by a collection's pause, if one falls
between: then a child that ended in the parent's first instants is
booked beside it, not inside).

Records, pauses, ``obs/steplog`` events and engine spans share one
clock (:func:`_now`: the epoch at import plus ``perf_counter``), read
at the END of what they time, so they overlay without alignment.

Switchboard discipline (swept by ``scripts/obs_lint.py``): module
default ``LEDGER = None``; every call site outside this module guards
with ``compiles.LEDGER is not None``.  Listeners are registered ONCE
per process and forward to whatever ``LEDGER`` currently is — JAX has
no public listener-unregister API, so :func:`uninstall` simply nulls
the switchboard and the resident listeners become no-ops (the
collector's callback, which can be removed, is).  Invariant 15
(ARCHITECTURE.md): nothing here touches traced values — jaxprs are
byte-identical with the ledger installed or absent.

Stdlib-only at import time; ``jax`` strictly lazily (``obs`` package
discipline).
"""

from __future__ import annotations

import contextlib
import gc
import os
import sys
import threading
from collections import deque
from typing import Dict, List, Optional, Tuple

from .metrics import REGISTRY
from .steplog import _now

__all__ = ["CompileLedger", "LEDGER", "install", "uninstall",
           "enable_persistent_cache", "persistent_cache",
           "entry_point_cache", "default_cache_dir",
           "set_label", "clear_label", "current_label", "label"]

#: Process-wide switchboard.  ``None`` (the default) means compile
#: observability is OFF and every guarded call site is a pointer test.
LEDGER: Optional["CompileLedger"] = None

#: Whether the process-global jax.monitoring listeners have been
#: registered (once, lazily, at first install — never unregistered).
_LISTENERS_REGISTERED = False

_TLS = threading.local()

#: The three phases of one program, as ``jax/_src/dispatch.py`` names
#: their duration events (jax 0.9.0).
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

#: A full collection longer than this gets a line of its own in the
#: snapshot's ``pauses`` (a stall an operator would see), not only its
#: share of the totals.
GC_PAUSE_LINE_MS = 100.0

# A thread's open intervals: one flat list, _ENTRY slots an interval —
# end, duration, the trace seconds and the lowering seconds inside it
# that no record has yet, its kind, its function's name.
_ENTRY = 6
_TRACE, _LOWER, _BOOKED = 0, 1, 2
#: Intervals one thread may hold before they are booked under no
#: program: the direct children of one open trace (thousands, for a
#: model unrolled in Python), or traces nothing ever lowered.
_STACK_CAP = 1 << 15


# --------------------------------------------------------------------------- #
# Per-thread program labels — jax.monitoring events are anonymous, so the
# engine names the work before dispatching it.
# --------------------------------------------------------------------------- #

def set_label(program: str, signature: str = ""):
    """Name subsequent compiles on THIS thread (engine dispatch sites)."""
    _TLS.label = (str(program), str(signature))


def clear_label():
    _TLS.label = None


def current_label() -> Tuple[str, str]:
    got = getattr(_TLS, "label", None)
    return got if got else ("unlabeled", "")


@contextlib.contextmanager
def label(program: str, signature: str = ""):
    """Scoped :func:`set_label` (tests and one-shot call sites)."""
    previous = getattr(_TLS, "label", None)
    set_label(program, signature)
    try:
        yield
    finally:
        _TLS.label = previous


class CompileLedger:
    """Record of every program this process took to the backend, and
    of the collector's full pauses meanwhile.

    Thread-safe; listener callbacks arrive on whichever thread ran the
    jit.  ``max_records`` bounds the per-program detail ring (counters
    are unbounded monotonic).
    """

    def __init__(self, service: str = "", max_records: int = 256,
                 registry=None):
        self.service = service or f"pid{os.getpid()}"
        self.registry = registry or REGISTRY
        self._lock = threading.Lock()
        self.compiles = 0                 # real compiles (cache misses incl.)
        self.steady_compiles = 0          # real compiles AFTER the fence
        self.cache_hits = 0
        self.cache_misses = 0
        self.total_ms = 0.0               # backend wall of real compiles
        self.cache_load_ms = 0.0          # backend wall of cache hits
        self.trace_ms = 0.0               # Python tracing, each second once
        self.lower_ms = 0.0               # lowering, less tracing inside it
        self.programs_traced = 0          # records with a trace of their own
        # The collector's callback takes no lock (it can fire inside
        # this ledger's own locked regions): plain stores, one
        # collection at a time.
        self.gc_full_pauses = 0
        self.gc_full_pause_ms = 0.0
        self._gc_began = None
        self.pauses: deque = deque(maxlen=32)
        self.fenced = False
        self.records: deque = deque(maxlen=max(1, int(max_records)))
        self._counter_compiles = self.registry.counter(
            "aiko_compiles_total", "XLA compiles observed by the ledger")
        self._counter_steady = self.registry.counter(
            "aiko_compiles_steady_state_total",
            "compiles after the warmup fence (bucket-discipline breaches)")
        self._counter_hits = self.registry.counter(
            "aiko_compile_cache_hits_total",
            "persistent compilation cache hits")
        self._counter_misses = self.registry.counter(
            "aiko_compile_cache_misses_total",
            "persistent compilation cache misses")
        self._hist_wall = self.registry.histogram(
            "aiko_compile_wall_ms", "per-compile wall time (ms)")

    # -- warmup fence -------------------------------------------------------- #

    def fence(self):
        """Drop the warmup fence: from now on every real compile is a
        steady-state anomaly (bumps the counter and fires a flight
        capture).  Idempotent."""
        with self._lock:
            self.fenced = True

    def lift_fence(self):
        """Re-enter warmup (e.g. before an intentional reconfigure)."""
        with self._lock:
            self.fenced = False

    # -- event sinks (called by the module listeners or the wrapped-jit
    #    fallback entry point) ----------------------------------------------- #

    def on_cache_hit(self):
        with self._lock:
            self.cache_hits += 1
            self._counter_hits.inc()
        _TLS.pending_hit = True

    def on_cache_miss(self):
        with self._lock:
            self.cache_misses += 1
            self._counter_misses.inc()
        _TLS.pending_hit = False

    def record_compile(self, wall_ms: float, program: str = "",
                       signature: str = "", cache_hit: bool = False,
                       trace_ms: float = 0.0, lower_ms: float = 0.0,
                       fun_name: str = ""):
        """Book one program at its backend event: the compile's wall,
        or the load's when ``cache_hit``, with the tracing and
        lowering that led to it.  Public so engines without
        ``jax.monitoring`` can wrap their jit entry points and call this
        directly (the documented fallback path)."""
        if not program:
            program, default_sig = current_label()
            signature = signature or default_sig
        steady = False
        with self._lock:
            entry = {"program": program, "signature": signature,
                     "fun_name": fun_name,
                     "trace_ms": round(float(trace_ms), 3),
                     "lower_ms": round(float(lower_ms), 3),
                     "wall_ms": round(float(wall_ms), 3),
                     "cache_hit": bool(cache_hit),
                     "steady": False, "ts": _now()}
            self.trace_ms += float(trace_ms)
            self.lower_ms += float(lower_ms)
            if trace_ms > 0:
                self.programs_traced += 1
            if cache_hit:
                self.cache_load_ms += float(wall_ms)
            else:
                self.compiles += 1
                self.total_ms += float(wall_ms)
                self._counter_compiles.inc()
                self._hist_wall.observe(float(wall_ms))
                if self.fenced:
                    steady = True
                    entry["steady"] = True
                    self.steady_compiles += 1
                    self._counter_steady.inc()
            self.records.append(entry)
        if steady:
            self._fire_steady_capture(entry)

    def book_unlowered(self, trace_ms: float, lower_ms: float):
        """Tracing (and lowering) that led to no backend event: into
        the totals, under no record."""
        with self._lock:
            self.trace_ms += float(trace_ms)
            self.lower_ms += float(lower_ms)

    def on_full_collection(self, phase: str):
        """One edge of a generation 2 collection (``gc.callbacks``)."""
        if phase == "start":
            self._gc_began = _now()
            return
        began, self._gc_began = self._gc_began, None
        if began is None:       # installed while the collection ran
            return
        ended = _now()
        pause_ms = (ended - began) * 1e3
        self.gc_full_pauses += 1
        self.gc_full_pause_ms += pause_ms
        if pause_ms > GC_PAUSE_LINE_MS:
            program, signature = current_label()
            self.pauses.append({"ts": ended, "ms": round(pause_ms, 3),
                                "program": program,
                                "signature": signature})

    def _fire_steady_capture(self, entry: Dict):
        # Lazy import: flight imports THIS module at top level for its
        # bundle section, so the dependency must stay one-way at import
        # time.  Never let a capture failure leak into the compile path.
        try:
            from . import flight
            if flight.FLIGHT is not None:
                flight.FLIGHT.capture(
                    "compile",
                    reason=(f"steady-state compile: "
                            f"{entry['program']}"
                            f"[{entry['signature']}] "
                            f"{entry['wall_ms']:.1f}ms"))
        except Exception:  # noqa: BLE001 - observability must stay passive
            pass

    # -- distinct signatures (the log-bound check reads this) ---------------- #

    def signatures(self, program: Optional[str] = None) -> List[Tuple[str, str]]:
        """Distinct (program, signature) pairs among retained records
        of REAL compiles, optionally filtered by program."""
        with self._lock:
            seen = []
            for entry in self.records:
                if entry["cache_hit"]:
                    continue
                key = (entry["program"], entry["signature"])
                if program is not None and key[0] != program:
                    continue
                if key not in seen:
                    seen.append(key)
            return seen

    # -- export --------------------------------------------------------------- #

    def snapshot(self) -> Dict:
        """Flight-bundle / doctor section: monotonic totals, recent
        records, and the collector's long pauses."""
        with self._lock:
            return {
                "service": self.service,
                "compiles": self.compiles,
                "compiles_steady_state": self.steady_compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "compile_wall_ms_total": round(self.total_ms, 3),
                "cache_load_ms_total": round(self.cache_load_ms, 3),
                "trace_ms_total": round(self.trace_ms, 3),
                "lower_ms_total": round(self.lower_ms, 3),
                "programs_traced": self.programs_traced,
                "gc_full_pauses": self.gc_full_pauses,
                "gc_full_pause_ms": round(self.gc_full_pause_ms, 3),
                "fenced": self.fenced,
                "records": [dict(entry) for entry in self.records],
                "pauses": [dict(entry) for entry in self.pauses],
            }


# --------------------------------------------------------------------------- #
# jax.monitoring listeners — registered once, forward to LEDGER if any.
# --------------------------------------------------------------------------- #

def _on_event(event: str, **kwargs):  # noqa: ARG001 - kwargs are empty
    ledger = LEDGER
    if ledger is None:
        return
    if "cache_hit" in event:
        ledger.on_cache_hit()
    elif "cache_miss" in event:
        ledger.on_cache_miss()


def _covered(stack: list, start: float):
    """Drops from ``stack`` the intervals that ended after ``start`` —
    the children of an interval that began then — and returns their
    summed (duration, unbooked trace s, unbooked lowering s)."""
    covered = trace_s = lower_s = 0.0
    size = len(stack)
    while size and stack[size - _ENTRY] > start:
        covered += stack[size - _ENTRY + 1]
        trace_s += stack[size - _ENTRY + 2]
        lower_s += stack[size - _ENTRY + 3]
        size -= _ENTRY
    del stack[size:]
    return covered, trace_s, lower_s


def _book(ledger: "CompileLedger", stack: list):
    """Books under no program the trace and lowering seconds that the
    intervals on top of ``stack`` still hold, down to the first one
    booked before.  They stay where they are, as booked time: a trace
    still open round them subtracts each second that has a place."""
    trace_s = lower_s = 0.0
    at = len(stack) - _ENTRY
    while at >= 0 and stack[at + 4] != _BOOKED:
        trace_s += stack[at + 2]
        lower_s += stack[at + 3]
        stack[at + 2] = stack[at + 3] = 0.0
        stack[at + 4] = _BOOKED
        at -= _ENTRY
    if trace_s or lower_s:
        ledger.book_unlowered(trace_s * 1e3, lower_s * 1e3)


def _on_duration(event: str, duration_secs: float, **kwargs):
    """Every ``jax.monitoring`` duration event, on the thread that ran
    the phase and as it ends.  Trace events arrive 10⁴–10⁵ a program:
    that path takes no lock and makes no record."""
    ledger = LEDGER
    if ledger is None:
        return
    if event == TRACE_EVENT:
        kind = _TRACE
    elif event == LOWER_EVENT:
        kind = _LOWER
    elif event == BACKEND_EVENT:
        kind = None
    else:
        return
    end = _now()
    try:
        stack = _TLS.stack
    except AttributeError:
        stack = _TLS.stack = []
    covered, trace_s, lower_s = _covered(stack, end - duration_secs)
    if kind is None:
        _close_program(ledger, stack, end, duration_secs, trace_s,
                       lower_s, kwargs.get("fun_name", ""))
        return
    own = duration_secs - covered
    if own > 0.0:
        if kind == _TRACE:
            trace_s += own
        else:
            lower_s += own
    stack += (end, duration_secs, trace_s, lower_s, kind,
              kwargs.get("fun_name", ""))
    if len(stack) > _STACK_CAP * _ENTRY:
        _book(ledger, stack)
        stack[:] = (end, sum(stack[1::_ENTRY]), 0.0, 0.0, _BOOKED, "")


def _close_program(ledger: "CompileLedger", stack: list, end: float,
                   duration_secs: float, trace_s: float, lower_s: float,
                   fun_name: str):
    """The backend event closes a program's record.  Its own lowering
    is the interval on top of ``stack``, its own trace the one below
    that; what else is still unbooked there led to no program."""
    began = end - duration_secs
    for kind in (_LOWER, _TRACE):
        if stack and stack[-2] == kind:
            began = stack[-_ENTRY] - stack[-_ENTRY + 1]
            trace_s += stack[-_ENTRY + 2]
            lower_s += stack[-_ENTRY + 3]
            if kind == _TRACE:
                fun_name = stack[-1] or fun_name
            del stack[-_ENTRY:]
    _book(ledger, stack)
    stack += (end, end - began, 0.0, 0.0, _BOOKED, "")
    # A persistent-cache hit still fires the backend event, for the
    # retrieval; the same-thread pending-hit flag (set by the hit
    # event that immediately precedes it) reclassifies it.
    pending = getattr(_TLS, "pending_hit", False)
    _TLS.pending_hit = False
    ledger.record_compile(duration_secs * 1e3, cache_hit=pending,
                          trace_ms=trace_s * 1e3, lower_ms=lower_s * 1e3,
                          fun_name=fun_name)


def _on_gc(phase: str, info: Dict):
    """The ``gc.callbacks`` entry: generations 0 and 1 return at once."""
    if info["generation"] == 2:
        ledger = LEDGER
        if ledger is not None:
            ledger.on_full_collection(phase)


def _register_listeners() -> bool:
    global _LISTENERS_REGISTERED
    if _LISTENERS_REGISTERED:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:  # noqa: BLE001 - fallback: wrapped-jit entry points
        return False
    _LISTENERS_REGISTERED = True
    return True


def install(service: str = "", max_records: int = 256,
            ledger: Optional[CompileLedger] = None) -> CompileLedger:
    """Turn the ledger on (idempotent; returns the active ledger).

    When ``jax.monitoring`` is unavailable the ledger still installs —
    engines then attribute compiles through the
    :meth:`CompileLedger.record_compile` fallback entry point.
    """
    global LEDGER
    if LEDGER is None:
        LEDGER = ledger or CompileLedger(service=service,
                                         max_records=max_records)
        # A label names work for the ledger that was on when it was
        # set: one left on this thread by an engine that ran under an
        # earlier ledger (or under none) names nothing of this one's.
        clear_label()
        _register_listeners()
        gc.callbacks.append(_on_gc)
    return LEDGER


def uninstall():
    """Null the switchboard; resident listeners become no-ops, and
    the collector's callback goes."""
    global LEDGER
    if LEDGER is not None:
        LEDGER = None
        gc.callbacks.remove(_on_gc)


# --------------------------------------------------------------------------- #
# Persistent compilation cache wiring.
# --------------------------------------------------------------------------- #

#: The environment's placement of the persistent cache.  JAX reads it
#: into ``jax_compilation_cache_dir`` at import; where it is set, no
#: code here sets another directory.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """The fixed, git-ignored cache directory of this checkout
    (``<repo>/.jax_cache``), resolved from the package's location —
    never from a temp name, pid or time: the path is part of the cache
    key's neighbourhood, and a directory that moves never hits."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def entry_point_cache() -> str:
    """Place the persistent cache for a process that STARTS the program
    (``chip_smoke.py``, ``benchmark/run.py``, the pipeline and
    registrar CLIs); returns the directory.  Call it first thing in
    ``main``: it exports the placement — the directory the environment
    already names, else :func:`default_cache_dir` — together with the
    cache-everything thresholds, so JAX reads them when it is imported
    and every child this process launches (``ProcessManager`` replicas
    included) inherits the same directory.  A control-plane process
    that never imports JAX pays nothing.  Importing the package and
    running the tests call this nowhere."""
    cache_dir = os.environ.setdefault(CACHE_DIR_ENV, default_cache_dir())
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                          "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
                          "-1")
    if "jax" in sys.modules:
        # JAX has already read its environment: configure it directly.
        enable_persistent_cache()
    return cache_dir


def enable_persistent_cache(cache_dir: Optional[str] = None,
                            min_compile_time_secs: float = 0.0,
                            min_entry_size_bytes: int = -1) -> str:
    """Turn JAX's persistent compilation cache on; returns the
    directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set that directory is the
    cache and ``cache_dir`` is ignored (the machine's owner placed the
    cache; an engine's ``compilation_cache_dir=`` may not move it).
    Otherwise ``cache_dir``, or :func:`default_cache_dir`.

    The aggressive thresholds default to "cache everything" because
    serving programs are few and warm-restart
    time-to-first-compiled-step is the metric that matters
    (``SERVING.md`` warm-restart story; the loadgen A/B gates on it).
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    cache_dir = os.environ.get(CACHE_DIR_ENV) or str(
        cache_dir or default_cache_dir())
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      int(min_entry_size_bytes))
    # jax initializes its cache singleton on first compile and ignores
    # later config changes; reset so a mid-process enable (replica
    # constructed after other engines compiled) works.
    compilation_cache.reset_cache()
    return cache_dir


@contextlib.contextmanager
def persistent_cache(cache_dir: str):
    """Test-rig scope: the cache in ``cache_dir`` for the block, then
    the setting that was found — a rig's temp directory must not stay
    configured after it is deleted, and must not erase a cache that
    was on before it.  Yields the directory in use, which is the
    environment's when ``JAX_COMPILATION_CACHE_DIR`` pins it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    found = {name: getattr(jax.config, name) for name in names}
    try:
        yield enable_persistent_cache(cache_dir)
    finally:
        for name, value in found.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
