"""Continuous batching: slot-based LLM decode serving.

The static-batch :class:`~.serving.ModelReplica` decodes one request (or
one fixed batch) at a time; modern LLM serving interleaves many requests
in ONE resident decode batch so the weight stream (the decode
bottleneck) is amortized over every live request and a new request never
waits for the whole batch to finish.  The reference has nothing in this
space (its LLM element shells out to Ollama per request,
examples/llm/elements_llm.py:191-220).

TPU-native design — static shapes throughout:

* The server owns ``slots`` decode lanes and a KV cache of shape
  ``(slots, max_seq, …)``.  A request is ONE slot for its lifetime.
* Admission: prompts are right-padded to a power-of-2 bucket, and each
  admission wave prefills per-bucket groups in power-of-2 sub-batches
  (causal attention keeps every row's numerics exact regardless of pad
  garbage or batch-mates; pow2 everywhere keeps the compile-shape
  count bounded), landing each sub-batch's KV rows in its slots with
  one jitted batched scatter (cache donated → in-place).
  The slot is seeded with the LAST prompt token at position
  ``prompt_len - 1``: its KV rewrite is idempotent, and the first chunk
  step then emits the first generated token — no separate
  "logits-after-prefill" path exists to disagree with.
* Decode: :func:`~..models.llama.decode_chunk_ragged` scans
  ``chunk_steps`` greedy steps for ALL slots in one compiled program —
  every slot at its own position (``positions`` vector), finished /
  empty slots masked by ``active``.  Admission happens between chunks.
* Completion: a slot retires when it hits its token budget or emits
  ``eos_id``; the freed slot admits a queued request at the next chunk
  boundary.

Greedy decode through this path EXACTLY matches per-request
``generate_tokens`` output regardless of admission order (tested), so
batching is a pure throughput optimization, never a quality trade.

Layered on the same slot machinery (each independently tested, all
composable — see docs/SERVING.md):

* **lookahead** — multi-step scheduling: chunks chained device-side,
  one host sync per run;
* **chunk_prefill_tokens** — chunked-prefill admission: long prompts
  prefill between decode runs instead of stalling them;
* **adapters=** — multi-adapter LoRA serving (SLoRA-style stacked
  factors, PEFT hot-deploy over the wire, id 0 = base);
* **draft_config_name=** — per-slot SPECULATIVE decoding: one ragged
  verify pass per round; greedy exact, sampled slots via the
  device-side MRS kernel (distribution-preserving);
* token streaming (``stream: 1``), ``(infer_cancel id)``, and
  TTFT/total latency on every response.

:class:`ContinuousReplica` speaks the same ``(infer …)`` wire protocol
as :class:`~.serving.ModelReplica` (discovery, router and failover
compose unchanged; :class:`~.client.InferClient` packages the client
side); a delayed self-post pump (the reference's own retry idiom,
main/actor.py:229-253) runs chunks while slots are live —
deterministic under the VirtualClock test engine, where flatout
handlers only run inside the blocking loop.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import itertools
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..obs import compiles, flight, profiler, steplog, trace
from ..obs.metrics import CounterDict, Histogram, REGISTRY
from ..runtime import faults
from ..runtime.actor import Actor
from ..utils.sexpr import generate, parse

__all__ = ["ContinuousBatchingServer", "ContinuousReplica",
           "DecodeRequest"]

#: Distinct ``instance=`` metric label per server in this process.
_SERVER_INSTANCE_IDS = itertools.count()


@dataclasses.dataclass
class DecodeRequest:
    request_id: str
    prompt: "np.ndarray"           # (prompt_len,) int32
    max_new_tokens: int
    response_topic: Optional[str] = None
    #: 0 = greedy (exact, default); > 0 samples with optional nucleus.
    temperature: float = 0.0
    top_p: float = 1.0
    #: Deliver ``(infer_partial …)`` token increments as decode chunks
    #: complete (the final ``(infer_response …)`` still carries the
    #: full sequence).  The reference's LLM element blocks on the whole
    #: completion (examples/llm/elements_llm.py:185); streaming falls
    #: out of continuous batching for free.
    stream: bool = False
    #: Named LoRA adapter this request runs under (None = base model).
    #: Requests with different adapters share ONE decode batch — the
    #: base weight stream is paid once for all of them (SLoRA-style;
    #: server must be constructed with ``adapters=``).
    adapter: Optional[str] = None
    #: Named grammar from the server's ``automata`` registry: output
    #: is masked to the automaton's allowed sets and deterministic
    #: segments commit as jump-forward speculation windows.  None =
    #: unconstrained (the automaton applies to GENERATED tokens only,
    #: never the prompt).
    automaton: Optional[str] = None
    #: Generation by block passes (a model module with
    #: ``block_slot_state``): denoise passes a block (1 .. block
    #: length), ``"static"`` or ``"dynamic"``, and the dynamic rule's
    #: confidence threshold.  None: the registered config's value.
    #: Requests with different schedules share one batch.
    denoise_steps: Optional[int] = None
    denoise_rule: Optional[str] = None
    denoise_threshold: Optional[float] = None
    #: Absolute host-monotonic deadline (``deadline_ms`` on the wire
    #: travels as a RELATIVE budget — clocks never cross processes).
    #: Expired requests are rejected at admission and evicted from
    #: their slot with ``error="deadline_exceeded"``.
    deadline_ts: Optional[float] = None
    # Filled by the server:
    tokens: Optional[List[int]] = None
    error: Optional[str] = None
    #: Back-off hint attached to an ``error="overloaded"`` shed.
    retry_after_ms: Optional[int] = None
    #: Latency telemetry (monotonic seconds, host-observed): TTFT is
    #: measured at the host sync that DELIVERS the first token — the
    #: number a client actually experiences under lookahead/chunked
    #: admission, not the device-internal emission time.
    submitted_ts: Optional[float] = None
    first_token_ts: Optional[float] = None
    finished_ts: Optional[float] = None
    #: Slot activation (admission wave that reserved the slot) — with
    #: the stamps above this decomposes the request's life into the
    #: phases the obs layer histograms: queue-wait (submit→activate),
    #: prefill (activate→first token), decode (first→finish).
    activated_ts: Optional[float] = None
    #: The request's first prefill dispatch, and the moment its lane
    #: flipped to decode (``_activate_slot``).  With the stamps above
    #: they tile time to first token (:func:`ttft_parts`): a slot is
    #: reserved at once while slots are free, so what a chunked
    #: admission waits for is mostly OTHER prompts' slices, and
    #: ``activated_ts`` alone cannot show that.
    prefill_started_ts: Optional[float] = None
    decode_ready_ts: Optional[float] = None
    #: Prompt tokens the prefix cache spared this request, and the
    #: prefill dispatches it took and tokens they carried (prompts
    #: are padded to a bucket, so at least its uncached tokens).
    shared_tokens: int = 0
    prefill_dispatches: int = 0
    prefill_tokens: int = 0
    #: Milliseconds spent restoring this request's prefix KV from a
    #: remote replica (0 when no kv_source hint / local hit).
    kv_restore_ms: float = 0.0
    #: Propagated trace context (``trace_id/span_id`` wire form) — the
    #: replica synthesizes phase spans under it at response time.
    trace_ctx: Optional[str] = None
    #: Encoded spans fetched alongside a remote KV restore (the
    #: source's ``kv_export`` span) — merged into the response tree.
    remote_spans: Optional[str] = None
    #: Per-spec-round accepted-proposal counts for THIS request (one
    #: entry per verify pass that advanced it; empty without a draft).
    #: Loadgen histograms these — the per-request acceptance shape,
    #: not just the fleet-mean rate.
    spec_accepted_rounds: Optional[List[int]] = None


#: The parts of time to first token, in order; part ``i`` runs from
#: stamp ``i`` to stamp ``i + 1`` of :func:`_ttft_stamps`.
TTFT_PARTS = ("queue", "slice_wait", "prefill_run", "first_chunk")
#: The server counter that sums each part (milliseconds).
TTFT_COUNTERS = ("queue_wait_ms", "slice_wait_ms", "prefill_run_ms",
                 "first_chunk_ms")


def _ttft_stamps(request: DecodeRequest):
    """The five stamps that bound :data:`TTFT_PARTS`, or ``None``
    until the request has delivered its first token."""
    stamps = (request.submitted_ts, request.activated_ts,
              request.prefill_started_ts, request.decode_ready_ts,
              request.first_token_ts)
    return None if None in stamps else stamps


def ttft_parts(request: DecodeRequest) -> Dict[str, float]:
    """Seconds per part of the request's time to first token:
    ``queue`` (waited for a slot or pool blocks), ``slice_wait`` (sat
    in the slice queue behind older prompts), ``prefill_run`` (its own
    prefill dispatches, one slice per chunk when chunked) and
    ``first_chunk`` (the decode chunk, and the ring sync, before its
    first token was seen).  They sum to ``ttft``; empty until the
    first token."""
    stamps = _ttft_stamps(request)
    if stamps is None:
        return {}
    return {part: end - start for part, start, end
            in zip(TTFT_PARTS, stamps, stamps[1:])}


def _bucket(n: int, minimum: int = 16) -> int:
    size = minimum
    while size < n:
        size *= 2
    return size


class ContinuousBatchingServer:
    """Slot-based continuous batching around a Llama-family model."""

    def __init__(self, config_name: str = "tiny", slots: int = 4,
                 max_seq: Optional[int] = None, chunk_steps: int = 8,
                 quantize: bool = False, eos_id: Optional[int] = None,
                 seed: int = 0, quantize_kv: bool = False, mesh=None,
                 lookahead: int = 1, adapters: Optional[Dict] = None,
                 lora_config=None, chunk_prefill_tokens: int = 0,
                 draft_config_name: Optional[str] = None,
                 draft_params=None, spec_k: int = 4,
                 draft_quantize: bool = False,
                 draft_mode: str = "auto", spec_ladder=None,
                 spec_adaptive: bool = False, automata=None,
                 params=None,
                 max_queue: Optional[int] = None,
                 watchdog_s: float = 0.0, replica_mesh=None,
                 compilation_cache_dir: Optional[str] = None,
                 compact_upload: bool = True,
                 ring_max: Optional[int] = None):
        import jax
        import jax.numpy as jnp
        from .. import models
        from ..models import llama

        self._jax = jax
        self._jnp = jnp
        #: The module that serves ``config_name``: every model call of
        #: the engine goes through it.  ``_llama`` stays bound for what
        #: is only ever a Llama-family model (a speculative draft).
        self._model, self.config = models.serving_model(config_name)
        #: Its jitted entry points, and how many signatures they held
        #: when the heap was last frozen (:meth:`_settle_heap`).
        self._model_programs = [
            entry for entry in vars(self._model).values()
            if hasattr(entry, "_cache_size")]
        self._programs_settled = 0
        self._llama = llama
        #: Positions of a block where the module generates by block
        #: passes (it has ``block_slot_state``), else 0: a slot's unit
        #: of work is then a block that takes several passes, a pass
        #: commits 0 .. block tokens of it in any order, and what is
        #: delivered is the contiguous committed prefix.
        self._block_length = (
            int(self.config.block_length)
            if hasattr(self._model, "block_slot_state") else 0)
        #: The module keeps two kinds of row in one pool (a window's
        #: exact rows, summaries of what lies behind it) and composes
        #: the table the K/V kernels walk from the absolute position:
        #: the engine asks it what a position holds and reads.
        self._composed = hasattr(self._model, "composed_tables")
        self._refuse_unsupported(
            mesh=mesh is not None, replica_mesh=replica_mesh is not None,
            adapters=bool(adapters),
            speculation=draft_config_name is not None
            or draft_mode not in ("auto", "model") or bool(automata))
        # Persistent compilation cache (PR 14): opt-in per replica,
        # wired BEFORE any jit below so the very first prefill/serve
        # compiles land in (or load from) the cache — a warm restart
        # then skips recompilation entirely (SERVING.md warm-restart;
        # loadgen.run_compile_cache_ab gates cold vs warm).  Where
        # JAX_COMPILATION_CACHE_DIR places the cache, that directory
        # wins over the argument.
        self.compilation_cache_dir = compilation_cache_dir
        if compilation_cache_dir:
            self.compilation_cache_dir = \
                compiles.enable_persistent_cache(compilation_cache_dir)
        if params is not None:
            # Caller-built weights (trained, imported, or
            # random_quantized_params) — an 8B-class server on a
            # 16 GB chip cannot afford the bf16 init below just to
            # requantize it.  ``quantize=`` then only DECLARES the
            # tree's layout (for the TP spec choice); no
            # re-quantization happens.
            self.params = params
        else:
            self.params = self._model.init_params(
                self.config, jax.random.PRNGKey(seed))
            if quantize:
                self.params = self._model.quantize_params(self.params)
        if mesh is not None:
            # Multi-chip serving: megatron-TP-shard the (possibly
            # quantized) params over the mesh's "tp" axis; the decode
            # state (cache/positions/tokens) stays replicated and XLA
            # inserts the activation collectives.  This is the
            # composition a TP serving deployment runs.
            from jax.sharding import NamedSharding
            specs = (self._model.quantized_param_specs(self.config)
                     if quantize
                     else self._model.param_specs(self.config))
            self.params = jax.tree.map(
                lambda leaf, spec: jax.device_put(
                    leaf, NamedSharding(mesh, spec)),
                self.params, specs)
        # Tensor-parallel replica: ONE replica owns ONE mesh.  Weights
        # shard on their output-feature axis, the paged KV pool shards
        # on the kv-head dimension, and the per-slot decode state stays
        # replicated — the host admission/commit protocol is untouched.
        # Collectives are all-gathers (pure data movement), so greedy
        # decode is BITWISE equal to the single-chip server (tested).
        self.replica_mesh = replica_mesh
        self._mesh = None
        self.tp_degree = 1
        self.sp_degree = 1
        self.ep_degree = 1
        self.mesh_shape = ""
        if replica_mesh is not None:
            if mesh is not None:
                raise ValueError(
                    "mesh= (GSPMD megatron sharding) and replica_mesh= "
                    "(shard_map TP engine) are distinct parallel "
                    "paths; pass one")
            replica_mesh.validate(self.config)
            from ..models import llama_tp
            self._llama_tp = llama_tp
            self._mesh = replica_mesh.build()
            self.tp_degree = int(replica_mesh.tp)
            self.sp_degree = int(replica_mesh.sp)
            self.ep_degree = int(replica_mesh.ep)
            self.mesh_shape = f"{replica_mesh.axis}={self.tp_degree}"
            second = replica_mesh.second_axis
            if second is not None:
                n2 = self.sp_degree if replica_mesh.sp > 1 \
                    else self.ep_degree
                self.mesh_shape += f",{second}={n2}"
            self.params = llama_tp.shard_params(
                self.params, self._mesh, replica_mesh.axis,
                ep_axis=(replica_mesh.ep_axis
                         if replica_mesh.ep > 1 else None),
                overlap=replica_mesh.overlap)
        self.slots = slots
        # Row max_seq-1 is the inactive-slot scratch row (see
        # decode_chunk_ragged); a live request may use at most
        # max_seq-2 positions.
        self.max_seq = max_seq or self.config.max_seq_len
        self.chunk_steps = chunk_steps
        # Multi-step scheduling: dispatch up to ``lookahead`` chunks
        # back-to-back with the device-returned tokens/positions chained
        # chunk-to-chunk, then sync to host ONCE for the whole run.
        # Bookkeeping (EOS, budgets, admission) lags by the run length,
        # but the device never idles waiting on a host round trip.
        # 1 = sync every
        # chunk (the exact original behavior).  GREEDY outputs are
        # identical for every value (slot isolation is exact, tested);
        # SAMPLED outputs are identical while the chunk-vs-admission
        # timeline is unchanged (tested) but may legitimately differ
        # when a mid-run EOS shifts a queued request's admission chunk
        # — the request then draws different RNG chunk keys.
        self.lookahead = max(1, int(lookahead))
        # Chunked-prefill admission: prompts longer than this prefill
        # ``chunk_prefill_tokens`` tokens per step, INTERLEAVED with
        # the running slots' decode chunks — a long prompt no longer
        # stalls every live request for its whole prefill (the
        # decode-latency/SLO half of vLLM-style chunked prefill).
        # 0 = off (whole-bucket admission).  Power of two so chunk
        # programs share one shape per bucket size — plus at most one
        # tail-chunk shape when ``max_seq`` clamps a bucket to a
        # non-multiple of the chunk width.
        self.chunk_prefill_tokens = int(chunk_prefill_tokens)
        if self.chunk_prefill_tokens:
            if self.chunk_prefill_tokens < 16 or \
                    self.chunk_prefill_tokens & \
                    (self.chunk_prefill_tokens - 1):
                raise ValueError(
                    "chunk_prefill_tokens must be a power of two >= "
                    f"16, got {self.chunk_prefill_tokens}")
        #: slot -> in-progress chunked admission state.
        self._prefilling: Dict[int, Dict] = {}
        # Per-slot SPECULATIVE decoding: a small draft model proposes
        # spec_k tokens for every live slot in one ragged chunk; ONE
        # target verify pass (llama.verify_chunk_ragged) scores all
        # proposals, and each slot commits its own accepted prefix
        # plus the target's correction/bonus token — greedy outputs
        # stay EXACTLY equal to the plain server (tested).  The draft
        # keeps its own (slots, max_seq) contiguous cache, prefilled
        # at admission alongside the target's.
        self._draft = None
        if draft_config_name is not None:
            # Speculation now composes with chunked-prefill admission
            # (the draft's prompt KV lands whole at _finish_prefill —
            # the draft is small, so one un-chunked prefill does not
            # reintroduce the stall chunking removes) and with
            # replica_mesh TP (draft replicated on the mesh, below).
            # Still-unsupported combos stay LOUD errors:
            if mesh is not None:
                raise ValueError(
                    "speculative decoding does not compose with mesh= "
                    "(GSPMD megatron sharding): draft placement is "
                    "only defined for replica_mesh= (shard_map TP, "
                    "draft replicated) — or pass no mesh")
            # NOTE the verify-window width guard (k+1 vs the prompt
            # bucket floor) moved to validate_ladder below: the paged
            # layout may RAISE the floor to block_size, so the check
            # must run after _init_layout — and it now names the
            # whole LADDER, the thing actually bounding compiled
            # shapes under adaptive k.
            draft_config = llama.CONFIGS[draft_config_name]
            if draft_config.vocab_size != self.config.vocab_size:
                raise ValueError("draft and target must share a "
                                 "vocabulary")
            if draft_params is None:
                draft_params = llama.init_params(
                    draft_config, jax.random.PRNGKey(seed + 1))
            if draft_quantize:
                draft_params = llama.quantize_params(draft_params)
            self._draft = dict(
                config=draft_config, params=draft_params,
                k=int(spec_k),
                cache=llama.init_cache(draft_config, slots,
                                       self.max_seq))
            if self._mesh is not None:
                # TP replica: the draft model rides the SAME mesh,
                # fully replicated (params + its contiguous cache).
                # Draft dispatches then run the ordinary jitted
                # programs on every device with no collectives — each
                # chip computes the identical proposal stream, so TP
                # spec greedy output is bitwise the single-chip
                # server's (invariants 9 + 11).
                self._draft["params"] = self._llama_tp.replicate(
                    self._draft["params"], self._mesh)
                self._draft["cache"] = self._llama_tp.replicate(
                    self._draft["cache"], self._mesh)
        self.eos_id = eos_id
        self.quantize_kv = quantize_kv
        self._bucket_minimum = 16
        #: Speculation policy (set after _init_layout — the ladder
        #: validates against the FINAL bucket floor).  None = plain
        #: decode; _draft above is only the model-mode proposer.
        self._spec = None
        self._automata = None
        self._autostates = None
        self._init_layout()
        self._init_spec(draft_mode, spec_k, spec_ladder, spec_adaptive,
                        automata)
        # Attention dispatch tags ("kernel" = Pallas kernel,
        # "reference" = jnp oracle) + the block geometry of the
        # attention view — decided once at init by the dispatch's own
        # predicate at this server's shapes, so a number is
        # attributable to the path that produced it.
        self._attn_block_size, self._attn_total_blocks = \
            self._attention_blocks()
        self.decode_attention_path, self.prefill_attention_path = \
            self._attention_paths()
        self.decode_scale_append_path = self._scale_append_path()
        self.decode_attend_form = self._attend_form()
        # Bookkeeping state lives HOST-side (numpy): admissions and
        # retirements mutate it for free, and it rides into the chunk
        # dispatch as three tiny h2d transfers.  The device-returned
        # copies are never fetched — the host mirror advances by the
        # same deterministic rule the compiled chunk applies
        # (positions += steps for chunk-active slots, next seed token
        # = last emitted).  Before this, every admission cost ~4
        # separate device scatters.
        # Multi-adapter LoRA serving (SLoRA-style): stack the named
        # adapters once (index 0 = all-zero identity = base model);
        # each slot carries the index of ITS adapter and prefill +
        # decode gather per-row factors — mixed-adapter batches pay
        # the base weight stream once.
        self._adapter_index: Dict[str, int] = {}
        self._lora_shared = None
        self._lora_config = lora_config
        self._free_adapter_ids: List[int] = []
        if adapters:
            from ..models import lora as lora_mod
            if lora_config is None:
                raise ValueError("adapters= requires lora_config=")
            names = list(adapters)
            self._adapter_index = {name: i + 1
                                   for i, name in enumerate(names)}
            self._lora_shared = self._place_lora(
                lora_mod.stack_adapters(
                    self.config, lora_config,
                    [adapters[name] for name in names]))
        self._adapter_ids = np.zeros((slots,), np.int32)
        # Multi-tenant load provenance: warm = restacked from paged
        # storage, cold = factors shipped in from outside.
        self.adapter_warm_loads = 0
        self.adapter_cold_loads = 0
        self.positions = np.zeros((slots,), np.int32)
        self.active = np.zeros((slots,), bool)
        self.tokens = np.zeros((slots, 1), np.int32)
        self._temperatures = np.zeros(slots, np.float32)
        self._top_ps = np.ones(slots, np.float32)
        self._rng = jax.random.PRNGKey(seed)
        self._any_sampled = False
        self._requests: List[Optional[DecodeRequest]] = [None] * slots
        self._emitted = np.zeros(slots, np.int64)  # tokens emitted so far
        self._queue: List[DecodeRequest] = []
        self.completed: List[DecodeRequest] = []
        # ---- device-resident serving state + async dispatch ring ---- #
        # The decode state (token tail, positions, active, remaining
        # budget, sampling controls, adapter ids — plus block tables in
        # the paged layout) lives in ``self._state``, a chain of small
        # immutable device dicts: each dispatched chunk consumes the
        # head and returns the next.  The host keeps numpy mirrors for
        # bookkeeping, but they ride to the device ONLY through
        # ``_sync_dirty`` — a single masked merge covering the slots an
        # admission/retirement actually touched — so the steady-state
        # decode loop performs ZERO host→device uploads.
        self._remaining = np.zeros(slots, np.int32)
        #: Host mirrors of the block in progress and the slot's
        #: schedule (the module's ``block_slot_state`` leaves), kept
        #: exact as passes are consumed.
        self._block_state = (
            self._model.block_slot_state(self.config, slots)
            if self._block_length else {})
        self._state = self._init_device_state()
        if self._mesh is not None:
            # Slot state (and the paged layout's block tables) must be
            # REPLICATED jax.Arrays on the replica mesh so shard_map's
            # P() in_specs see one consistent copy per shard.
            self._state = self._llama_tp.replicate(self._state,
                                                   self._mesh)
        # In-flight ring: results of dispatched-but-unconsumed chunks.
        # Depth max(2, lookahead) double-buffers at minimum: step t+1
        # launches while step t's tiny (tokens, counts, active) result
        # is still in flight, and np.asarray happens only at consume.
        # The depth is ADAPTIVE between ``ring_min`` and ``ring_max``:
        # ``_ring_policy`` widens while the device is starved (ring
        # syncs return instantly AND the ring keeps running dry
        # between host passes) and shrinks back while the device is
        # saturated (syncs dwarf dispatch cost) — extra depth then
        # only delays retire/admit decisions by more chunks.
        from collections import deque
        self._ring = deque()
        self.ring_min = max(2, self.lookahead)
        self.ring_max = (int(ring_max) if ring_max is not None
                         else max(4, 2 * self.ring_min))
        if self.ring_max < self.ring_min:
            raise ValueError(
                f"ring_max {self.ring_max} below the double-buffer "
                f"floor max(2, lookahead) = {self.ring_min}")
        self._ring_depth = self.ring_min
        self._ema_wait_ms: Optional[float] = None
        self._ema_dispatch_ms: Optional[float] = None
        self._starved_streak = 0
        #: the next dispatch follows an admission wave whose last
        #: prefill may still be in flight (steplog classification only)
        self._post_admission = False
        #: compact dirty-row uploads (default): ``_sync_dirty``
        #: gathers ONLY the dirty mirror rows into a pow2-bucketed
        #: packet and row-scatters it into the resident state.  False
        #: = the legacy full-mirror masked merge — kept as the parity
        #: reference the compact path is tested bitwise against.
        self.compact_upload = bool(compact_upload)
        #: per-slot admission generation: an in-flight entry only
        #: applies to a slot whose serial still matches the entry's
        #: snapshot, so a retire-then-readmit can never credit a stale
        #: chunk's tokens to the new occupant.
        self._slot_serial = np.zeros(slots, np.int64)
        #: decode steps dispatched but not yet consumed, per slot —
        #: dispatch sizing subtracts this so a slot is never scheduled
        #: past its budget while results are in flight.
        self._inflight_sched = np.zeros(slots, np.int64)
        #: slots whose host mirror changed since the last dispatch.
        self._dirty = np.zeros(slots, bool)
        #: slots with a live sampling-param edit pending (uploads ONLY
        #: the sampling leaves — the slot may have chunks in flight).
        self._dirty_sampling = np.zeros(slots, bool)
        # Registry-mirrored engine counters: the dict API is unchanged
        # (tests and stats() read it directly) while every write also
        # lands in the process metrics registry under
        # ``aiko_server_<key>{instance=…}`` for the (metrics …) dump.
        # Process-monotonic instance id: ``id(self)`` hashes collide
        # when the allocator reuses a freed server's address, silently
        # MERGING two servers' registry series (histogram counts
        # accumulate across unrelated servers).
        self._instance_id = next(_SERVER_INSTANCE_IDS)
        self._metrics_labels = {"instance": f"srv{self._instance_id}"}
        self.counters: Dict = CounterDict(dict(
            dispatches=0, decode_steps=0, tokens_committed=0,
            host_syncs=0, sync_wait_ms=0.0, sync_elements=0,
            state_uploads=0, dirty_rows_uploaded=0, max_in_flight=0,
            ring_starved_steps=0, admission_deferred=0,
            decode_blocks_read=0, decode_iterations=0,
            decode_wide_iterations=0, prefill_tokens=0,
            sp_prefill_dispatches=0,
            # Time to first token, accounted where it is spent: the
            # six below are added TOGETHER when a request's first
            # token is committed (the four parts sum to ttft_ms over
            # any interval); then the slice queue's service, its
            # depth summed over dispatches, and the prompt tokens
            # admitted past the prefix cache (prefill_tokens counts
            # what was dispatched for them, padding included).
            first_tokens=0, ttft_ms=0.0, queue_wait_ms=0.0,
            slice_wait_ms=0.0, prefill_run_ms=0.0, first_chunk_ms=0.0,
            prefill_slices=0, prefill_slices_mixed=0,
            prefill_backlog=0, prompt_tokens=0,
            # What the append attention was asked to do: key blocks x
            # query tiles of every paged prefill dispatch, one layer's
            # worth (ops/paged_prefill.prefill_key_blocks).
            prefill_key_blocks=0,
            deadline_exceeded=0, shed=0, watchdog_trips=0,
            heap_freezes=0),
            prefix="server", labels=self._metrics_labels)
        for name in self._model.COUNTERS:
            # What a serve chunk of this model module returns beside
            # its tokens; added when the chunk is read (_consume_ready).
            self.counters[name] = 0
        for name in getattr(self._model, "CACHE_COUNTERS", ()):
            # What a cache of two kinds of row did, reckoned from the
            # host's position mirrors (_note_rows, _note_decode_blocks).
            self.counters[name] = 0
        if self._block_length:
            # Live slot-passes; those that only stored a finished
            # block's K/V; blocks whose K/V became final.
            # ``decode_steps`` counts passes here, and
            # ``tokens_committed`` what the passes delivered.
            for name in ("block_pass_rows", "block_store_rows",
                         "blocks_finished"):
                self.counters[name] = 0
        if self._model.RECURRENT_STATE:
            # Prompt tokens that advanced a slot's recurrent state, and
            # prompts that began from a zero state (paged._state_slice).
            self.counters["ssm_prefill_tokens"] = 0
            self.counters["ssm_state_resets"] = 0
        # Per-phase latency histograms — FIXED log-spaced buckets, so
        # the router/loadgen can merge them across replicas exactly
        # (they ride EC shares as ``hist.<phase>`` encoded strings).
        # Registry-created, so the (metrics …) scrape renders them as
        # proper ``_bucket``/``_sum``/``_count`` series too.
        self.latency_hists: Dict[str, Histogram] = {
            phase: REGISTRY.histogram(
                f"aiko_latency_{phase}_ms",
                help=f"Per-request {phase} latency (ms).",
                labels=self._metrics_labels)
            for phase in ("ttft", "total", "queue", "prefill",
                          "slice_wait", "prefill_run", "first_chunk",
                          "decode", "kv_restore")}
        self._serve_started: Optional[float] = None
        # ---- robustness: backpressure + device watchdog -------------- #
        #: bounded queue: submits past this depth shed with
        #: ``error="overloaded"`` + a retry-after hint (None = unbounded,
        #: the pre-robustness behavior).
        self.max_queue = max_queue
        #: host-side stall threshold (seconds) around the in-flight
        #: ring sync; 0 disables.  A sync past the threshold trips the
        #: watchdog: in-flight work fails with the RETRIABLE
        #: ``error="watchdog_stalled"`` and the replica goes (and
        #: stays) unhealthy until an operator restarts it.
        self.watchdog_s = float(watchdog_s)
        self.healthy = True
        self._watchdog_tripped = False
        # ---- on-demand device profiling (PR 14) ---------------------- #
        #: measured per-step device ms from the last (profile) bracket
        #: (None until one ran).
        self._device_step_ms: Optional[float] = None
        #: the open ``dispatch`` step-log span of a decode chunk or
        #: spec round, from before its dirty-row upload to
        #: ``_note_dispatch`` (None unless a recorder is installed).
        #: Parked here so that the layout's ``_serve_chunk`` can note
        #: the prefill slice the chunk carries: the dispatch's cause.
        self._dispatch_span = None
        #: what the last ``_serve_chunk`` got back beyond tokens,
        #: counts, state and pool (``self._model.COUNTERS``: device
        #: scalars, read with the chunk's tokens), until the chunk's
        #: ring entry takes it.
        self._chunk_counters = None
        self._profiles = 0
        self._profile_idle = 0

        @jax.jit
        def merge_state(state, host_state, mask):
            def merge(dev, host):
                m = mask.reshape((-1,) + (1,) * (dev.ndim - 1))
                return jnp.where(m, host.astype(dev.dtype), dev)
            return jax.tree.map(merge, state, host_state)

        self._merge_state = merge_state

    def _refuse_unsupported(self, **asked) -> None:
        """What a model module cannot be served with yet is refused at
        construction, by name.  The module says what that is
        (``UNSUPPORTED``: what the model has that the feature cannot
        carry, and per feature the missing piece); ``asked``: feature
        -> whether the caller asked for it."""
        if self._model.UNSUPPORTED is None:
            return
        has, missing = self._model.UNSUPPORTED
        for feature, wanted in asked.items():
            if wanted and feature in missing:
                raise ValueError(
                    f"{feature} is not available for a model with "
                    f"{has} "
                    f"({self._model.__name__.rsplit('.', 1)[-1]}): it "
                    f"needs {missing[feature]}")

    def _init_device_state(self) -> Dict:
        """Device-resident per-slot serving state (layout hook: the
        paged server adds its block tables)."""
        jnp = self._jnp
        slots = self.slots
        return {
            "token": jnp.zeros((slots, 1), jnp.int32),
            "positions": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
            "remaining": jnp.zeros((slots,), jnp.int32),
            "temps": jnp.zeros((slots,), jnp.float32),
            "tops": jnp.ones((slots,), jnp.float32),
            "adapter_ids": jnp.zeros((slots,), jnp.int32),
            **{name: jnp.asarray(leaf)
               for name, leaf in self._block_state.items()},
        }

    def _host_state(self) -> Dict:
        """Host mirror of :meth:`_init_device_state` (same keys; numpy
        views, uploaded only for dirty slots by ``_sync_dirty``)."""
        return {
            "token": self.tokens,
            "positions": self.positions,
            "active": self.active,
            "remaining": self._remaining,
            "temps": self._temperatures,
            "tops": self._top_ps,
            "adapter_ids": self._adapter_ids,
            **self._block_state,
        }

    def _sync_dirty(self) -> None:
        """Merge dirty host-mirror rows into the resident device state
        — the ONLY host→device path for decode state.  No admissions or
        retirements since the last dispatch ⇒ no upload at all.

        Compact path (default): gather ONLY the dirty rows into a
        small ``(n_dirty, …)`` packet, pad to a pow2 bucket (repeating
        the last row — idempotent under the duplicate scatter), and
        row-scatter it into the resident state via
        :func:`~..models.llama.scatter_state_rows` (its
        :mod:`~..models.llama_tp` twin under a replica mesh).  Upload
        cost is O(dirty), not O(slots), and compile shapes stay
        log-bounded in the fleet size.

        The mirrors are SNAPSHOTTED (copied) here: the CPU backend may
        alias a numpy argument zero-copy into the async computation,
        and the host keeps mutating the mirrors (consume, retire)
        before the merge actually reads them — without the copy the
        merge races its own inputs.  The compact packet is race-safe
        by construction (fancy indexing always copies); the legacy
        masked-merge fallback keeps the full-shape operand its mask
        needs but copies live data for the DIRTY rows only.

        Two dirty classes.  STRUCTURAL rows (``_dirty``: admission,
        retirement, budget rebase) upload every leaf — valid only
        because such a slot has no live in-flight entries (the serial
        bump / ring drain guarantees it), so the mirrors equal the
        resident truth.  SAMPLING rows (``_dirty_sampling``: live
        ``update_sampling`` edits) may have chunks in flight whose
        progress leaves (``token``/``positions``/``remaining``) the
        host cannot know yet — those rows scatter ONLY the sampling
        leaves, never the progress leaves."""
        structural = self._dirty
        sampling = self._dirty_sampling & ~structural
        if not (structural.any() or sampling.any()):
            return
        rows = np.nonzero(structural)[0].astype(np.int32)
        sampling_rows = np.nonzero(sampling)[0].astype(np.int32)
        n_dirty = len(rows) + len(sampling_rows)
        span = None
        if steplog.RECORDER is not None:
            span = steplog.RECORDER.begin("state_upload", rows=n_dirty)
        if not self.compact_upload:
            # Legacy merge has no per-leaf mask; update_sampling
            # settles the ring before marking on this path, so every
            # dirty row is safe to merge wholesale.
            mask = structural | sampling
            merge_rows = np.nonzero(mask)[0]
            if compiles.LEDGER is not None:
                compiles.set_label("merge_state")
            snapshot = {}
            for key, value in self._host_state().items():
                buffer = np.zeros_like(value)
                buffer[merge_rows] = value[merge_rows]
                snapshot[key] = buffer
            self._state = self._merge_state(self._state, snapshot,
                                            mask.copy())
        else:
            if len(rows):
                padded = self._pow2_rows(rows)
                packet = {key: value[padded]
                          for key, value in self._host_state().items()}
                if compiles.LEDGER is not None:
                    compiles.set_label("scatter_rows",
                                       f"r{len(padded)}")
                self._state = self._scatter_rows(self._state, padded,
                                                 packet)
            if len(sampling_rows):
                padded = self._pow2_rows(sampling_rows)
                packet = {"temps": self._temperatures[padded],
                          "tops": self._top_ps[padded]}
                if compiles.LEDGER is not None:
                    compiles.set_label("scatter_sampling",
                                       f"r{len(padded)}")
                sub = {key: self._state[key] for key in packet}
                merged = self._scatter_rows(sub, padded, packet)
                self._state = {**self._state, **merged}
        self._dirty[:] = False
        self._dirty_sampling[:] = False
        self.counters["state_uploads"] += 1
        self.counters["dirty_rows_uploaded"] += n_dirty
        if span is not None:
            span.end()

    def _pow2_rows(self, rows: np.ndarray) -> np.ndarray:
        """Pad a dirty-row index vector to its pow2 bucket (clamped to
        the fleet size) by repeating the LAST row — duplicate indices
        scatter identical payloads, so the merge stays exact while the
        compile-shape count stays log-bounded."""
        bucket = 1
        while bucket < len(rows):
            bucket *= 2
        bucket = min(bucket, self.slots)
        padded = np.empty(bucket, np.int32)
        padded[:len(rows)] = rows
        padded[len(rows):] = rows[-1]
        return padded

    def _scatter_rows(self, state, padded, packet):
        """Route the row scatter to the single-chip kernel or its TP
        twin (which re-replicates the packet onto the replica mesh)."""
        if self._mesh is not None:
            return self._llama_tp.scatter_state_rows(
                state, padded, packet, self._mesh)
        return self._model.scatter_state_rows(state, padded, packet)

    def _attention_blocks(self):
        """``(block_size, total_blocks_per_row)`` of the decode-
        attention view: the contiguous cache is the kernel's degenerate
        block pool (the paged server overrides with its real pool
        geometry)."""
        from ..ops.paged_attention import contiguous_block_size
        block_size = contiguous_block_size(self.max_seq) or self.max_seq
        return block_size, -(-self.max_seq // block_size)

    def _kv_geometry(self):
        """``(head_dim, local kv heads, KV dtype)`` as the attention
        dispatch sees them (the paged server divides the heads by its
        tensor-parallel degree)."""
        return self._model.kv_geometry(self.config, self.quantize_kv)

    def _attention_paths(self):
        """``(decode, prefill)`` path tags of the contiguous layout:
        decode feeds the cache to the paged kernel as a degenerate
        pool when a block size exists; whole-bucket prefill is flash
        attention (later chunked-prefill slices are always jnp)."""
        from ..ops.attention import flash_tiles
        from ..ops.paged_attention import (contiguous_block_size,
                                           decode_attention_path)
        decode = "reference"
        if contiguous_block_size(self.max_seq):
            decode = decode_attention_path(*self._kv_geometry())
        flash = (self._jax.default_backend() == "tpu"
                 and flash_tiles(self._bucket_minimum,
                                 self._bucket_minimum))
        return decode, "kernel" if flash else "reference"

    def _scale_append_path(self) -> str:
        """How a decode step appends its int8 KV scales: ``"kernel"``
        (the paged layout's lane rows, patched in place),
        ``"scatter"`` (XLA, into planes: this layout always) or
        ``"none"`` (a float cache has no scales)."""
        return "scatter" if self.quantize_kv else "none"

    def _attend_form(self) -> str:
        """Which ``attend`` body of the K/V decode kernel this
        geometry takes, by the kernel's own deciding function:
        ``"all_heads"`` (one query row a kv head: every head of a key
        at once), ``"word_rows"`` (head by head, a float pool's heads
        read as whole 32-bit word rows) or ``"per_head"``.  Static a
        server."""
        from ..ops.paged_attention import decode_attend_form
        _, kv_heads, kv_dtype = self._kv_geometry()
        query_rows = (self.config.n_heads // (kv_heads * self.tp_degree)
                      * max(self._block_length, 1))
        return decode_attend_form(query_rows, kv_heads,
                                  self._attn_block_size, kv_dtype)

    def _note_decode_blocks(self, live, sched) -> None:
        """Estimate the KV blocks each dispatched decode step reads,
        from the host position mirrors (positions as of dispatch;
        intra-chunk advance is ignored — at most ``steps/block_size``
        blocks/row of undercount).  Kernel path: only the row's live
        blocks, window-clamped; reference path: the whole cache/table
        every step — the counter makes the O(max_seq) → O(len) traffic
        difference a tracked number."""
        sched_live = sched[live]
        positions = self.positions[live]
        if self._composed:
            # The kernel walks the composed row: summaries of the
            # windows behind, then the window's own rows.
            positions, held = self._model.cache_rows(
                self.config, positions, self._attn_block_size)
            for name, a_step in held.items():
                self.counters[name] += int((a_step * sched_live).sum())
        if self.decode_attention_path == "kernel":
            block_size = self._attn_block_size
            # A step reads up to its own row; a block pass up to its
            # block's last, once for all the block's queries.
            reach = max(self._block_length, 1)
            blocks = (positions + reach - 1
                      + block_size) // block_size   # ceil((pos+1)/bs)
            window = self.config.sliding_window
            if window:
                blocks = np.minimum(blocks, window // block_size + 1)
            if not hasattr(self._model, "attention_paths"):
                # The K/V decode kernel's own loop bounds at the same
                # positions: passes through its block-table loop, and
                # those that attend over a whole W-key tile.
                from ..ops.paged_attention import decode_iteration_counts
                _, kv_heads, _ = self._kv_geometry()
                for rows, counter in zip(decode_iteration_counts(
                        positions + reach - 1,
                        block_size=block_size,
                        table_blocks=self._attn_total_blocks,
                        kv_heads=kv_heads, window=window or None,
                        form=self.decode_attend_form),
                        ("decode_iterations", "decode_wide_iterations")):
                    self.counters[counter] += int(
                        (rows * sched_live).sum())
        else:
            blocks = np.full(sched_live.shape, self._attn_total_blocks,
                             np.int64)
        self.counters["decode_blocks_read"] += int(
            (blocks * sched_live).sum())

    def _init_layout(self):
        """Cache-layout hook (overridden by the paged server): the
        contiguous layout reserves ``slots x max_seq`` rows."""
        jax = self._jax

        self._refuse_unsupported(contiguous_layout=True)
        self.cache = self._model.init_cache(
            self.config, self.slots, self.max_seq,
            quantize_kv=self.quantize_kv)
        if self._mesh is not None:
            # Contiguous layout under a replica mesh: weights are
            # sharded (output axis), cache/state replicated, and the
            # existing jitted programs run under GSPMD — XLA inserts
            # the activation all-gathers.  The paged layout instead
            # uses the explicit shard_map TPEngine (pool sharding).
            self.cache = self._llama_tp.replicate(self.cache,
                                                  self._mesh)

        @functools.partial(jax.jit, donate_argnames=("cache",),
                           static_argnames=("padded",))
        def insert_slots(cache, bucket_cache, slot_rows, padded):
            """Land a (k, padded, …) prefilled bucket batch in the k
            rows named by ``slot_rows`` (rows past each prompt hold
            pad garbage; each is rewritten by the decode step that
            first makes it attendable) — ONE dispatch per admission
            sub-batch instead of one per admission."""
            new_cache = []
            for cache_layer, filled in zip(cache, bucket_cache):
                layer = {}
                for key in cache_layer:
                    dst = cache_layer[key]
                    layer[key] = dst.at[slot_rows, :padded].set(
                        filled[key].astype(dst.dtype))
                new_cache.append(layer)
            return new_cache

        self._insert_slots = insert_slots

    def _init_spec(self, draft_mode: str, spec_k: int, spec_ladder,
                   spec_adaptive: bool, automata) -> None:
        """Speculation v2 policy wiring (after ``_init_layout`` — the
        ladder validates against the FINAL prompt-bucket floor, which
        the paged layout raises to ``block_size``).  Three proposers
        share one verify/accept/commit path:

        * ``model`` — the PR-10 paired draft (``draft_config_name``);
        * ``ngram`` — model-free self-drafting: suffix-match proposals
          from each slot's own committed history, assembled host-side;
        * grammar jump-forward — ``automata`` registers named
          :class:`~..models.constrained.TokenAutomaton` grammars;
          requests naming one get masked free tokens and deterministic
          segments committed as speculation windows.

        ``draft_mode="auto"`` resolves to ``model`` when a draft is
        configured, else ``ngram``; speculation is OFF only when no
        draft, no explicit ngram, and no automata are given."""
        spec_on = (self._draft is not None
                   or draft_mode in ("ngram", "model")
                   or bool(automata))
        if not spec_on:
            if draft_mode not in ("auto", "model", "ngram"):
                raise ValueError(
                    f"draft_mode must be 'model', 'ngram' or 'auto', "
                    f"got {draft_mode!r}")
            return
        mode = draft_mode
        if mode == "auto":
            mode = "model" if self._draft is not None else "ngram"
        if mode not in ("model", "ngram"):
            raise ValueError(
                f"draft_mode must be 'model', 'ngram' or 'auto', got "
                f"{draft_mode!r}")
        if mode == "model" and self._draft is None:
            raise ValueError(
                "draft_mode='model' requires draft_config_name=")
        if mode == "ngram" and self._draft is not None:
            raise ValueError(
                "draft_mode='ngram' does not take draft_config_name= "
                "(the slot's own committed history is the draft)")
        from .spec_control import (SpecController, default_ladder,
                                   validate_ladder)
        ladder = (tuple(int(k) for k in spec_ladder)
                  if spec_ladder is not None
                  else default_ladder(int(spec_k)))
        ladder = validate_ladder(ladder, self._bucket_minimum)
        if ladder[-1] < 1:
            raise ValueError(
                f"spec ladder {ladder} has no usable rung: the top "
                "rung must be >= 1 (k=0 alone is just plain decode)")
        controller = (SpecController(self.slots, ladder)
                      if spec_adaptive else None)
        self._spec = dict(mode=mode, k=int(ladder[-1]), ladder=ladder,
                          controller=controller,
                          adaptive=bool(spec_adaptive))
        from ..models.speculative import SpecStats
        self.spec_stats = SpecStats()
        if automata:
            from ..models.constrained import stack_automata
            table = stack_automata(dict(automata))
            if table.vocab != self.config.vocab_size:
                raise ValueError(
                    f"automata vocab {table.vocab} != model vocab "
                    f"{self.config.vocab_size}")
            allowed = self._jnp.asarray(table.allowed)
            if self._mesh is not None:
                allowed = self._llama_tp.replicate(allowed, self._mesh)
            self._automata = dict(table=table, allowed=allowed)
            #: per-slot GLOBAL automaton state; -1 = unconstrained.
            self._autostates = np.full(self.slots, -1, np.int64)

    # ------------------------------------------------------------- #

    def submit(self, request: DecodeRequest) -> None:
        request.tokens = []
        request.submitted_ts = time.monotonic()
        if request.deadline_ts is not None \
                and request.submitted_ts >= request.deadline_ts:
            # Expired on arrival (queueing upstream, transit): never
            # admit work whose answer nobody is waiting for.
            self._finish_rejected(request, "deadline_exceeded")
            return
        if not self.healthy:
            # Tripped watchdog: the router re-dispatches on this error.
            self._finish_rejected(request, "watchdog_stalled")
            return
        if self.max_queue is not None \
                and len(self._queue) >= self.max_queue:
            request.retry_after_ms = self._retry_after_ms()
            self._finish_rejected(request, "overloaded")
            return
        prompt_len = int(np.asarray(request.prompt).shape[0])
        reason = self._admission_reject(prompt_len, request)
        if reason:
            request.error = reason
            self.completed.append(request)
            return
        self._queue.append(request)

    def _finish_rejected(self, request: DecodeRequest,
                         reason: str) -> None:
        """Terminal admission rejection on the robustness paths —
        counted, stamped, and flowed out through the normal completion
        list (the replica publishes it like any other response)."""
        request.error = reason
        request.finished_ts = time.monotonic()
        if reason == "deadline_exceeded":
            self.counters["deadline_exceeded"] += 1
        elif reason == "overloaded":
            self.counters["shed"] += 1
        self.completed.append(request)

    def _retry_after_ms(self) -> int:
        """Shed hint: scale with how far over capacity we are — a
        saturated queue at 2× capacity hints twice the wait of one at
        1×.  Coarse by design; clients jitter their own retries."""
        depth = len(self._queue)
        per_request_ms = 50
        return int(min(5_000, per_request_ms * max(1, depth)))

    def _admission_reject(self, prompt_len: int,
                          request: DecodeRequest) -> Optional[str]:
        """Reject hook: a non-None reason fails the request at submit
        time (never queue what can never run — a deferred-forever head
        request would starve the whole FIFO)."""
        if prompt_len == 0:
            # There is no last prompt token to seed the slot with; an
            # empty prompt would decode an all-pad bucket into
            # plausible-looking garbage.
            return "empty_prompt"
        if prompt_len + request.max_new_tokens > self.max_seq - 1:
            return "prompt_too_long"
        if request.adapter is not None \
                and request.adapter not in self._adapter_index:
            return "unknown_adapter"
        asked = (request.denoise_steps, request.denoise_rule,
                 request.denoise_threshold)
        if any(value is not None for value in asked):
            if not self._block_length:
                return "no_block_passes"
            if request.denoise_steps is not None and not (
                    1 <= request.denoise_steps <= self._block_length):
                return "bad_denoise_steps"
            if request.denoise_rule not in (None, "static", "dynamic"):
                return "bad_denoise_rule"
        if self._block_length and prompt_len + request.max_new_tokens \
                + self._block_length > self.max_seq:
            # The answer's last block is generated whole and cut.
            return "prompt_too_long"
        if request.automaton is not None \
                and (self._automata is None
                     or request.automaton
                     not in self._automata["table"].offsets):
            return "unknown_automaton"
        if self._spec is not None:
            if prompt_len + request.max_new_tokens \
                    + self._spec["k"] + 1 > self.max_seq:
                # Speculation writes k rows past the live position;
                # without this headroom the verify slab's clamped
                # write would corrupt committed rows.  Bounded by the
                # ladder TOP — adaptivity can only narrow.
                return "prompt_too_long"
        return None

    def live_requests(self) -> List[DecodeRequest]:
        """Requests currently holding a decode slot (streaming
        delivery and operator introspection)."""
        return [r for r in self._requests if r is not None]

    @property
    def slots_active(self) -> int:
        """Live decode lanes (operator telemetry)."""
        return len(self.live_requests())

    @property
    def queue_depth(self) -> int:
        """Requests awaiting a slot (operator telemetry)."""
        return len(self._queue)

    @property
    def busy(self) -> bool:
        # Prefilling slots hold their request in _requests, so
        # slots_active covers chunked admissions too; in-flight ring
        # entries carry undelivered tokens even after every slot's
        # final chunk has been dispatched.
        return bool(self._queue) or self.slots_active > 0 \
            or bool(self._ring)

    #: ``arrival_hold_s``: the share of the host's last waits on a
    #: chunk that its loop may spend listening, and the most it may.
    ARRIVAL_HOLD_SHARE = 0.125
    ARRIVAL_HOLD_MAX_S = 0.010

    def arrival_hold_s(self) -> float:
        """How long the caller's loop may go on taking messages before
        its next :meth:`step`, at no cost to the device.

        ``step()`` admits what has arrived, commits the next chunk and
        then blocks on the running one, so a request that reaches the
        loop just after ``_admit`` waits a whole chunk more than one
        just before it.  Straight after a step that left a chunk in
        flight that chunk has only begun, and the next dispatch is not
        due until it ends: an eighth of what the host lately waited on
        a chunk (at most 10 ms) is slack, and listening through it
        takes the cut-off away from the chunk's own end, which is
        where a caller that sends on a completion, or on a schedule
        the chunks happen to beat against, arrives (my chip runs,
        PR 29: a request due 0.2 to 3.1 ms after a chunk's end made or
        missed the 1 ms the loop then listened, run by run, and
        ``ttft_p50_ms`` read 207 or 233).  0.0 with nothing in flight:
        an idle device must not wait."""
        if not self._ring or self._ema_wait_ms is None:
            return 0.0
        return min(self.ARRIVAL_HOLD_MAX_S,
                   self.ARRIVAL_HOLD_SHARE * self._ema_wait_ms / 1e3)

    def _admit(self) -> None:
        span = None
        if steplog.RECORDER is not None:
            span = steplog.RECORDER.begin("admission")
        admissions = []
        for slot in range(self.slots):
            if self._requests[slot] is not None or not self._queue:
                continue
            request = self._queue[0]
            prompt = np.asarray(request.prompt, np.int32)[None, :]
            prompt_len = prompt.shape[1]
            # Clamp the bucket to the cache: a prompt near max_seq must
            # not prefill a bucket larger than the slot rows.
            padded = min(_bucket(prompt_len, self._bucket_minimum),
                         self.max_seq)
            if not self._reserve_slot(slot, padded, request):
                self.counters["admission_deferred"] += 1
                break      # capacity (paged pool) exhausted; next chunk
            self._queue.pop(0)
            request.activated_ts = time.monotonic()
            self.counters["prompt_tokens"] += \
                prompt_len - request.shared_tokens
            prompt_padded = np.zeros((1, padded), np.int32)
            prompt_padded[:, :prompt_len] = prompt
            if self.chunk_prefill_tokens \
                    and prompt_len > self.chunk_prefill_tokens:
                # Chunked admission: the slot is OCCUPIED (queued
                # requests cannot take it) but not yet active —
                # chunks are fed one per step between the running
                # slots' decode runs (standalone _advance_prefills
                # here; folded into the mixed decode dispatch on the
                # paged backend).
                self._requests[slot] = request
                self._begin_chunked_prefill(slot, request,
                                            prompt_padded, prompt_len)
                continue
            admissions.append((slot, request, prompt_padded, prompt_len))
        if span is not None:
            # The slot scan alone; the wave's prefills and activations
            # are phases of their own below.
            if admissions or self._prefilling:
                span.end(slots=len(admissions),
                         chunked=len(self._prefilling))
            else:
                span.drop()
        if not admissions:
            return
        self._prefill_and_insert(admissions)
        for slot, request, prompt_padded, prompt_len in admissions:
            self._activate_slot(slot, request, prompt_padded,
                                prompt_len)
        # The wave's LAST prefill is still in flight here (nothing
        # blocks on it); on a one-in-flight backend the next decode
        # dispatch absorbs its compute.  Flag it so attribution can
        # file that gap under admission, not the decode loop.
        self._post_admission = True

    def _activate_slot(self, slot: int, request, prompt_padded,
                       prompt_len: int) -> None:
        """Seed a prefilled slot for decode — with the LAST prompt
        token at its own position: the next chunk's first step
        re-writes that KV row with identical values and emits the
        first generated token.  The ONE activation path for both
        whole-bucket and chunked admission."""
        span = None
        if steplog.RECORDER is not None:
            span = steplog.RECORDER.begin("sampling_edit", slot=slot)
        request.decode_ready_ts = time.monotonic()
        self.tokens[slot, 0] = prompt_padded[0, prompt_len - 1]
        self.positions[slot] = prompt_len - 1
        self.active[slot] = True
        self._adapter_ids[slot] = self._adapter_id(request)
        self._temperatures[slot] = max(0.0, float(request.temperature))
        self._top_ps[slot] = float(request.top_p)
        self._requests[slot] = request
        self._emitted[slot] = 0
        self._remaining[slot] = request.max_new_tokens
        self._inflight_sched[slot] = 0
        self._slot_serial[slot] += 1
        self._dirty[slot] = True
        self._any_sampled = bool((self._temperatures > 0).any())
        if self._block_length:
            self._open_first_block(slot, request, prompt_padded,
                                   prompt_len)
        if self._spec is not None \
                and self._spec["controller"] is not None:
            # New occupant: forget the previous request's acceptance
            # history (optimistic start at the ladder top).
            self._spec["controller"].reset(slot)
        if self._automata is not None:
            name = request.automaton
            self._autostates[slot] = (
                self._automata["table"].start(name)
                if name is not None else -1)
        if span is not None:
            span.end(temperature=float(request.temperature),
                     top_p=float(request.top_p))

    def _open_first_block(self, slot: int, request, prompt_padded,
                          prompt_len: int) -> None:
        """Generation by block passes: seed the slot with the first
        block it generates.  The prompt's whole blocks are in the
        cache; its last ``prompt_len mod block`` tokens open the block
        as given (its passes write their rows again), the rest of it
        is masked.  The schedule is the request's, else the
        config's."""
        B, state, config = self._block_length, self._block_state, \
            self.config
        base = prompt_len // B * B
        given = prompt_len - base
        self.positions[slot] = base
        state["window"][slot] = 0
        state["window"][slot, :given] = prompt_padded[0, base:prompt_len]
        state["masked"][slot] = np.arange(B) >= given
        state["delivered"][slot] = given
        state["passes"][slot] = 0
        state["denoise_steps"][slot] = (
            config.denoise_steps if request.denoise_steps is None
            else request.denoise_steps)
        state["dynamic"][slot] = (
            config.denoise_dynamic if request.denoise_rule is None
            else request.denoise_rule == "dynamic")
        state["threshold"][slot] = (
            config.denoise_threshold
            if request.denoise_threshold is None
            else request.denoise_threshold)

    def _begin_chunked_prefill(self, slot: int, request, prompt_padded,
                               prompt_len: int) -> None:
        """Layout hook: open a chunked admission for ``slot``.  The
        contiguous layout prefills into a private batch-1 bucket that
        :func:`_finish_prefill` seals into the slot cache; the paged
        server overrides this to append straight into the slot's
        block chain (no bucket ever exists)."""
        self._prefilling[slot] = dict(
            request=request, prompt_padded=prompt_padded,
            prompt_len=prompt_len, start=0,
            lora=self._request_lora(request),
            bucket=self._model.init_cache(
                self.config, 1, prompt_padded.shape[1],
                quantize_kv=self.quantize_kv))

    def _advance_prefills(self) -> None:
        """Run ONE prefill chunk for every in-progress chunked
        admission; a slot whose chunks now cover its whole prompt is
        sealed into the main cache and becomes decode-active."""
        jnp = self._jnp
        for slot in list(self._prefilling):
            state = self._prefilling[slot]
            start = state["start"]
            size = min(self.chunk_prefill_tokens,
                       state["prompt_padded"].shape[1] - start)
            chunk = state["prompt_padded"][:, start:start + size]
            self._note_prefill(size, (state["request"],), sliced=True)
            _, state["bucket"] = self._model.prefill_chunk(
                self.params, jnp.asarray(chunk), state["bucket"],
                jnp.int32(start), self.config, lora=state["lora"])
            state["start"] = start + size
            if state["start"] >= state["prompt_len"]:
                # Rows past prompt_len stay zero-initialized — exactly
                # as unattendable as the whole-prefill path's
                # pad-garbage rows (absolute-position masking).
                self._finish_prefill(slot, state)

    def _finish_prefill(self, slot: int, state: Dict) -> None:
        jnp = self._jnp
        self.cache = self._insert_slots(
            self.cache, state["bucket"],
            jnp.asarray(np.asarray([slot], np.int32)),
            state["prompt_padded"].shape[1])
        del self._prefilling[slot]
        if self._draft is not None:
            # The draft needs the SAME committed history before the
            # slot's first spec round.  Whole-prompt in one dispatch:
            # the draft is small by construction, so this does not
            # reintroduce the batch stall chunked admission removes.
            self._prefill_draft_rows([slot], state["prompt_padded"])
        self._activate_slot(slot, state["request"],
                            state["prompt_padded"],
                            state["prompt_len"])

    def _prefill_and_insert(self, admissions) -> None:
        """Admission-group hook.  Contiguous layout: group admissions
        by bucket size, prefill each group batched (causal attention
        keeps every row's numerics independent of its batch-mates),
        and land each batch with ONE batched scatter — dispatch count
        per admission wave drops from 2 × admissions to ~2 × distinct
        bucket sizes.  Groups split into power-of-2 sub-batches so the
        compile-shape count stays bounded at log2(slots) × n_buckets
        (a compile mid-traffic stalls every slot; same pow2 discipline
        as the prompt buckets themselves).  (The paged server overrides this
        with its per-slot prefix-cache walk.)"""
        jnp = self._jnp
        groups: Dict[int, List] = {}
        for slot, request, prompt_padded, prompt_len in admissions:
            adapter_id = self._adapter_id(request)
            groups.setdefault(prompt_padded.shape[1], []).append(
                (slot, prompt_padded, adapter_id, request))
        for padded, group in groups.items():
            start = 0
            while start < len(group):
                # Largest power of two <= the remaining group.
                size = 1 << ((len(group) - start).bit_length() - 1)
                sub = group[start:start + size]
                start += size
                slots = [slot for slot, _, _, _ in sub]
                prompts = np.concatenate([p for _, p, _, _ in sub],
                                         axis=0)
                self._note_prefill(len(sub) * padded,
                                   [request for _, _, _, request in sub])
                if compiles.LEDGER is not None:
                    # Shape-bucket signature: any compile with a
                    # signature OUTSIDE the pow2 grid is a bucket-
                    # discipline breach (the ledger's log-bound test).
                    compiles.set_label("prefill",
                                       f"b{padded}x{len(sub)}")
                # The prompt KV must be built under the SAME adapter
                # the decode chunks will run (None for all-base).
                lora = self._make_lora([aid for _, _, aid, _ in sub])
                bucket_cache = self._model.init_cache(
                    self.config, len(sub), padded,
                    quantize_kv=self.quantize_kv)
                _, bucket_cache = self._model.prefill(
                    self.params, jnp.asarray(prompts), bucket_cache,
                    self.config, lora=lora)
                slot_rows = jnp.asarray(np.asarray(slots, np.int32))
                self.cache = self._insert_slots(
                    self.cache, bucket_cache, slot_rows, padded)
                if self._draft is not None:
                    # The draft needs the SAME committed history: its
                    # prompt KV lands in its own slot cache alongside.
                    self._prefill_draft_rows(slots, prompts)

    def _prefill_draft_rows(self, slots_list, prompts) -> None:
        """Land the draft model's prompt KV for ``slots_list`` (its
        contiguous per-slot cache rows), batched.  The ONE draft
        admission path shared by every layout and admission mode:
        whole-bucket waves, chunked-admission finishes, and the paged
        server's per-request appends all funnel here — the draft has
        no prefix cache and no pool, so it always prefills the whole
        padded prompt regardless of what the target reused."""
        draft, jax, jnp = self._draft, self._jax, self._jnp
        if "insert" not in draft:
            # Same insert-batch closure as the contiguous target
            # layout, built lazily because the paged server's
            # _init_layout never creates one.
            @functools.partial(jax.jit, donate_argnames=("cache",),
                               static_argnames=("padded",))
            def draft_insert(cache, bucket_cache, slot_rows, padded):
                new_cache = []
                for cache_layer, filled in zip(cache, bucket_cache):
                    layer = {}
                    for key in cache_layer:
                        dst = cache_layer[key]
                        layer[key] = dst.at[slot_rows, :padded].set(
                            filled[key].astype(dst.dtype))
                    new_cache.append(layer)
                return new_cache

            draft["insert"] = draft_insert
        padded = prompts.shape[1]
        if compiles.LEDGER is not None:
            compiles.set_label("draft_prefill",
                               f"b{padded}x{len(slots_list)}")
        bucket = self._llama.init_cache(draft["config"],
                                        len(slots_list), padded)
        _, bucket = self._llama.prefill(
            draft["params"], jnp.asarray(prompts), bucket,
            draft["config"])
        slot_rows = jnp.asarray(np.asarray(slots_list, np.int32))
        draft["cache"] = draft["insert"](draft["cache"], bucket,
                                         slot_rows, padded)

    def _reserve_slot(self, slot: int, padded: int, request) -> bool:
        """Capacity hook: claim layout resources for an admission.
        Contiguous layout always has room (the slot IS the room)."""
        return True

    def _adapter_id(self, request) -> int:
        """Stacked-factor index for a request (0 = base identity;
        unknown names are rejected at submit)."""
        return self._adapter_index.get(request.adapter, 0)

    @property
    def adapters_loaded(self) -> List[str]:
        """Names currently servable (operator telemetry)."""
        return sorted(self._adapter_index)

    def adapter_slot_counts(self) -> Dict[str, int]:
        """name -> decode slots currently pinned to that adapter
        (dashboard pane + pool census; host-side reads only)."""
        if not self._adapter_index:
            return {}
        slot_ids = np.asarray(self._adapter_ids).reshape(-1)
        return {name: int(np.sum(slot_ids == index))
                for name, index in sorted(self._adapter_index.items())}

    def _adapter_users(self, name: str) -> int:
        """Requests pinning adapter ``name`` — by NAME, not stacked
        index: a chunk-prefilling slot holds its request before
        ``_activate_slot`` assigns the id, and queued requests have no
        slot at all, yet both will decode under the name."""
        live = sum(1 for r in self._requests
                   if r is not None and r.adapter == name)
        return live + sum(1 for r in self._queue if r.adapter == name)

    def _adapter_load_counter(self, kind: str):
        """Lazily-created ``aiko_adapter_loads_total{kind=}`` mirror
        of the warm/cold load attributes (lazy so base-model servers
        never emit the series)."""
        counters = getattr(self, "_adapter_load_counters", None)
        if counters is None:
            counters = self._adapter_load_counters = {}
        counter = counters.get(kind)
        if counter is None:
            counter = REGISTRY.counter(
                "aiko_adapter_loads_total",
                "adapter hot-deploys by provenance (warm = restacked "
                "from a paged pool copy, cold = client-uploaded "
                "factor bytes)",
                labels=dict(self._metrics_labels, kind=kind))
            counters[kind] = counter
        return counter

    def load_adapter(self, name: str, lora_params=None,
                     lora_config=None) -> None:
        """Register (or replace) a LoRA adapter at RUNTIME — deploy a
        new fine-tune without restarting the replica.  The first load
        on an adapter-less server defines the shared LoRAConfig; later
        loads must match it (one stacked shape per server).  Replacing
        a name requires no live request on it (``adapter_busy``).

        ``lora_params=None`` is a WARM load: the factors restack from
        the replica's paged adapter storage (any tier — the shared
        pool keeps unloaded adapters warm) with no client re-upload;
        ``KeyError`` when no paged copy survives (``adapter_cold``)."""
        from ..models import lora as lora_mod
        jnp = self._jnp

        if lora_params is None:
            fetched = self._fetch_adapter_pages(name)
            if fetched is None:
                raise KeyError(f"adapter_cold: no paged copy of "
                               f"{name!r} to warm-load")
            lora_params, paged_config = fetched
            if lora_config is None:
                lora_config = paged_config
            self.adapter_warm_loads += 1
            self._adapter_load_counter("warm").inc()
        else:
            self.adapter_cold_loads += 1
            self._adapter_load_counter("cold").inc()

        if self._lora_config is None:
            if lora_config is None:
                raise ValueError("first load_adapter needs lora_config")
            # Committed only after stack_adapters validates it below —
            # a failed first load must not wedge the server with a
            # config that never actually loaded.
        elif lora_config is not None and (
                lora_config.rank != self._lora_config.rank
                or set(lora_config.targets)
                != set(self._lora_config.targets)
                or lora_config.alpha != self._lora_config.alpha):
            # Targets compare as SETS: PEFT serializes target_modules
            # from a set, so order varies while the stacked layout
            # (keyed by target name) is unaffected.
            # The stacked scale (= alpha/rank) is shared server-wide;
            # a mismatched adapter would serve at the wrong scale.
            raise ValueError(
                f"adapter {name!r} config (rank {lora_config.rank}, "
                f"alpha {lora_config.alpha}, targets "
                f"{lora_config.targets}) does not match the server's "
                f"(rank {self._lora_config.rank}, alpha "
                f"{self._lora_config.alpha}, targets "
                f"{self._lora_config.targets})")
        # Direct-API callers may omit the config (the wire path always
        # supplies one); stack_adapters below shape-verifies every
        # factor against the server's config — but alpha is NOT
        # recoverable from the weights, so an adapter trained at a
        # different alpha with matching shapes MUST pass its config to
        # be rejected; omitting it asserts the server's scale.
        candidate_config = self._lora_config or lora_config
        stacked_one = lora_mod.stack_adapters(
            self.config, candidate_config, [lora_params])
        self._lora_config = candidate_config
        if self._lora_shared is None:
            self._lora_shared = self._place_lora(stacked_one)
            self._adapter_index[name] = 1
            self._register_adapter_pages(name, lora_params)
            return
        existing = self._adapter_index.get(name)
        if existing is not None:
            if self._adapter_users(name):
                raise ValueError(f"adapter_busy: {name!r} has live "
                                 "requests")
            index = existing
            # New weights under an old id: cached prompt KV built with
            # the previous weights must not be served (paged prefix
            # cache keys carry the numeric id).
            self._invalidate_adapter_cache(index)
        elif self._free_adapter_ids:
            index = self._free_adapter_ids.pop()
        else:
            index = None           # append (stack widens; recompile)
        new_layers = []
        for layer, one in zip(self._lora_shared["layers"],
                              stacked_one["layers"]):
            merged = {}
            for target, factors in layer.items():
                fresh = one[target]
                if index is None:
                    merged[target] = {
                        "a": jnp.concatenate(
                            [factors["a"], fresh["a"][1:]]),
                        "b": jnp.concatenate(
                            [factors["b"], fresh["b"][1:]]),
                    }
                else:
                    merged[target] = {
                        "a": factors["a"].at[index].set(fresh["a"][1]),
                        "b": factors["b"].at[index].set(fresh["b"][1]),
                    }
            new_layers.append(merged)
        self._lora_shared = self._place_lora(
            {"scale": self._lora_shared["scale"],
             "layers": new_layers})
        if index is None:
            index = self._lora_shared["layers"][0][
                next(iter(new_layers[0]))]["a"].shape[0] - 1
        self._adapter_index[name] = index
        self._register_adapter_pages(name, lora_params)

    def unload_adapter(self, name: str) -> None:
        """Remove a served adapter; its stacked index is zeroed and
        recycled (no recompile).  Requires no live request on it.
        Paged adapter storage is deliberately NOT dropped: the pages
        stay resident under the shared eviction clock, so a future
        ``load_adapter(name)`` warm-loads with no re-upload."""
        jnp = self._jnp
        index = self._adapter_index.get(name)
        if index is None:
            raise KeyError(name)
        if self._adapter_users(name):
            raise ValueError(f"adapter_busy: {name!r} has live "
                             "requests")
        new_layers = []
        for layer in self._lora_shared["layers"]:
            merged = {}
            for target, factors in layer.items():
                merged[target] = {
                    "a": factors["a"].at[index].set(
                        jnp.zeros_like(factors["a"][index])),
                    "b": factors["b"].at[index].set(
                        jnp.zeros_like(factors["b"][index])),
                }
            new_layers.append(merged)
        self._lora_shared = self._place_lora(
            {"scale": self._lora_shared["scale"],
             "layers": new_layers})
        del self._adapter_index[name]
        # The id will be recycled: stale cached KV under it must go
        # before a future adapter can collide with its chain keys.
        self._invalidate_adapter_cache(index)
        self._free_adapter_ids.append(index)

    def _invalidate_adapter_cache(self, index: int) -> None:
        """Layout hook: drop any cached state keyed by this stacked
        adapter id (the paged prefix cache overrides this; the
        contiguous layout caches nothing across requests)."""

    def _register_adapter_pages(self, name: str, adapter) -> int:
        """Layout hook: mirror a loaded adapter's factors into paged
        storage so it stays warm across unloads (the paged layout
        overrides this; the contiguous layout has no pool)."""
        return 0

    def _fetch_adapter_pages(self, name: str):
        """Layout hook: recover ``(lora_params, LoRAConfig)`` for a
        previously paged adapter, or None when cold (the paged layout
        overrides this; the contiguous layout never pages)."""
        return None

    def _place_lora(self, lora_shared):
        """Layout hook: place the stacked adapter tree for the serving
        programs.  Single chip: host tree as-is.  Contiguous layout
        under a replica mesh: REPLICATE the factors — the GSPMD
        programs then compute every rank-r delta identically on each
        device (exact; the factors are tiny).  The paged layout
        overrides with the TPEngine's explicit column sharding
        (:func:`~..models.llama_tp.shard_lora`)."""
        if lora_shared is not None and self._mesh is not None:
            return self._llama_tp.replicate(lora_shared, self._mesh)
        return lora_shared

    def _make_lora(self, ids):
        """Assemble the batched lora argument for per-row adapter
        ``ids`` — or None when no row actually runs an adapter, so
        all-base traffic keeps the adapter-free compiled program (no
        gather/einsum work; the same discipline ``_any_sampled``
        applies to sampling math)."""
        ids = np.asarray(ids, np.int32)
        if self._lora_shared is None or not ids.any():
            return None
        return dict(ids=self._jnp.asarray(ids), **self._lora_shared)

    def _request_lora(self, request):
        """Batch-1 lora argument for a single request's prefill (the
        paged per-slot admission path)."""
        return self._make_lora([self._adapter_id(request)])

    def _prefill_bucket(self, slot: int, prompt_padded,
                        prompt_len: int, lora=None):
        """Prefill hook: run the padded prompt into a fresh batch-1
        bucket cache.  Used by the PAGED server's cache-miss path (its
        prefix-cache walk is per-slot); the contiguous layout itself
        admits through the batched ``_prefill_and_insert``."""
        model, jnp = self._model, self._jnp
        bucket_cache = model.init_cache(
            self.config, 1, prompt_padded.shape[1],
            quantize_kv=self.quantize_kv)
        _, bucket_cache = model.prefill(
            self.params, jnp.asarray(prompt_padded), bucket_cache,
            self.config, lora=lora)
        return bucket_cache

    def _release_slot(self, slot: int) -> None:
        """Layout hook: return a retiring slot's resources."""

    def _retire(self, slot: int) -> None:
        request = self._requests[slot]
        if request is not None:
            request.finished_ts = time.monotonic()
            self.completed.append(request)
        self._release_slot(slot)
        self._requests[slot] = None
        self.active[slot] = False
        self._adapter_ids[slot] = 0
        self._remaining[slot] = 0
        self._inflight_sched[slot] = 0
        # Bump the admission generation: any still-in-flight entry's
        # data for this slot is now stale and will be skipped.
        self._slot_serial[slot] += 1
        self._dirty[slot] = True
        if self._autostates is not None:
            self._autostates[slot] = -1
        # Reset sampling state so an all-greedy batch returns to the
        # pure-greedy compiled program (no sort/softmax per step).
        self._temperatures[slot] = 0.0
        self._top_ps[slot] = 1.0
        self._any_sampled = bool((self._temperatures > 0).any())

    def update_sampling(self, request_id: str,
                        temperature: Optional[float] = None,
                        top_p: Optional[float] = None,
                        max_new_tokens: Optional[int] = None) -> bool:
        """Edit a live (or still-queued) request's sampling params /
        decode budget in place — DEVICE-RESIDENT: for a live slot the
        edit updates the host mirrors, marks the slot sampling-dirty,
        and rides the next dispatch's compact packet — uploading ONLY
        the sampling leaves, because the slot may have chunks in
        flight whose progress leaves the host cannot mirror yet.  No
        full-mirror upload, no dedicated round trip.  Edits take
        effect from the next dispatched chunk (chunks already in
        flight keep the params they were dispatched with).

        Budget edits additionally drain the in-flight ring first: the
        device's resident ``remaining`` counter must be rebased
        against a settled ``emitted`` count, and an in-flight chunk
        retiring the lane under the OLD budget while the packet
        revives it would strand the slot.  A new budget at or below
        the tokens already emitted retires the request immediately
        (finished, no error).  Returns False for an unknown id."""
        for request in self._queue:
            if request.request_id == request_id:
                if temperature is not None:
                    request.temperature = float(temperature)
                if top_p is not None:
                    request.top_p = float(top_p)
                if max_new_tokens is not None:
                    request.max_new_tokens = int(max_new_tokens)
                return True
        for slot in range(self.slots):
            request = self._requests[slot]
            if request is None or request.request_id != request_id:
                continue
            if max_new_tokens is not None:
                self._drain_ring()
                if self._requests[slot] is not request:
                    return True    # finished naturally while draining
                request.max_new_tokens = int(max_new_tokens)
                if request.max_new_tokens <= self._emitted[slot]:
                    self._prefilling.pop(slot, None)
                    self._retire(slot)
                    return True
                self._remaining[slot] = (request.max_new_tokens
                                         - self._emitted[slot])
            if temperature is not None:
                request.temperature = float(temperature)
                self._temperatures[slot] = max(
                    0.0, float(temperature))
            if top_p is not None:
                request.top_p = float(top_p)
                self._top_ps[slot] = float(top_p)
            if max_new_tokens is not None:
                # The ring is drained (above): the mirrors are exact,
                # so the full-row structural upload is safe — and the
                # rebased ``remaining`` must reach the device.
                self._dirty[slot] = True
            elif self.compact_upload:
                # Sampling-only edit on a slot that may have chunks in
                # flight: a full-row upload would stomp the device's
                # progress leaves (token/positions/remaining) with
                # stale mirrors — ride the sampling-leaf scatter.
                self._dirty_sampling[slot] = True
            else:
                # Legacy full-mirror merge has no per-leaf mask:
                # settle the ring so the mirrors are exact first.
                self._drain_ring()
                if self._requests[slot] is not request:
                    return True     # finished while settling
                self._dirty[slot] = True
            self._any_sampled = bool((self._temperatures > 0).any())
            if steplog.RECORDER is not None:
                steplog.RECORDER.record(
                    "sampling_edit", slot=slot,
                    temperature=float(self._temperatures[slot]),
                    top_p=float(self._top_ps[slot]))
            return True
        return False

    def cancel(self, request_id: str) -> bool:
        """Cancel by id, wherever the request currently lives: queued
        (dropped), chunk-prefilling (admission aborted, slot freed), or
        decoding (retired early, partial tokens kept).  The request
        completes with ``error="cancelled"`` and flows out through the
        normal completion path.  Returns False for an unknown id."""
        for i, request in enumerate(self._queue):
            if request.request_id == request_id:
                self._queue.pop(i)
                request.error = "cancelled"
                request.finished_ts = time.monotonic()
                self.completed.append(request)
                return True
        for slot in range(self.slots):
            request = self._requests[slot]
            if request is None or request.request_id != request_id:
                continue
            if slot not in self._prefilling:
                # Decoding: drain the in-flight ring FIRST so chunks
                # already dispatched deliver their partial tokens and
                # the device provably stops touching this lane before
                # its resources (paged blocks) are freed for reuse.
                self._drain_ring()
                if self._requests[slot] is not request:
                    return True      # finished naturally while draining
            request.error = "cancelled"
            self._prefilling.pop(slot, None)
            self._retire(slot)
            return True
        return False

    def step(self) -> List[DecodeRequest]:
        """Admit pending requests, keep the in-flight ring full, apply
        one (or, at the drain tail, every) completed chunk's results,
        retire finished slots.  Returns (and clears) the completed
        list.

        Async double-buffering: dispatch fills the ring to the
        adaptive depth (``ring_min = max(2, lookahead)`` floor, widened
        toward ``ring_max`` by ``_ring_policy`` while the device runs
        dry), then consume drains it to depth-1 in ONE batched pass —
        so in steady state every ``step()`` launches the next chunk
        BEFORE blocking on the previous one's (tiny) result, and the
        device never idles on host bookkeeping.  When nothing can be
        dispatched (all budgets scheduled, or no live slot) the ring is
        drained completely so results are never stranded."""
        self._evict_expired()
        self._admit()
        self._advance_prefills()
        if profiler.PROFILER is not None \
                and profiler.PROFILER.wants(id(self)):
            # On-demand device profiling: the FIRST engine whose step
            # loop sees a pending session claims it (jax.profiler is
            # process-global) and runs its next N steps synchronously
            # inside the trace bracket — the one step mode where we
            # deliberately give up double-buffering, because the
            # timed dispatch→sync window is the real device ms the
            # attribution table wants.
            self._profiled_step()
            if self._watchdog_tripped:
                self._fail_all("watchdog_stalled")
            done, self.completed = self.completed, []
            return done
        if self.slots_active and not self._ring:
            # The device drained everything we ever handed it before
            # this host pass came back — a starvation marker the ring
            # controller turns into extra depth.
            self.counters["ring_starved_steps"] += 1
            self._starved_streak += 1
        else:
            self._starved_streak = 0
        depth = self._ring_depth
        dispatched = False
        while len(self._ring) < depth and self._dispatch_round():
            dispatched = True
        self._settle_heap()
        target = depth - 1 if dispatched else 0
        if len(self._ring) > target:
            self._consume_ready(len(self._ring) - target)
        self._ring_depth = self._ring_policy(
            depth, self.ring_min, self.ring_max, self._ema_wait_ms,
            self._ema_dispatch_ms, self._starved_streak)
        if self._watchdog_tripped:
            # A stalled device step already failed this batch's
            # guarantees — fail everything live/queued with the
            # retriable error so routers move the work, rather than
            # letting clients discover the wedge by timeout.
            self._fail_all("watchdog_stalled")
        done, self.completed = self.completed, []
        return done

    def _settle_heap(self) -> None:
        """Keep the collector off the compiled programs.  Tracing a
        serving program leaves hundreds of thousands of long-lived
        Python objects (jaxprs, avals, tracebacks: 320,000 for one
        mixed program of an 11-layer model, 0.12 s a full collection
        here), and a full collection walks all of them with the
        interpreter lock held: with thirty programs resident it
        stalled the engine loop, and so the device, for 1.7 to 3.0 s
        once or twice a run at 400 streamed partials a second (my chip
        runs, PR 26).  ``gc.freeze()`` moves everything alive into the
        permanent generation, which no collection visits.  It is
        called after a step in which the model module's jitted entry
        points traced a signature they did not hold (their caches
        grew: a count the engine reads, no clock), so at most once a
        program of the process, whichever server dispatched it first,
        and never once the programs are warm.  What else is alive at
        that moment is frozen with it: requests hold no cycles, so
        reference counts still free them, and a frozen cycle that
        dies stays until the process ends.  Not covered: programs of
        the shard_map engine (``replica_mesh``), which live in
        another module."""
        traced = sum(program._cache_size()
                     for program in self._model_programs)
        if traced != self._programs_settled:
            self._programs_settled = traced
            gc.freeze()
            self.counters["heap_freezes"] += 1

    def _evict_expired(self) -> None:
        """Deadline enforcement between chunks: drop expired queued
        requests, and evict live slots past deadline (draining the
        in-flight ring first, same discipline as :meth:`cancel`, so
        the device provably stops touching the lane before its
        resources are reused)."""
        now = time.monotonic()
        for index in reversed(range(len(self._queue))):
            request = self._queue[index]
            if request.deadline_ts is not None \
                    and now >= request.deadline_ts:
                self._queue.pop(index)
                request.error = "deadline_exceeded"
                request.finished_ts = now
                self.counters["deadline_exceeded"] += 1
                self.completed.append(request)
        expired = [slot for slot in range(self.slots)
                   if self._requests[slot] is not None
                   and self._requests[slot].deadline_ts is not None
                   and now >= self._requests[slot].deadline_ts]
        if not expired:
            return
        self._drain_ring()
        for slot in expired:
            request = self._requests[slot]
            if request is None or request.deadline_ts is None \
                    or time.monotonic() < request.deadline_ts:
                continue       # finished naturally while draining
            request.error = "deadline_exceeded"
            self.counters["deadline_exceeded"] += 1
            self._prefilling.pop(slot, None)
            self._retire(slot)

    def _fail_all(self, reason: str) -> None:
        """Fail every queued and live request with ``reason`` (the
        watchdog path — in-flight ring results are consumed first so
        partial tokens are preserved on the responses)."""
        self._drain_ring()
        now = time.monotonic()
        for request in self._queue:
            request.error = reason
            request.finished_ts = now
            self.completed.append(request)
        self._queue.clear()
        for slot in range(self.slots):
            if self._requests[slot] is not None:
                self._requests[slot].error = reason
                self._prefilling.pop(slot, None)
                self._retire(slot)

    def _plan_remaining(self) -> "np.ndarray":
        """Per-slot decode budget still UNSCHEDULED: max_new − emitted
        − in-flight.  A slot at zero needs no further dispatch — the
        chunks already in flight are guaranteed to finish it (the in-jit
        budget cap retires the lane the moment ``remaining`` hits 0)."""
        plan = np.zeros(self.slots, np.int64)
        for slot in range(self.slots):
            request = self._requests[slot]
            if request is None or not self.active[slot]:
                continue
            left = request.max_new_tokens - self._emitted[slot]
            if self._block_length:
                # In PASSES, and an upper bound: every block that
                # holds an undelivered token takes at most its denoise
                # passes and a store pass.  The device retires the
                # lane itself; a pass too many runs it as an idle row.
                B = self._block_length
                left = -(-(left + B - 1) // B) * (int(
                    self._block_state["denoise_steps"][slot]) + 1)
            plan[slot] = left - self._inflight_sched[slot]
        return plan

    @staticmethod
    def _ring_policy(depth: int, ring_min: int, ring_max: int,
                     wait_ema, dispatch_ema, starved_streak: int) -> int:
        """Adaptive ring-depth decision (pure, unit-tested): widen
        while the DEVICE is starved, shrink under HOST backlog, clamp
        to ``[ring_min, ring_max]``.

        Signals: ``wait_ema``/``dispatch_ema`` are EMAs of the ms the
        host blocked in a ring sync vs the ms a dispatch call took;
        ``starved_streak`` counts consecutive host passes that found
        the ring already empty with live slots.  Syncs returning
        near-instantly WHILE the ring keeps running dry means the
        device finished everything between host passes — queue more
        chunks ahead.  Syncs dwarfing dispatch cost means the device
        is saturated — extra depth buys nothing and delays every
        retire/admit decision by more in-flight chunks, so decay back
        toward the double-buffer floor."""
        if wait_ema is not None and dispatch_ema is not None \
                and dispatch_ema > 0.0:
            if starved_streak >= 2 and wait_ema < 0.25 * dispatch_ema:
                depth += 1
            elif wait_ema > 2.0 * dispatch_ema:
                depth -= 1
        return max(ring_min, min(ring_max, depth))

    def _dispatch_round(self) -> bool:
        """Launch one decode chunk (or speculative round) against the
        resident device state WITHOUT waiting for its result.  Returns
        False when no slot needs scheduling.  The call's duration
        feeds the dispatch-tax EMA the ring controller weighs sync
        waits against."""
        began = time.monotonic()
        if self._spec is not None:
            dispatched = self._dispatch_spec_round()
        else:
            dispatched = self._dispatch_chunk()
        if dispatched:
            elapsed_ms = (time.monotonic() - began) * 1e3
            self._ema_dispatch_ms = (
                elapsed_ms if self._ema_dispatch_ms is None
                else 0.25 * elapsed_ms + 0.75 * self._ema_dispatch_ms)
        return dispatched

    def _dispatch_chunk(self) -> bool:
        plan = self._plan_remaining()
        live = plan > 0
        if not live.any():
            return False
        steps = int(min(self.chunk_steps, int(plan[live].max())))
        fields = {}
        if self._block_length:
            # One program whatever is left: the plan is an upper bound
            # of passes, and a short chunk would be a compile.
            steps = int(self.chunk_steps)
            fields["passes"] = steps
        if steplog.RECORDER is not None:
            self._dispatch_span = steplog.RECORDER.begin(
                "dispatch", chunk=self.counters["dispatches"] + 1,
                steps=steps, live_rows=int(live.sum()), **fields)
        self._sync_dirty()
        rng_key = None
        if self._any_sampled:
            # One split per dispatched chunk — the RNG schedule the
            # sampled-determinism tests pin down.
            self._rng, rng_key = self._jax.random.split(self._rng)
        # Snapshot slot occupancy BEFORE the dispatch: a mixed step
        # whose slice finishes the prompt calls _finish_prefill →
        # _activate_slot inside _serve_chunk, bumping the slot serial.
        # The entry must carry the serials of the occupancy the
        # program actually READ — copying after the bump would judge
        # the freshly activated request by an active_after flag
        # computed while its lane was still a scratch row, silently
        # retiring it with zero tokens.
        serial = self._slot_serial.copy()
        if compiles.LEDGER is not None:
            compiles.set_label(*(("serve_block_chunk", f"p{steps}")
                                 if self._block_length
                                 else ("serve_chunk", f"s{steps}")))
        tokens_d, counts_d, self._state = self._serve_chunk(
            self._state, steps,
            -1 if self.eos_id is None else int(self.eos_id),
            self._any_sampled, rng_key, self._serve_lora())
        sched = np.where(live, np.minimum(steps, plan), 0)
        self._inflight_sched += sched
        self._note_decode_blocks(live, sched)
        # A block entry's ``tokens`` are (slots, passes, block) windows
        # and its ``counts`` (slots, passes) marks: which positions
        # are still masked, live, store pass (_commit_block_passes).
        self._ring.append(dict(
            kind="block" if self._block_length else "chunk",
            tokens=tokens_d, counts=counts_d,
            active_after=self._state["active"], steps=steps,
            sched=sched, serial=serial,
            model_counters=self._chunk_counters))
        self._chunk_counters = None
        self._note_dispatch()
        return True

    def _serve_lora(self):
        """Stacked adapter factors for a serve dispatch — WITHOUT ids:
        per-row routing comes from the resident ``adapter_ids`` state.
        None while no live slot runs an adapter, so all-base traffic
        keeps the adapter-free compiled program."""
        if self._lora_shared is None or not self._adapter_ids.any():
            return None
        return self._lora_shared

    def _serve_chunk(self, state, steps: int, eos_id: int,
                     sampled: bool, rng_key, lora_shared):
        """Cache-layout strategy hook: dispatch ``steps`` device-
        resident decode steps.  The paged server overrides this with
        :func:`~..models.llama.serve_chunk_paged`; ALL bookkeeping —
        admission order, budgets, EOS, retirement — stays in this
        class (and most of THAT now runs in-jit)."""
        tokens_d, counts_d, new_state, self.cache = \
            self._model.serve_chunk_ragged(
                self.params, state, self.cache, steps, self.config,
                eos_id=eos_id, sampled=sampled, rng_key=rng_key,
                lora_shared=lora_shared)
        return tokens_d, counts_d, new_state

    def _dispatch_spec_round(self) -> bool:
        """ONE per-slot speculative round, dispatched entirely on
        device: a proposer fills each live slot's ``k``-token window
        (paired draft model, or host-assembled n-gram/prompt-lookup
        continuations, or grammar jump-forward segments), ONE target
        verify pass scores it, the acceptance kernel (greedy
        argmax-prefix or MRS — per-slot ``caps`` from the adaptive
        controller narrow individual rows) picks each slot's committed
        window, and :func:`~..models.speculative.spec_commit` applies
        EOS/budget caps and advances the resident state in-jit.
        Results flow through the same in-flight ring as plain chunks.
        Greedy outputs are exactly the plain server's under EVERY
        proposer/cap combination (invariants 11 + 18); sampled slots
        commit tokens distributed exactly as target-only sampling (MRS
        for model drafts, its delta-draft degenerate form for ngram).

        Adaptive rounds run at ``round_k`` = the max controller rung
        over live slots — always a ladder member, so the compiled
        shape set stays bounded (warm_spec_ladder pre-compiles it).
        ``round_k == 0`` (every live slot degraded) delegates to the
        plain chunk program."""
        plan = self._plan_remaining()
        live = plan > 0
        if not live.any():
            return False
        jnp, spec = self._jnp, self._spec
        mode = spec["mode"]
        controller = spec["controller"]
        cons_live = None
        if self._autostates is not None:
            cons_live = live & (self._autostates >= 0)
            if not cons_live.any():
                cons_live = None
        if (mode == "ngram" or cons_live is not None) and self._ring:
            # Host-fed proposers need SETTLED host mirrors: with
            # entries in flight, ngram would propose from stale
            # history (quality loss only) and — worse — grammar
            # jump-forward would walk forced segments from a stale
            # automaton state (committed unconditionally: a
            # correctness bug).  Serialize: consume first, dispatch
            # on the next pass.
            return False
        k = spec["k"]
        caps_host = None
        if controller is not None:
            k = controller.round_k(live)
            if cons_live is not None:
                # Grammar rows always get the full window: forced
                # jump-forward segments want width, and the masked
                # free token is cap-independent.
                k = spec["k"]
            caps_host = controller.caps(live)
            controller.note_dispatch(live)
            if k == 0:
                # Every live slot parked at k=0: run the ordinary
                # multi-step chunk program — the ladder's "plain
                # decode" rung — and tick the re-probe counters.
                controller.tick_cold_round(live)
                return self._dispatch_chunk()
        if steplog.RECORDER is not None:
            self._dispatch_span = steplog.RECORDER.begin(
                "dispatch", chunk=self.counters["dispatches"] + 1,
                steps=1, live_rows=int(live.sum()))
        self._sync_dirty()
        if compiles.LEDGER is not None:
            compiles.set_label("spec_round", f"k{k}")
        st = self._state
        lora_shared = self._serve_lora()
        lora = (dict(lora_shared, ids=st["adapter_ids"])
                if lora_shared is not None else None)
        from ..models.speculative import (delta_draft_logits,
                                          greedy_accept_batch,
                                          merge_forced,
                                          mrs_accept_batch,
                                          ngram_propose, spec_commit)
        draft_key = accept_key = cons_key = None
        if self._any_sampled:
            self._rng, draft_key, accept_key, cons_key = \
                self._jax.random.split(self._rng, 4)
        draft_logits = None
        if mode == "model":
            proposals, draft_logits = self._draft_propose(st, k,
                                                          draft_key)
        else:
            # Self-draft: suffix-match each live slot's own committed
            # history (prompt + delivered tokens — settled, see the
            # serialization gate above).  Host numpy only; proposals
            # ride the dispatch as one tiny (slots, k) upload.
            props = np.zeros((self.slots, k), np.int32)
            hits = 0
            for slot in np.nonzero(live)[0]:
                request = self._requests[int(slot)]
                history = list(request.prompt) + list(request.tokens)
                row, hit = ngram_propose(history, k)
                props[slot] = row
                hits += int(hit)
            self.spec_stats.ngram_hits += hits
            proposals = jnp.asarray(props)
            if self._any_sampled:
                # Delta-draft MRS: q = point mass at the proposal, so
                # accept w.p. min(1, p(prop)) and the residual is the
                # target's own distribution with the proposal's mass
                # removed — the textbook rejection decomposition of p.
                # Committed tokens stay EXACTLY target-distributed
                # with no draft model in sight.
                draft_logits = delta_draft_logits(
                    proposals, self.config.vocab_size)
        forced_counts = None
        cons_states = None
        if cons_live is not None:
            # Grammar jump-forward: while a slot's automaton state
            # admits exactly one token, that token is the ONLY output
            # a masked decode could produce — emit the whole forced
            # chain as its proposal window (committed via the same
            # verify pass, which writes its KV rows).
            table = self._automata["table"]
            forced_host = np.zeros((self.slots, k), np.int32)
            forced_counts = np.zeros(self.slots, np.int32)
            cons_states = np.zeros(self.slots, np.int32)
            for slot in np.nonzero(cons_live)[0]:
                slot = int(slot)
                segment, end_state = table.deterministic_segment(
                    int(self._autostates[slot]), k)
                forced_host[slot, :len(segment)] = segment
                forced_counts[slot] = len(segment)
                cons_states[slot] = end_state
            proposals = merge_forced(proposals,
                                     jnp.asarray(forced_host),
                                     jnp.asarray(cons_live))
        chunk = jnp.concatenate([st["token"], proposals], axis=1)
        logits = self._spec_verify(st, chunk, lora)
        caps_dev = (jnp.asarray(caps_host)
                    if caps_host is not None else None)
        if self._any_sampled:
            window, counts_raw = mrs_accept_batch(
                logits, draft_logits, proposals, st["temps"],
                st["tops"], accept_key, caps=caps_dev)
        else:
            window, counts_raw = greedy_accept_batch(
                logits, proposals, caps=caps_dev)
        if cons_live is not None:
            from ..models.constrained import constrained_accept_batch
            if cons_key is None:
                cons_key = self._jax.random.PRNGKey(0)
            window, counts_raw = constrained_accept_batch(
                logits, window, counts_raw,
                jnp.asarray(forced_host), jnp.asarray(forced_counts),
                jnp.asarray(cons_states), jnp.asarray(cons_live),
                self._automata["allowed"], st["temps"], st["tops"],
                cons_key)
        prev_positions, prev_active = st["positions"], st["active"]
        (emit_tokens, emit_counts, drafted, accepted, resync,
         self._state) = spec_commit(
            st, window, counts_raw,
            eos_id=-1 if self.eos_id is None else int(self.eos_id))
        if mode == "model":
            self._draft_resync(st, resync, prev_positions, prev_active)
        # A round commits AT LEAST one token per live lane, so 1 is
        # the safe in-flight schedule increment (over-dispatch is
        # harmless: exhausted lanes go inactive in-jit and emit 0).
        # (A terminal-state grammar lane can commit 0 — the consume
        # pass retires it immediately, settling the over-count.)
        sched = np.where(live, 1, 0)
        self._inflight_sched += sched
        self._ring.append(dict(
            kind="spec", tokens=emit_tokens, counts=emit_counts,
            counts_full=jnp.where(prev_active, counts_raw, 0),
            drafted=drafted, accepted=accepted,
            active_after=self._state["active"], steps=1, sched=sched,
            serial=self._slot_serial.copy(), width=k + 1,
            caps=caps_host,
            drafted_host=(int(caps_host[live].sum())
                          if caps_host is not None else None),
            cons=(cons_live.copy() if cons_live is not None else None),
            forced=(forced_counts.copy()
                    if forced_counts is not None else None)))
        self._note_dispatch()
        return True

    def _draft_propose(self, st, k: int, draft_key):
        """Model-mode proposer hook (cache-layout strategy): run the
        paired draft ``k`` ragged decode steps from the resident
        state.  Contiguous layout decodes against the draft's own
        (slots, max_seq) cache; the paged server overrides this with
        the pool-resident draft (``decode_chunk_paged`` over the
        target's block tables).  Returns ``(proposals (slots, k),
        draft_logits | None)``."""
        draft, llama = self._draft, self._llama
        if draft_key is not None:
            proposals, draft_logits, _, _, draft["cache"] = \
                llama.decode_chunk_ragged(
                    draft["params"], st["token"], draft["cache"],
                    st["positions"], st["active"], k, draft["config"],
                    temperatures=st["temps"], top_ps=st["tops"],
                    rng_key=draft_key, return_logits=True)
            return proposals, draft_logits
        proposals, _, _, draft["cache"] = llama.decode_chunk_ragged(
            draft["params"], st["token"], draft["cache"],
            st["positions"], st["active"], k, draft["config"])
        return proposals, None

    def _draft_resync(self, st, resync, prev_positions,
                      prev_active) -> None:
        """Draft-cache resync hook: replay committed[:-1] so the
        draft's KV matches the target's committed history before the
        next round (spans positions+1 onward, zero-padded; idempotent
        rewrites — stale pad rows are rewritten before they become
        attendable, the same policy as
        models.speculative._resync_draft)."""
        draft = self._draft
        _, draft["cache"] = self._llama.verify_chunk_ragged(
            draft["params"], resync, draft["cache"],
            prev_positions + 1, prev_active, draft["config"])

    def _spec_verify(self, st, chunk, lora):
        """Target-verify dispatch hook (cache-layout strategy): score
        the (slots, k+1) window against the resident cache, every row
        at its own absolute position.  Contiguous layout appends into
        the slot rows via :func:`~..models.llama.verify_chunk_ragged`;
        the paged server overrides this with the pool-direct
        :func:`~..models.llama.verify_chunk_paged` (and its TPEngine
        twin under a replica mesh)."""
        logits, self.cache = self._model.verify_chunk_ragged(
            self.params, chunk, self.cache, st["positions"],
            st["active"], self.config, lora=lora)
        return logits

    def _note_spec_rollback(self, slot: int, advance: int,
                            width: int) -> None:
        """Layout hook: account KV rows a spec round wrote past the
        committed frontier (``advance`` of ``width`` window rows
        kept).  The contiguous layout has nothing to account — slot
        rows are reserved wholesale; the paged server counts the
        rolled-back BLOCKS (``spec_rollback_blocks``)."""

    def _note_dispatch(self) -> None:
        if self._serve_started is None:
            self._serve_started = time.monotonic()
        self.counters["dispatches"] += 1
        self.counters["max_in_flight"] = max(
            self.counters["max_in_flight"], len(self._ring))
        # The slice queue's depth, summed over dispatches: over an
        # interval, prefill_backlog / dispatches is its mean depth.
        self.counters["prefill_backlog"] += len(self._prefilling)
        span, self._dispatch_span = self._dispatch_span, None
        if span is not None:
            if self._post_admission:
                span.end(ring=len(self._ring), after_admission=1)
            else:
                span.end(ring=len(self._ring))
        self._post_admission = False

    def _note_prefill(self, tokens: int, requests=(),
                      sliced: bool = False, mixed: bool = False,
                      key_blocks: int = 0) -> None:
        """Count prompt tokens about to be dispatched to prefill (any
        path: whole-bucket, standalone chunk, mixed step), for the
        ``requests`` whose prompt they belong to; the first such
        dispatch stamps a request's ``prefill_started_ts``.
        Prefix-cache hits never reach a prefill dispatch, so this
        measures work actually done — the gap to raw admitted prompt
        length IS the cache's savings.  ``sliced``: one slice of a
        chunked admission (the slice queue served once); ``mixed``:
        it rides a decode chunk rather than running standalone;
        ``key_blocks``: what a paged append's attention sweep has to
        visit (``prefill_key_blocks`` in the counters)."""
        if self._serve_started is None:
            self._serve_started = time.monotonic()
        self.counters["prefill_tokens"] += int(tokens)
        self.counters["prefill_key_blocks"] += int(key_blocks)
        for request in requests:
            if request.prefill_started_ts is None:
                request.prefill_started_ts = time.monotonic()
            request.prefill_dispatches += 1
            request.prefill_tokens += int(tokens) // len(requests)
        if sliced:
            self.counters["prefill_slices"] += 1
            if mixed:
                self.counters["prefill_slices_mixed"] += 1

    def _note_rows(self, slot: int, before: int, after: int) -> None:
        """The rows a slot has written went from ``before`` to
        ``after`` (a prefill slice dispatched, decode steps read back):
        where the module keeps two kinds of row, count the chunks that
        summarised and the windows that closed, and log a window's
        end.  Nothing, for any other module."""
        if not self._composed or after <= before:
            return
        events = self._model.cache_events(
            self.config, int(before), int(after), self._attn_block_size)
        for name, count in events.items():
            self.counters[name] += count
        if events.get("eva_windows_closed") \
                and steplog.RECORDER is not None:
            steplog.RECORDER.record("window_end", slot=slot,
                                    rows=int(after), **events)

    def _note_first_token(self, request: DecodeRequest) -> None:
        """A request's first token was committed: add its time to
        first token and the parts that tile it to the counters, all at
        once, so that the parts sum to the whole over any interval."""
        counters = self.counters
        counters["first_tokens"] += 1
        counters["ttft_ms"] += (request.first_token_ts
                                - request.submitted_ts) * 1e3
        for key, seconds in zip(TTFT_COUNTERS,
                                ttft_parts(request).values()):
            counters[key] += seconds * 1e3

    def _consume_one(self) -> None:
        """Apply the OLDEST in-flight entry's results (see
        :meth:`_consume_ready` — the batched form this delegates
        to)."""
        self._consume_ready(1)

    def _consume_ready(self, max_entries: int) -> None:
        """Apply the oldest ``max_entries`` in-flight entries' results
        to host bookkeeping in ONE pass: deliver tokens, advance
        mirrors, retire lanes the device deactivated.  This is the
        only device→host transfer on the serving path — per entry,
        (slots × steps) token ids plus two slots-sized vectors, never
        logits.

        Batching is the drain-tail optimisation: one watchdog window,
        one sync-wait measurement, one vectorized live-mask sweep and
        ONE steplog sync/token-dispatch/commit record cover the whole
        batch, instead of paying the fixed host cost per entry.
        Per-slot delivery still walks entries oldest-first, so
        streaming order — and the router's token-offset dedup
        contract — is exactly the sequential path's."""
        count = min(int(max_entries), len(self._ring))
        if count <= 0:
            return
        entries = [self._ring.popleft() for _ in range(count)]
        sync_span = commit_span = None
        if steplog.RECORDER is not None:
            sync_span = steplog.RECORDER.begin("sync", entries=count)
        wait_start = time.monotonic()
        if faults.PLAN is not None:
            stall = faults.PLAN.check("stall_step")
            if stall is not None:
                # Simulated device wedge: the sync below "takes" this
                # long — exactly what the watchdog exists to catch.
                time.sleep(float(stall.get("ms", 50.0)) / 1e3)
        alarm = None
        if self.watchdog_s > 0:
            # The alarm thread flips ``healthy`` even while this thread
            # is still blocked inside np.asarray (a truly wedged jit
            # never returns) — telemetry readers on other threads see
            # the trip; the post-sync check below handles the
            # recoverable-stall case deterministically.
            alarm = threading.Timer(self.watchdog_s,
                                    self._trip_watchdog)
            alarm.daemon = True
            alarm.start()
        # Entries were dispatched in program order on one device
        # stream, so materializing them oldest-first never waits on
        # work younger than the entry being read.
        elements = 0
        for entry in entries:
            entry["tokens"] = np.asarray(entry["tokens"])
            entry["counts"] = np.asarray(entry["counts"])
            entry["active_after"] = np.asarray(entry["active_after"])
            elements += (entry["tokens"].size + entry["counts"].size
                         + entry["active_after"].size)
            if entry["kind"] == "spec":
                entry["counts_full"] = np.asarray(entry["counts_full"])
            for name, value in (entry.get("model_counters")
                                or {}).items():
                self.counters[name] += int(np.asarray(value))
        if alarm is not None:
            alarm.cancel()
            if time.monotonic() - wait_start > self.watchdog_s:
                self._trip_watchdog()
        now = time.monotonic()
        wait_ms = (now - wait_start) * 1e3
        self._ema_wait_ms = (wait_ms if self._ema_wait_ms is None
                             else 0.25 * wait_ms
                             + 0.75 * self._ema_wait_ms)
        batch_steps = sum(int(entry["steps"]) for entry in entries)
        self.counters["host_syncs"] += 1
        self.counters["sync_wait_ms"] += wait_ms
        self.counters["sync_elements"] += elements
        self.counters["decode_steps"] += batch_steps
        if sync_span is not None:
            sync_span.end(wait_ms=round(wait_ms, 3), steps=batch_steps)
        if steplog.RECORDER is not None:
            commit_span = steplog.RECORDER.begin("commit")
        # ONE vectorized live-mask sweep across the whole batch: an
        # entry's lane is live iff its dispatch-time serial still
        # matches, the slot is active and occupied.  Serials only
        # change mid-batch via _retire below (admission never runs
        # inside consume), so rows retired while walking entry i are
        # explicitly cleared from the younger entries' masks — the
        # exact effect the per-entry serial recheck had.
        dispatch_start = time.monotonic()
        serials = np.stack([np.asarray(entry["serial"])
                            for entry in entries])
        batch_live = ((serials == self._slot_serial) & self.active
                      & np.fromiter((request is not None
                                     for request in self._requests),
                                    bool, self.slots))
        delivered = 0
        committed_upper = 0
        touched_slots = set()
        for index, entry in enumerate(entries):
            spec = entry["kind"] == "spec"
            if spec:
                self.spec_stats.target_passes += 1
                # Adaptive rounds proposed each slot only its CAP, not
                # the window width the device program sees — the host
                # snapshot is the truthful "drafted" count.
                if entry.get("drafted_host") is not None:
                    self.spec_stats.drafted += entry["drafted_host"]
                else:
                    self.spec_stats.drafted += int(
                        np.asarray(entry["drafted"]))
                self.spec_stats.accepted += int(
                    np.asarray(entry["accepted"]))
            live = batch_live[index]
            sched = np.asarray(entry["sched"])
            self._inflight_sched[live] -= sched[live]
            # Batched token dispatch: one tolist() per result field
            # turns the entry's whole token matrix into Python ints up
            # front and the walk touches only live lanes — no
            # per-token numpy scalar boxing, no per-slot ndarray
            # indexing (the host-path tax the step log attributed to
            # token delivery).
            token_rows = entry["tokens"].tolist()
            count_list = entry["counts"].tolist()
            full_list = (entry["counts_full"].tolist() if spec
                         else count_list)
            active_list = entry["active_after"].tolist()
            block = entry["kind"] == "block"
            if not block:
                committed_upper += int(entry["counts"].sum())
            cons_mask = entry.get("cons") if spec else None
            forced_ct = entry.get("forced") if spec else None
            caps_snap = entry.get("caps") if spec else None
            for slot in np.nonzero(live)[0]:
                slot = int(slot)
                touched_slots.add(slot)
                request = self._requests[slot]
                if block:
                    count = self._commit_block_passes(
                        slot, request, token_rows[slot],
                        count_list[slot], now)
                    delivered += count
                    committed_upper += count
                    if not active_list[slot]:
                        self._retire(slot)
                        batch_live[index + 1:, slot] = False
                    continue
                count = count_list[slot]
                constrained = (cons_mask is not None
                               and bool(cons_mask[slot]))
                must_retire = not active_list[slot]
                if count:
                    if request.first_token_ts is None:
                        request.first_token_ts = now
                        self._note_first_token(request)
                    request.tokens.extend(token_rows[slot][:count])
                    self._emitted[slot] += count
                    self._remaining[slot] = (request.max_new_tokens
                                             - self._emitted[slot])
                    # Mirrors advance by what the device WROTE: the
                    # full committed window for spec rounds (cache
                    # rows exist past the emit caps), the emitted
                    # prefix for chunks.
                    advance = full_list[slot]
                    if spec:
                        # Pre-advance mirror position = the window's
                        # first written row; the layout hook turns the
                        # rejected tail into its block-rollback
                        # accounting.
                        self._note_spec_rollback(slot, advance,
                                                 entry["width"])
                        if request.spec_accepted_rounds is None:
                            request.spec_accepted_rounds = []
                        request.spec_accepted_rounds.append(advance - 1)
                        if constrained:
                            self.spec_stats.jump_forward_tokens += min(
                                int(forced_ct[slot]), count)
                    self._note_rows(slot, self.positions[slot],
                                    self.positions[slot] + advance)
                    self.positions[slot] += advance
                    self.tokens[slot, 0] = token_rows[slot][advance - 1] \
                        if spec else token_rows[slot][count - 1]
                    delivered += count
                if spec and caps_snap is not None and not constrained \
                        and self._spec is not None \
                        and self._spec["controller"] is not None:
                    # Acceptance feedback at the cap the round ran
                    # under for THIS slot (k=0 ticks the re-probe
                    # counter instead).  Grammar rows are excluded:
                    # their acceptance is the grammar's, not the
                    # request's predictability.
                    self._spec["controller"].observe(
                        slot, int(caps_snap[slot]),
                        (full_list[slot] - 1) if count else 0)
                if constrained and self._autostates[slot] >= 0:
                    # Advance the host automaton over the DELIVERED
                    # tokens; a terminal state (no legal continuation)
                    # ends the request — grammar rounds serialize, so
                    # nothing else is in flight for this lane.
                    table = self._automata["table"]
                    state = int(self._autostates[slot])
                    for tok in token_rows[slot][:count]:
                        state = table.advance(state, int(tok))
                        if state < 0:
                            break
                    self._autostates[slot] = state
                    if state < 0 or table.is_terminal(state):
                        must_retire = True
                if must_retire:
                    self._retire(slot)
                    batch_live[index + 1:, slot] = False
        self.counters["tokens_committed"] += delivered
        if steplog.RECORDER is not None:
            # The instant that carries the walk's duration in a field
            # (obs/attrib reads it so); ``commit`` is the walk as a
            # span.
            steplog.RECORDER.record(
                "token_dispatch", slots=len(touched_slots),
                tokens=delivered,
                ms=round((time.monotonic() - dispatch_start) * 1e3, 3))
        if commit_span is not None:
            # Device-reported emit counts: stale-serial lanes may be
            # excluded above, so this is an upper bound on committed.
            commit_span.end(tokens=committed_upper)

    def _commit_block_passes(self, slot: int, request, windows, marks,
                             now: float) -> int:
        """Apply one chunk's passes to a slot that generates by block
        passes: ``windows[p]`` is the slot's block after pass ``p`` and
        ``marks[p]`` says which of its positions were still masked,
        whether the slot was live at the pass's start and whether the
        pass was the block's store pass (the model module's
        ``MARK_LIVE`` / ``MARK_STORE``).  Delivers the contiguous
        committed prefix past what the block has delivered, cut to the
        asked length and at the end-of-sequence id (the device retired
        the lane by the same rule), and keeps the block's mirrors as
        the device has them.  Returns the tokens delivered."""
        B, state = self._block_length, self._block_state
        live_bit = 1 << (B + self._model.MARK_LIVE)
        store_bit = 1 << (B + self._model.MARK_STORE)
        eos = -1 if self.eos_id is None else int(self.eos_id)
        room = request.max_new_tokens - int(self._emitted[slot])
        at = int(state["delivered"][slot])
        passes = int(state["passes"][slot])
        rows = stores = count = 0
        masked = last = None
        for window, mark in zip(windows, marks):
            if not mark & live_bit:
                continue
            rows += 1
            if mark & store_bit:
                # The block's K/V rows are final: on to the next.
                stores += 1
                at = passes = 0
                masked, last = live_bit - 1, None
                continue
            masked, last = mark & (live_bit - 1), window
            passes += 1
            prefix = (masked & -masked).bit_length() - 1 if masked else B
            if prefix <= at:
                continue
            new = window[at:prefix][:room - count]
            at = prefix
            if eos >= 0 and eos in new:
                new = new[:new.index(eos) + 1]
            if new:
                request.tokens.extend(new)
                count += len(new)
        if not rows:
            return 0
        self.positions[slot] += stores * B
        state["delivered"][slot] = at
        state["passes"][slot] = passes
        state["masked"][slot] = [masked >> i & 1 for i in range(B)]
        if last is not None:
            state["window"][slot] = last
        counters = self.counters
        counters["block_pass_rows"] += rows
        counters["block_store_rows"] += stores
        counters["blocks_finished"] += stores
        if count:
            if request.first_token_ts is None:
                request.first_token_ts = now
                self._note_first_token(request)
            self._emitted[slot] += count
            self._remaining[slot] = (request.max_new_tokens
                                     - self._emitted[slot])
        return count

    def _trip_watchdog(self) -> None:
        """Mark the replica wedged (idempotent; callable from the
        alarm thread).  ``step()`` fails outstanding work on its next
        pass; recovery is an operator restart, never self-clearing —
        a device that stalled once is not trustworthy."""
        if self._watchdog_tripped:
            return
        self._watchdog_tripped = True
        self.healthy = False
        self.counters["watchdog_trips"] += 1
        if flight.FLIGHT is not None:
            # Forensics around the stall: correlate the bundle with
            # whichever request's trace context is in flight (if any),
            # so the fleet-wide dump joins on one trace id.
            carrier = next((r.trace_ctx for r in self._requests
                            if r is not None and r.trace_ctx), "")
            context = trace.extract(carrier)
            flight.FLIGHT.capture(
                "watchdog",
                trace_id=context.trace_id if context else None,
                reason=f"ring sync stalled past {self.watchdog_s:g}s")

    def _drain_ring(self) -> None:
        while self._ring:
            self._consume_ready(len(self._ring))

    # ---- on-demand device profiling (PR 14) -------------------------- #

    def request_profile(self, steps: int = 4, reason: str = "",
                        trace_id: str = "", out_dir=None) -> bool:
        """Ask for a ``(profile)`` bracket around this process's next
        ``steps`` engine steps.  Returns False when a session is
        already pending (one bracket at a time per process —
        ``jax.profiler`` is process-global)."""
        session = profiler.request(
            out_dir=out_dir, steps=steps, reason=reason,
            trace_id=trace_id,
            service=f"srv{self._instance_id}")
        return session is not None

    def _profiled_step(self) -> None:
        """One SYNCHRONOUS timed chunk inside the profiler bracket:
        drain the ring, start the trace (first pass), dispatch one
        round and sync it, and book the dispatch→sync wall ms as that
        chunk's device time — on a saturated device the host does
        nothing else in that window, which is exactly the number the
        attribution table wants in place of the probe estimate.  An
        idle engine (nothing live to dispatch) finishes the session
        after a bounded number of empty passes rather than holding the
        process-global profiler hostage."""
        session = None
        if profiler.PROFILER is not None:
            session = profiler.PROFILER
        if session is None:
            return
        self._drain_ring()
        if not session.ensure_started():
            return                       # start failed; session closed
        steps_before = self.counters["decode_steps"]
        began = time.monotonic()
        dispatched = self._dispatch_round()
        self._drain_ring()
        if dispatched:
            self._profile_idle = 0
            session.chunk_done(
                (time.monotonic() - began) * 1e3,
                int(self.counters["decode_steps"] - steps_before))
        else:
            self._profile_idle += 1
        if session.remaining == 0 or self._profile_idle >= 50:
            self._finish_profile(session)

    def _finish_profile(self, session) -> None:
        self._profile_idle = 0
        live_ids = []
        for request in self._requests:
            if request is not None and request.trace_ctx:
                context = trace.extract(request.trace_ctx)
                if context:
                    live_ids.append(context.trace_id)
        manifest = session.finish(live_trace_ids=live_ids)
        if manifest.get("steps"):
            self._device_step_ms = manifest["device_step_ms"]
        self._profiles += 1
        if flight.FLIGHT is not None:
            # Park the manifest in a bundle immediately: the artifact
            # dir is outside the bundle ring, but the manifest (and
            # the ledger section) ride the ring like any capture.
            flight.FLIGHT.capture(
                "profile",
                trace_id=session.trace_id or None,
                reason=manifest.get("reason", "")
                or f"profile bracket: {manifest.get('steps', 0)} steps")

    def stats(self) -> Dict:
        """Serving perf counters + derived rates (dashboard payloads,
        the benchmark's counters, smoke assertions)."""
        steps = self.counters["decode_steps"]
        elapsed = (time.monotonic() - self._serve_started
                   if self._serve_started is not None else 0.0)
        out = dict(
            self.counters,
            in_flight=len(self._ring),
            ring_depth=self._ring_depth,
            queue_depth=self.queue_depth,
            slots_active=self.slots_active,
            free_slots=self.slots - self.slots_active,
            healthy=int(self.healthy),
            tp_degree=self.tp_degree,
            sp_degree=self.sp_degree,
            ep_degree=self.ep_degree,
            mesh_shape=self.mesh_shape,
            decode_attention_path=self.decode_attention_path,
            decode_scale_append_path=self.decode_scale_append_path,
            decode_attend_form=self.decode_attend_form,
            prefill_attention_path=self.prefill_attention_path,
            blocks_read_per_step=(
                round(self.counters["decode_blocks_read"] / steps, 2)
                if steps else 0.0),
            # Share of the table (slots x blocks a row may hold) that
            # held live blocks: what the decode kernel's loop walks, of
            # what a grid over the whole table would have stepped.
            decode_table_live_share=(
                round(self.counters["decode_blocks_read"]
                      / (steps * self.slots * self._attn_total_blocks),
                      4) if steps else 0.0),
            # Share of the K/V decode kernel's loop passes that attend
            # over a whole W-key tile (the rest are rows' tails).
            decode_wide_iteration_share=(
                round(self.counters["decode_wide_iterations"]
                      / self.counters["decode_iterations"], 4)
                if self.counters["decode_iterations"] else 0.0),
            decode_steps_per_sec=(
                round(steps / elapsed, 1) if elapsed > 0 else 0.0),
            prefill_tokens_per_sec=(
                round(self.counters["prefill_tokens"] / elapsed, 1)
                if elapsed > 0 else 0.0),
            prefill_queue_depth=len(self._prefilling),
            sync_stalls_per_100_steps=(
                round(100.0 * self.counters["host_syncs"] / steps, 2)
                if steps else 0.0))
        if self._spec is not None:
            # Speculation counters (host-side SpecStats increments in
            # _consume_one — never traced, invariant 7).
            controller = self._spec["controller"]
            out.update(
                spec_k=self._spec["k"],
                spec_rounds=self.spec_stats.target_passes,
                spec_proposed=self.spec_stats.drafted,
                spec_accepted=self.spec_stats.accepted,
                spec_acceptance_rate=round(
                    self.spec_stats.acceptance_rate, 4),
                spec_tokens_per_target_pass=round(
                    self.spec_stats.tokens_per_target_pass, 4),
                spec_rollback_blocks=self.spec_stats.rollback_blocks,
                spec_draft_mode=self._spec["mode"],
                spec_k_effective=(controller.hist_string()
                                  if controller is not None else "-"),
                spec_jump_forward_tokens=(
                    self.spec_stats.jump_forward_tokens),
                spec_ngram_hits=self.spec_stats.ngram_hits)
        if compiles.LEDGER is not None:
            # Compile-ledger view (PR 14): rides EC shares via
            # TELEMETRY_KEYS so the router's steady-compile watch and
            # the dashboard pane see it without extra plumbing.  The
            # ledger is process-wide; a multi-engine process reports
            # the same numbers from each engine (documented).
            out.update(
                compiles=compiles.LEDGER.compiles,
                compiles_steady_state=compiles.LEDGER.steady_compiles,
                compile_cache_hits=compiles.LEDGER.cache_hits,
                compile_cache_misses=compiles.LEDGER.cache_misses,
                compile_wall_ms=round(compiles.LEDGER.total_ms, 1),
                compile_cache_load_ms=round(
                    compiles.LEDGER.cache_load_ms, 1),
                compile_trace_ms=round(compiles.LEDGER.trace_ms, 1),
                compile_lower_ms=round(compiles.LEDGER.lower_ms, 1),
                programs_traced=compiles.LEDGER.programs_traced)
        if self._device_step_ms is not None:
            out.update(device_step_ms=round(self._device_step_ms, 3),
                       profiles=self._profiles)
        return out

    def warm_spec_ladder(self, sampled: bool = False) -> None:
        """Pre-compile every spec-round program shape the ladder can
        reach — call while the engine is IDLE (no live slots, empty
        ring): each rung's proposer/verify/accept/commit programs run
        once against the real all-inactive resident state (inactive
        rows write the scratch row/block and the commit is a masked
        no-op, so state content is unchanged).  After this, adaptive k
        can wander the whole ladder without a single steady-state
        compile — the PR-14 ledger gate
        (``aiko_compiles_steady_state_total == 0``) survives
        adaptivity by construction.  ``sampled=True`` additionally
        warms the MRS/sampled-draft variants."""
        if self._spec is None:
            return
        if self.slots_active or self._ring:
            raise RuntimeError(
                "warm_spec_ladder must run on an idle engine")
        jnp, jax = self._jnp, self._jax
        from ..models.speculative import (delta_draft_logits,
                                          greedy_accept_batch,
                                          mrs_accept_batch,
                                          spec_commit)
        adaptive = self._spec["controller"] is not None
        for k in self._spec["ladder"]:
            if k == 0:
                continue       # the plain chunk program; warmed by
                               # ordinary traffic/warmup
            if compiles.LEDGER is not None:
                compiles.set_label("spec_round", f"k{k}")
            st = self._state
            draft_key = (jax.random.PRNGKey(0) if sampled else None)
            if self._spec["mode"] == "model":
                proposals, draft_logits = self._draft_propose(
                    st, k, draft_key)
            else:
                proposals = jnp.zeros((self.slots, k), jnp.int32)
                draft_logits = (delta_draft_logits(
                    proposals, self.config.vocab_size)
                    if sampled else None)
            chunk = jnp.concatenate([st["token"], proposals], axis=1)
            logits = self._spec_verify(st, chunk, None)
            caps = (jnp.zeros((self.slots,), jnp.int32)
                    if adaptive else None)
            if sampled:
                window, counts_raw = mrs_accept_batch(
                    logits, draft_logits, proposals, st["temps"],
                    st["tops"], jax.random.PRNGKey(1), caps=caps)
            else:
                window, counts_raw = greedy_accept_batch(
                    logits, proposals, caps=caps)
            prev_positions = st["positions"]
            prev_active = st["active"]
            _, _, _, _, resync, self._state = spec_commit(
                st, window, counts_raw,
                eos_id=-1 if self.eos_id is None else int(self.eos_id))
            if self._spec["mode"] == "model":
                self._draft_resync(st, resync, prev_positions,
                                   prev_active)

    def run_until_drained(self, max_chunks: int = 10_000):
        """Synchronous helper (tests / batch jobs): pump until every
        queued request completes."""
        finished, self.completed = self.completed, []
        chunks = 0
        while self.busy:
            finished.extend(self.step())
            chunks += 1
            if chunks > max_chunks:
                raise RuntimeError("continuous batching did not drain")
        return finished


class ContinuousReplica(Actor):
    """Actor wrapper: same ``(infer …)`` protocol as
    :class:`~.serving.ModelReplica`, but requests join the continuous
    batch instead of running serially.  A delayed self-post pump runs
    decode chunks between message deliveries while any slot is live.

    Paged servers with the prefix cache enabled additionally join the
    distributed KV cache (:mod:`~..kvstore`): the replica advertises
    its cached prefix digest on its EC-share state topic (every pump,
    plus a slow re-advertise timer so idle replicas keep their
    directory lease alive), answers ``(kv_export …)`` block-transfer
    RPCs from peers, and — when a routed request carries a
    ``kv_source`` hint — pulls the prefix from the named owner before
    admission, falling back to plain local prefill if the owner does
    not answer within ``kv_fetch_timeout_s`` (a dead owner costs
    latency, never correctness).

    ``prefill_only=True`` makes this a dedicated PREFILL replica for
    the opt-in disaggregated mode: generation budgets clamp to one
    token (the admission seed), the cache retains the prompt's
    blocks, and the digest advertises role ``prefill`` so routers
    never send it decode traffic."""

    #: Re-advertise the prefix digest this often even when idle —
    #: must stay well under the router directory's ``lease_s`` or an
    #: idle replica's cached prefixes drop out of routing.
    KV_ADVERTISE_S = 5.0

    #: While requests are being served the digest is refreshed at most
    #: this often (``_share_telemetry``).
    KV_DIGEST_S = 0.25

    def __init__(self, context, process=None, server=None,
                 prefill_only: bool = False,
                 kv_fetch_timeout_s: float = 2.0):
        from .serving import REPLICA_PROTOCOL
        context.protocol = context.protocol or REPLICA_PROTOCOL
        super().__init__(context, process)
        self.server = server or ContinuousBatchingServer()
        self.prefill_only = prefill_only
        self.kv_fetch_timeout_s = kv_fetch_timeout_s
        self._command_handlers["infer"] = self._wire_infer
        self._command_handlers["pump"] = self._pump
        self._command_handlers["adapter_load"] = self._wire_adapter_load
        self._command_handlers["adapter_unload"] = \
            self._wire_adapter_unload
        self._command_handlers["infer_cancel"] = self._wire_cancel
        self._command_handlers["kv_export"] = self._wire_kv_export
        self._command_handlers["retire"] = self._wire_retire
        self._command_handlers["migrate_prepare"] = \
            self._wire_migrate_prepare
        self.share["slots"] = self.server.slots
        self.share["tp_degree"] = getattr(self.server, "tp_degree", 1)
        self.share["mesh_shape"] = getattr(self.server, "mesh_shape",
                                           "")
        self.share["requests_served"] = 0
        self._pumping = False
        #: Graceful drain in progress (``(retire)`` received): routers
        #: stop sending new work; queued/active requests finish here.
        self._retiring = False
        #: id(request) -> tokens already delivered via infer_partial.
        #: Keyed by object identity, not request_id: the client owns
        #: that string and may reuse it across concurrent requests.
        self._stream_sent: Dict[int, int] = {}
        #: request ids a router is live-migrating AWAY from this
        #: replica: while non-empty the prefix digest carries the
        #: ``/migrating`` flag (routers stop scoring this replica for
        #: NEW prefix placement) and the shared lifecycle reads
        #: ``migrating``.  Ids clear when their request terminates
        #: here (usually via the post-cutover cancel).
        self._migrating_ids: set = set()
        #: slowest completed requests — ``(total_ms, request_id,
        #: {phase: ms})`` kept sorted descending; surfaces in the EC
        #: share as ``slow_requests`` for the dashboard pane.
        self._slow: List = []
        # Warm-start fetches in flight: token -> parked DecodeRequest.
        self._kv_pending: Dict[str, DecodeRequest] = {}
        self._kv_started: Dict[str, float] = {}
        self._kv_counter = 0
        self._kv_topic = f"{self.topic_path}/kv"
        self._kv_digest_ts = float("-inf")
        self._kv_digest_migrating = False
        if self._kv_capable():
            self.process.add_message_handler(self._on_kv_message,
                                             self._kv_topic)
            self.process.event.add_timer_handler(
                self._kv_advertise, self.KV_ADVERTISE_S)

    def _kv_capable(self) -> bool:
        return getattr(self.server, "enable_prefix_cache", False) \
            and hasattr(self.server, "kv_export_payload")

    @property
    def kv_role(self) -> str:
        return "prefill" if self.prefill_only else "decode"

    def _wire_infer(self, request_id, response_topic, payload=None):
        from ..pipeline.codec import decode_swag
        request = DecodeRequest(request_id=str(request_id), prompt=None,
                                max_new_tokens=0, tokens=[],
                                response_topic=str(response_topic))
        try:
            inputs = decode_swag(payload or {})
            request.prompt = np.asarray(inputs["tokens"],
                                        np.int32).reshape(-1)
            request.max_new_tokens = int(
                np.asarray(inputs.get("max_new_tokens", 16)))
            request.temperature = float(
                np.asarray(inputs.get("temperature", 0.0)))
            request.top_p = float(np.asarray(inputs.get("top_p", 1.0)))
            request.stream = bool(
                int(np.asarray(inputs.get("stream", 0))))
            adapter = inputs.get("adapter")
            request.adapter = str(adapter) if adapter else None
            automaton = inputs.get("automaton")
            request.automaton = str(automaton) if automaton else None
            if inputs.get("denoise_steps") is not None:
                request.denoise_steps = int(
                    np.asarray(inputs["denoise_steps"]))
            if inputs.get("denoise_rule"):
                request.denoise_rule = str(inputs["denoise_rule"])
            if inputs.get("denoise_threshold") is not None:
                request.denoise_threshold = float(
                    np.asarray(inputs["denoise_threshold"]))
            deadline_ms = inputs.get("deadline_ms")
            if deadline_ms is not None:
                # Relative budget → local monotonic deadline (wall
                # clocks never cross processes; transit time before
                # arrival is not charged).
                request.deadline_ts = time.monotonic() + \
                    float(np.asarray(deadline_ms)) / 1e3
            carrier = inputs.get("trace")
            if carrier:
                request.trace_ctx = str(carrier)
            kv_source = inputs.get("kv_source")
            kv_tier_hint = inputs.get("kv_tier_hint")
            kv_migrate = bool(
                int(np.asarray(inputs.get("kv_migrate", 0))))
            if self.prefill_only or inputs.get("prefill_only"):
                # Dedicated prefill: the admission seed IS the one
                # generated token; the prompt's blocks stay cached
                # for the decode replica to pull.
                request.max_new_tokens = 1
                request.stream = False
        except Exception:  # noqa: BLE001 - bad request must still respond
            self.logger.exception("%s: malformed infer request %s",
                                  self.name, request_id)
            request.error = "infer_failed"
            self._respond(request)
            return
        if kv_source and self._kv_capable() \
                and request.adapter is None:
            if self._begin_kv_fetch(request, str(kv_source),
                                    migrate=kv_migrate):
                return        # parked until import or timeout
        if kv_tier_hint and request.adapter is None \
                and hasattr(self.server, "prefetch_promote"):
            # Router hinted this prompt at a demoted/spilled chain:
            # start the async promotion NOW so the restore overlaps
            # the request's queue wait instead of beginning at its
            # admission deferral (tier-aware prefetch).
            self.server.prefetch_promote(request.prompt)
        self.server.submit(request)
        self._ensure_pumping()

    def _wire_retire(self, *_args):
        """``(retire)`` — graceful drain (autoscaler scale-in): flip
        the shared ``lifecycle`` to ``retiring`` so routers stop
        sending NEW work, keep serving whatever is queued or active,
        and advertise ``drained 1`` once idle so the supervisor knows
        the process is safe to stop.  Requests that raced the flip in
        transit are still served — zero-lost outranks a prompt exit."""
        if self._retiring:
            return
        self._retiring = True
        self.logger.info("%s: retiring — draining %d queued / %d active",
                         self.name, self.server.queue_depth,
                         self.server.slots_active)
        updates = {"lifecycle": "retiring"}
        if not self.server.busy and not self._kv_pending:
            updates["drained"] = 1
        self.share.update(updates)
        if self.ec_producer is not None:
            for key, value in updates.items():
                self.ec_producer.update(key, value)
        if self.server.busy:
            self._ensure_pumping()

    def _wire_migrate_prepare(self, request_id, response_topic,
                              payload=None):
        """``(migrate_prepare mid reply swag{request_id})`` — a router
        is live-migrating one of our requests away.  Register the
        request's LIVE chain (prompt + committed tokens) in the prefix
        index so ``kv_export`` can serve it, mark the request
        migrating (digest flag + ``migrating`` lifecycle), and answer
        ``(migrate_ready mid swag{request_id, blocks, tokens})`` — or
        an error swag the router degrades on (cold resume, or abort
        when the request is simply gone).  We KEEP serving the
        request: the double-delivery window is the whole point."""
        from ..pipeline.codec import decode_swag, encode_swag
        mid = str(request_id)
        try:
            target_id = str(decode_swag(payload or {})["request_id"])
        except Exception:  # noqa: BLE001 - malformed → router aborts
            target_id = ""
        request = next(
            (r for r in self.server.live_requests()
             if r.request_id == target_id), None)
        if request is None:
            outputs: Dict = {"request_id": target_id,
                             "error": "migrate_unknown_request"}
        elif not self._kv_capable() \
                or not hasattr(self.server, "publish_live_chain"):
            outputs = {"request_id": target_id,
                       "error": "migrate_unsupported"}
        else:
            try:
                blocks = int(self.server.publish_live_chain(request))
            except Exception:  # noqa: BLE001 - degrade to cold resume
                self.logger.exception(
                    "%s: publish_live_chain failed for %s",
                    self.name, target_id)
                blocks = -1
            if blocks < 0:
                outputs = {"request_id": target_id,
                           "error": "migrate_export_failed"}
            else:
                outputs = {"request_id": target_id, "blocks": blocks,
                           "tokens": len(request.tokens or [])}
                self._migrating_ids.add(target_id)
                updates = {}
                if self.share.get("lifecycle") == "ready":
                    updates["lifecycle"] = "migrating"
                # Push the flagged digest NOW — routers must stop
                # scoring us for new prefix placement before the
                # transfer traffic starts, not at the next pump.
                updates["kv_prefixes"] = self.server.prefix_digest(
                    role=self.kv_role, migrating=True)
                self.share.update(updates)
                if self.ec_producer is not None:
                    for key, value in updates.items():
                        self.ec_producer.update(key, value)
        self.process.message.publish(
            str(response_topic),
            generate("migrate_ready", [mid, encode_swag(outputs)]))

    def _ensure_pumping(self):
        if not self._pumping:
            self._pumping = True
            self._schedule_pump()

    def _schedule_pump(self):
        from ..runtime.actor import ActorMessage, Mailbox
        # At least the millisecond that lets other mailbox traffic
        # in between steps; more while a chunk that has just begun
        # keeps the device busy (``arrival_hold_s``).
        self._post_message(Mailbox.IN, ActorMessage("pump", []),
                           delay=max(0.001, self.server.arrival_hold_s()))

    def _pump(self):
        if faults.PLAN is not None:
            hit = faults.PLAN.check("kill_replica", key=self.name)
            if hit is not None:
                # Die mid-decode with requests in flight — the LWT
                # (absent) fires, the Registrar evicts this process's
                # services, and routers re-dispatch.  ``hard=1``
                # additionally kills the OS process (cross-process
                # chaos; the exit code marks an injected death).
                self.logger.warning("%s: fault kill_replica firing",
                                    self.name)
                self._pumping = False
                self.process.kill()
                if hit.get("hard"):
                    import os
                    os._exit(13)
                return
            if self._migrating_ids:
                hit = faults.PLAN.check("kill_source_mid_migration",
                                        key=self.name)
                if hit is not None:
                    # Die as the SOURCE of an in-flight migration —
                    # the router must promote the destination when
                    # the resume was dispatched, else fall back to
                    # the plain re-dispatch replay.  Same LWT path
                    # as kill_replica.
                    self.logger.warning(
                        "%s: fault kill_source_mid_migration firing",
                        self.name)
                    self._pumping = False
                    self.process.kill()
                    if hit.get("hard"):
                        import os
                        os._exit(13)
                    return
        finished = self.server.step()
        self._stream_partials()
        for request in finished:
            self._respond(request)
        self._share_telemetry()
        if self.server.busy or self.server.completed:
            self._schedule_pump()
        else:
            self._pumping = False

    def _share_telemetry(self):
        """Operator view (dashboard / any ECConsumer): live slot
        occupancy, queue depth, async-loop perf counters, latency
        quantiles and encoded histograms, refreshed every pump.

        Quantiles come from the server's fixed-bucket histograms
        (obs.metrics) rather than a rolling raw-sample window: the
        SAME bucket bounds everywhere mean a router can merge the
        ``hist.<phase>`` encodings it watches across replicas and
        quote exact fleet-level p50/p95/p99 — nearest-rank lists
        cannot merge without shipping every sample."""
        from .serving import serving_telemetry
        updates = serving_telemetry(self.server.stats())
        if self._kv_capable():
            # The digest walks the whole prefix index (33 ms a call
            # with 48,000 blocks cached before its walk was pared
            # down, my chip runs, PR 31) to tell routers what they
            # cannot act on sixteen times a second, and every change
            # of it is a publication.  So: at most every KV_DIGEST_S
            # while busy, at once when the ``migrating`` flag turns,
            # and always on the pump that leaves the server idle, so
            # what an idle replica advertises is exact.
            migrating = bool(self._migrating_ids)
            now = time.monotonic()
            if not self.server.busy \
                    or migrating != self._kv_digest_migrating \
                    or now - self._kv_digest_ts >= self.KV_DIGEST_S:
                self._kv_digest_ts = now
                self._kv_digest_migrating = migrating
                updates["kv_prefixes"] = self.server.prefix_digest(
                    role=self.kv_role, migrating=migrating)
        hists = self.server.latency_hists
        if hists["ttft"].count:
            updates["ttft_p50_ms"] = round(hists["ttft"].quantile(0.5), 1)
            # p95 is the admission-stall number SLOs watch (p50 hides
            # a prefill convoy behind the median).
            updates["ttft_p95_ms"] = round(
                hists["ttft"].quantile(0.95), 1)
        if hists["total"].count:
            updates["total_p50_ms"] = round(
                hists["total"].quantile(0.5), 1)
        for phase, hist in hists.items():
            if hist.count:
                updates[f"hist.{phase}"] = hist.encode()
        slot_counts = self.server.adapter_slot_counts() \
            if hasattr(self.server, "adapter_slot_counts") else {}
        if slot_counts:
            # Per-adapter slot occupancy for the dashboard's adapter
            # pane — ``name=count`` pairs, space-joined like
            # ``slow_requests``.
            updates["adapter_slots"] = " ".join(
                f"{name}={count}"
                for name, count in slot_counts.items())
        if self._slow:
            updates["slow_requests"] = " ".join(
                f"{request_id}:{total_ms}:" + ",".join(
                    f"{phase}={value}" for phase, value
                    in sorted(breakdown.items()))
                for total_ms, request_id, breakdown in self._slow)
        if flight.FLIGHT is not None and flight.FLIGHT.captures:
            # Recent flight-recorder triggers, newest last — the
            # dashboard's recent-triggers pane reads this.
            updates["flight_captures"] = flight.FLIGHT.captures
            recent = flight.FLIGHT.recent()
            if recent:
                updates["last_capture"] = " ".join(
                    f"{entry['trigger']}@{entry['ts']:.0f}"
                    for entry in recent[-3:])
        if self._retiring and not self.server.busy \
                and not self._kv_pending:
            # Drain complete: every queued/active request reached a
            # terminal state.  The supervisor watches this key before
            # stopping the process.
            updates["drained"] = 1
        if not self.server.healthy \
                and self.share.get("lifecycle") != "unhealthy":
            # The router watches lifecycle on the replica's state
            # topic: flipping it drains this replica (in-flight work
            # re-dispatched, no new routes) without waiting for the
            # process to die.
            updates["lifecycle"] = "unhealthy"
        changed = {key: value for key, value in updates.items()
                   if self.share.get(key) != value}
        if not changed:
            return
        self.share.update(changed)
        if self.ec_producer is not None:
            for key, value in changed.items():
                self.ec_producer.update(key, value)

    # -- distributed KV cache (kvstore subsystem) ------------------- #

    def _kv_advertise(self, *_args):
        """Slow periodic re-advertise: refreshes the router
        directory's lease on this replica's prefixes while idle (no
        pump runs, so :meth:`_share_telemetry`'s diff never fires),
        and catches routers that subscribed after the last change."""
        if not self._kv_capable():
            return
        digest = self.server.prefix_digest(
            role=self.kv_role, migrating=bool(self._migrating_ids))
        self.share["kv_prefixes"] = digest
        if self.ec_producer is not None:
            self.ec_producer.update("kv_prefixes", digest)

    def _wire_kv_export(self, request_id, response_topic,
                        payload=None):
        """``(kv_export id reply swag)`` — peer block-transfer RPC:
        resolve the requested chain segment and answer with the pool
        rows, or an error the importer treats as a recompute
        fallback."""
        from ..obs import trace
        from ..pipeline.codec import decode_swag, encode_swag
        started = trace.now()
        carrier = None
        outputs = {"error": "kv_unsupported"}
        if self._kv_capable():
            try:
                inputs = decode_swag(payload or {})
                carrier = inputs.get("trace")
                keys = [str(k) for k in inputs["kv_keys"]]
                exported = self.server.kv_export_payload(
                    keys,
                    int(np.asarray(inputs.get("kv_start_depth", 0))))
                if faults.PLAN is not None:
                    if exported is not None \
                            and inputs.get("kv_migrate") \
                            and faults.PLAN.check(
                                "drop_migration_block",
                                key=str(request_id)) is not None:
                        # Ship the migration chain one block short:
                        # the destination's import comes up short and
                        # its admission walk recomputes the tail —
                        # colder, never wrong.
                        from ..kvstore.transfer import drop_one_block
                        self.logger.warning(
                            "%s: fault drop_migration_block firing",
                            self.name)
                        exported = drop_one_block(exported)
                outputs = exported if exported is not None \
                    else {"error": "kv_prefix_gone"}
            except Exception:  # noqa: BLE001 - RPC must answer
                self.logger.exception("%s: kv_export failed",
                                      self.name)
                outputs = {"error": "kv_export_failed"}
        if carrier and "error" not in outputs:
            # Transfer-source span: the exporter's share of a traced
            # request's warm start, riding back with the blocks.
            span = trace.synth_span(
                "kv_export", str(carrier), self.name, started,
                trace.now(), attrs={"keys": len(keys)})
            outputs["trace_spans"] = trace.encode_spans([span])
        self.process.message.publish(
            str(response_topic),
            generate("kv_export_response",
                     [str(request_id), encode_swag(outputs)]))

    def _begin_kv_fetch(self, request: DecodeRequest,
                        kv_source: str,
                        migrate: bool = False) -> bool:
        """Warm start: request the prompt's missing prefix blocks
        from the owner the router named.  Returns False when there is
        nothing worth fetching (prompt too short, already cached
        locally, or the owner is this replica) — the caller submits
        normally.  Otherwise the request PARKS until the import lands
        or the fallback timer fires; either way it is submitted
        exactly once."""
        from ..pipeline.codec import encode_swag
        if kv_source == self.topic_path:
            return False
        keys = self.server.prefix_keys_hex(request.prompt)
        local = self.server.prefix_local_depth(request.prompt)
        if not keys or local >= len(keys):
            return False
        self._kv_counter += 1
        token = f"kvf{self._kv_counter}"
        self._kv_pending[token] = request
        self._kv_started[token] = time.monotonic()
        swag = {"kv_keys": keys[local:], "kv_start_depth": local}
        if migrate:
            # Marks the export as a live-migration transfer: the
            # source tags its accountant flows and the
            # ``drop_migration_block`` fault point keys off it.
            swag["kv_migrate"] = 1
        if request.trace_ctx:
            # The owner answers with its "kv_export" span under the
            # SAME trace — the transfer source joins the request tree.
            swag["trace"] = request.trace_ctx
        self.process.message.publish(
            f"{kv_source}/in",
            generate("kv_export",
                     [token, self._kv_topic, encode_swag(swag)]))
        self.process.event.add_timer_handler(
            lambda: self._kv_fetch_timeout(token),
            self.kv_fetch_timeout_s, once=True)
        return True

    def _kv_fetch_timeout(self, token: str):
        """Owner never answered (dead, partitioned, or slow): fall
        back to plain local prefill — correctness never depended on
        the transfer."""
        request = self._kv_pending.pop(token, None)
        started = self._kv_started.pop(token, None)
        if request is None:
            return                    # import landed first
        if started is not None:
            # The wait WAS spent — latency the kv_restore phase owns
            # even though no blocks arrived.
            request.kv_restore_ms = round(
                (time.monotonic() - started) * 1e3, 3)
        self.server.kv_transfer_failures += 1
        self.logger.warning("%s: kv fetch %s timed out — local "
                            "prefill fallback", self.name, token)
        self.server.submit(request)
        self._ensure_pumping()

    def _on_kv_message(self, _topic: str, payload: str):
        """``(kv_export_response token swag)`` from the owner:
        import, then submit the parked request (the admission hit
        walk adopts the imported blocks)."""
        from ..pipeline.codec import decode_swag
        try:
            command, params = parse(payload)
        except Exception:
            return
        if command != "kv_export_response" or len(params) < 2:
            return
        request = self._kv_pending.pop(str(params[0]), None)
        started = self._kv_started.pop(str(params[0]), None)
        if request is None:
            return                    # timed out already; late reply
        try:
            outputs = decode_swag(params[1])
            if "error" in outputs:
                self.server.kv_transfer_failures += 1
            else:
                # Async landing: the keys register behind the
                # RESTORING sentinel now, the rows land a few blocks
                # per step — the submit below parks on the hit walk's
                # restore_wait defer until the chain is whole, and
                # decode keeps producing meanwhile.
                self.server.kv_import_payload(
                    outputs, engine=self.process.event,
                    async_import=True)
                remote = outputs.get("trace_spans")
                if remote:
                    request.remote_spans = str(remote)
        except Exception:  # noqa: BLE001 - fall back to local prefill
            self.logger.exception("%s: kv import failed", self.name)
            self.server.kv_transfer_failures += 1
        if started is not None:
            request.kv_restore_ms = round(
                (time.monotonic() - started) * 1e3, 3)
        self.server.submit(request)
        self._ensure_pumping()

    def _wire_cancel(self, request_id, response_topic=None):
        """``(infer_cancel request_id [response_topic])``: the
        cancelled request's normal ``infer_response`` (error
        ``cancelled``, any partial tokens) is the acknowledgement.  An
        unknown id — already responded, or aged out — resolves the
        caller's future with ``error="cancel_unrouted"`` when a reply
        topic rides along (the true response may still arrive first;
        the client's terminal-state race rules apply)."""
        if self.server.cancel(str(request_id)):
            self._ensure_pumping()
            return
        self.logger.info("%s: infer_cancel for unknown id %s",
                         self.name, request_id)
        if response_topic:
            from ..pipeline.codec import encode_swag
            self.process.message.publish(
                str(response_topic),
                generate("infer_response",
                         [request_id,
                          encode_swag({"error": "cancel_unrouted"})]))

    def _wire_adapter_load(self, request_id, response_topic,
                           payload=None):
        """``(adapter_load id resp (name: n) (path: dir))``: import a
        PEFT-layout adapter directory and make it servable — deploy a
        fine-tune to a RUNNING replica.  Responds
        ``(adapter_response id ok|error …)``."""
        def action(inputs):
            from ..tools.import_weights import import_lora
            name = str(inputs["name"])
            lora_params, lora_config = import_lora(
                str(inputs["path"]), self.server.config)
            self.server.load_adapter(name, lora_params, lora_config)
            return name

        self._adapter_action("adapter_load", action, request_id,
                             response_topic, payload)

    def _wire_adapter_unload(self, request_id, response_topic,
                             payload=None):
        def action(inputs):
            name = str(inputs["name"])
            self.server.unload_adapter(name)
            return name

        self._adapter_action("adapter_unload", action, request_id,
                             response_topic, payload)

    def _adapter_action(self, what, action, request_id, response_topic,
                        payload):
        from ..pipeline.codec import decode_swag, encode_swag
        try:
            name = action(decode_swag(payload or {}))
            outputs = {"ok": name,
                       "adapters": " ".join(
                           self.server.adapters_loaded)}
        except Exception as error:  # noqa: BLE001 - must respond
            self.logger.warning("%s: %s failed: %s", self.name, what,
                                error)
            outputs = {"error": str(error)}
        self._share_adapters()
        if response_topic:
            self.process.message.publish(
                str(response_topic),
                generate("adapter_response",
                         [request_id, encode_swag(outputs)]))

    def _share_adapters(self):
        loaded = " ".join(self.server.adapters_loaded)
        if self.share.get("adapters") == loaded:
            return
        self.share["adapters"] = loaded
        if self.ec_producer is not None:
            self.ec_producer.update("adapters", loaded)

    def _stream_partials(self):
        """Deliver newly decoded tokens for every live streaming
        request — one ``(infer_partial request_id swag)`` per pump
        with the increment since the last delivery."""
        for request in self.server.live_requests():
            self._emit_partial(request)

    def _emit_partial(self, request: DecodeRequest):
        if not (request.stream and request.response_topic
                and request.tokens):
            return
        sent = self._stream_sent.get(id(request), 0)
        if len(request.tokens) <= sent:
            return
        from ..pipeline.codec import encode_swag
        increment = np.asarray(request.tokens[sent:], np.int32)
        self._stream_sent[id(request)] = len(request.tokens)
        self.process.message.publish(
            request.response_topic,
            generate("infer_partial",
                     [request.request_id,
                      encode_swag({"tokens_out": increment})]))

    def _respond(self, request: DecodeRequest):
        from ..pipeline.codec import encode_swag
        # Flush the final streaming increment first: concatenated
        # partials always equal the final sequence.
        self._emit_partial(request)
        self._stream_sent.pop(id(request), None)
        if request.request_id in self._migrating_ids:
            # The migrated-away request reached a terminal state here
            # (usually the post-cutover cancel): this replica is no
            # longer anyone's migration source.
            self._migrating_ids.discard(request.request_id)
            if not self._migrating_ids \
                    and self.share.get("lifecycle") == "migrating":
                self.share["lifecycle"] = "ready"
                if self.ec_producer is not None:
                    self.ec_producer.update("lifecycle", "ready")
        self.share["requests_served"] += 1
        if self.ec_producer is not None:
            self.ec_producer.update("requests_served",
                                    self.share["requests_served"])
        if request.error is not None:
            outputs: Dict = {"error": request.error}
            if request.error == "cancelled" and request.tokens:
                # Partial tokens are real work the client may keep.
                outputs["tokens_out"] = np.asarray(request.tokens,
                                                   np.int32)
            if request.retry_after_ms is not None:
                outputs["retry_after_ms"] = int(request.retry_after_ms)
        else:
            outputs = {"tokens_out": np.asarray(request.tokens,
                                                np.int32)}
        if request.spec_accepted_rounds is not None:
            # Per-round accepted-token counts (draft replicas only):
            # the client-side acceptance histogram loadgen A/B runs
            # aggregate without touching server internals.
            outputs["spec_accepted_rounds"] = np.asarray(
                request.spec_accepted_rounds, np.int32)
        served = request.error is None
        phases = self._phase_latencies(request)
        for phase, seconds in phases.items():
            outputs[f"{phase}_ms"] = round(seconds * 1e3, 2)
        if served:
            # Aggregates track SERVED requests only: a burst of
            # queued-then-cancelled requests must not drag the
            # dashboard's p50 toward zero.
            for phase, seconds in phases.items():
                self.server.latency_hists[phase].observe(seconds * 1e3)
            self._note_slow(request, phases)
        if request.trace_ctx:
            outputs["trace_spans"] = self._request_spans(request)
        if request.response_topic:
            encoded = encode_swag(outputs)
            if faults.PLAN is not None:
                if faults.PLAN.check("corrupt_response",
                                     key=request.request_id) is not None:
                    # Undecodable swag on the wire: the client resolves
                    # the future with error="corrupt_response".
                    encoded = "!corrupt!"
            self.process.message.publish(
                request.response_topic,
                generate("infer_response",
                         [request.request_id, encoded]))

    def _phase_latencies(self, request: DecodeRequest) -> Dict[str, float]:
        """Seconds per phase from the request's lifecycle stamps:
        ``queue`` (submit→slot), ``prefill`` (slot→first token) with
        the three parts that tile it (``slice_wait``, ``prefill_run``,
        ``first_chunk``: :func:`ttft_parts`),
        ``decode`` (first→finish), the classic end-to-end ``ttft`` /
        ``total``, and any ``kv_restore`` time (the warm-start fetch
        runs BEFORE submission, so it is invisible to — not double-
        counted by — the queue phase).  Keys match the server's
        ``latency_hists`` phases and respond as ``<phase>_ms``."""
        out: Dict[str, float] = {}
        if request.submitted_ts is None:
            return out
        if request.first_token_ts is not None:
            out["ttft"] = request.first_token_ts - request.submitted_ts
        if request.finished_ts is not None:
            out["total"] = request.finished_ts - request.submitted_ts
        if request.activated_ts is not None:
            out["queue"] = request.activated_ts - request.submitted_ts
            if request.first_token_ts is not None:
                out["prefill"] = (request.first_token_ts
                                  - request.activated_ts)
                if request.finished_ts is not None:
                    out["decode"] = (request.finished_ts
                                     - request.first_token_ts)
        out.update(ttft_parts(request))     # ``queue`` is the same
        if request.kv_restore_ms:
            out["kv_restore"] = request.kv_restore_ms / 1e3
        return out

    def _prefill_children(self, request: DecodeRequest, prefill_span,
                          offset: float) -> List:
        """The ``prefill`` span's children, which tile it: where the
        time from slot to first token went (:func:`ttft_parts`)."""
        from ..obs import trace
        stamps = _ttft_stamps(request)
        if stamps is None:
            return []
        parent = trace.inject(prefill_span)
        children = []
        for part, start, end in zip(TTFT_PARTS[1:], stamps[1:],
                                    stamps[2:]):
            attrs = {"request_id": request.request_id}
            if part == "prefill_run":
                attrs.update(
                    slices=request.prefill_dispatches,
                    tokens_dispatched=request.prefill_tokens,
                    prompt_tokens=(len(request.prompt)
                                   - request.shared_tokens),
                    shared_tokens=request.shared_tokens)
            children.append(trace.synth_span(
                part, parent, self.name, offset + start, offset + end,
                attrs=attrs))
        return children

    _SLOW_K = 5

    def _note_slow(self, request: DecodeRequest,
                   phases: Dict[str, float]) -> None:
        """Track the top-k slowest served requests with their phase
        breakdown — the dashboard's \"slowest requests\" pane."""
        total = phases.get("total")
        if total is None:
            return
        self._slow.append((round(total * 1e3, 1), request.request_id,
                           {phase: round(seconds * 1e3, 1)
                            for phase, seconds in phases.items()}))
        self._slow.sort(key=lambda entry: -entry[0])
        del self._slow[self._SLOW_K:]

    def _request_spans(self, request: DecodeRequest) -> str:
        """Synthesize this replica's phase spans for a TRACED request
        (``trace_ctx`` arrived on the wire) from its lifecycle stamps
        — no tracer calls anywhere near the engine hot path, and an
        untraced request pays exactly one ``is None`` test.

        The monotonic stamps convert to the epoch-aligned span clock
        through one wall-clock anchor taken here; sub-ms skew at
        worst, far below the cross-process clock sync the tree
        already tolerates."""
        from ..obs import trace
        offset = time.time() - time.monotonic()
        spans = []
        if request.submitted_ts is not None:
            submitted = offset + request.submitted_ts
            finished = offset + (request.finished_ts
                                 or request.submitted_ts)
            restore_s = request.kv_restore_ms / 1e3
            replica_span = trace.synth_span(
                "replica", request.trace_ctx, self.name,
                submitted - restore_s, finished,
                attrs={"request_id": request.request_id,
                       "tokens_out": len(request.tokens or [])})
            if request.error is not None:
                replica_span.set_attr("error", request.error)
            spans.append(replica_span)
            parent = trace.inject(replica_span)
            if restore_s:
                spans.append(trace.synth_span(
                    "kv_restore", parent, self.name,
                    submitted - restore_s, submitted))
            if request.activated_ts is not None:
                activated = offset + request.activated_ts
                spans.append(trace.synth_span(
                    "queue", parent, self.name, submitted, activated))
                if request.first_token_ts is not None:
                    first = offset + request.first_token_ts
                    prefill_span = trace.synth_span(
                        "prefill", parent, self.name, activated,
                        first)
                    spans.append(prefill_span)
                    spans.extend(self._prefill_children(
                        request, prefill_span, offset))
                    decode_span = trace.synth_span(
                        "decode", parent, self.name, first, finished)
                    decode_span.mark("first_token", first)
                    decode_span.mark("last_token", finished)
                    spans.append(decode_span)
        encoded = [span.to_dict() for span in spans]
        if request.remote_spans:
            encoded.extend(span.to_dict() for span in
                           trace.decode_spans(request.remote_spans))
        return trace.encode_spans(encoded)
