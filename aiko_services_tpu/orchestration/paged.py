"""Paged-KV continuous batching (vLLM-style block pool on TPU).

The contiguous :class:`~.continuous.ContinuousBatchingServer` reserves
``slots × max_seq`` KV rows up front, so HBM — not demand — caps the
slot count when ``max_seq`` is large.  The paged server backs ALL slots
with one block pool (``n_blocks × block_size`` rows per layer) and
per-slot block tables; a request holds only the blocks its actual
length needs, so a 32k-capable replica admits many short requests at
once.

Static-shape TPU design (no dynamic allocation inside jit):

* The pool, tables, positions, and active mask are fixed-shape arrays;
  :func:`~..models.llama.decode_chunk_paged` scans whole chunks in one
  compiled program, writing each slot's row at ``(table[pos//bs],
  pos%bs)`` with a single batched scatter and reading attention via a
  block-table gather that reuses the contiguous cache's masked-GQA
  implementation verbatim.
* Allocation policy: **worst-case reservation, preemption-free** — at
  admission a request reserves blocks for ``prompt_bucket +
  max_new_tokens`` rows and keeps them until retirement.  Admission
  defers (stays queued) when the pool cannot cover that; nothing can
  run out of blocks mid-flight, so decode never preempts or restarts a
  request.  The statistical win over the contiguous layout is that the
  reservation is the REQUEST's worst case, not ``max_seq``.
* Block 0 is reserved scratch: unallocated table entries point at it
  and inactive slots write there; absolute-position masking keeps it
  unattendable.
* **Tiered KV cache** (``host_tier_blocks > 0``): leaf-first eviction
  DEMOTES zero-ref cached blocks to pinned host RAM (device→host copy
  of the block rows via the transfer codec's gather) instead of
  deleting them — the chain index keeps demoted chains addressable as
  a HOST state.  A prefix hit against a demoted chain starts an
  ASYNCHRONOUS restore: host rows promote back into freshly allocated
  pool blocks a few per step (``restore_blocks_per_step``), riding the
  same async-dispatch discipline as chunked admission, with
  ``_producing``-style miss semantics until landed — decode never
  stalls on a restore and never reads a half-landed chain
  (ARCHITECTURE invariant 10).  All of it host-side bookkeeping: no
  tier branch exists in any traced module (invariant 7, jaxpr/AST
  pinned in tests/test_kv_tier.py).
* **SSD spill tier** (``spill_dir=``): host-RAM overflow demotes block
  rows to a crash-durable spill directory (:mod:`~..kvstore.spill`:
  write-temp + fsync + rename groups, CRC-sealed headers carrying the
  full chain identity) instead of purging them, and a respawned
  replica re-adopts the directory at startup — a crash restart is a
  WARM start, advertised at tier 2 in the prefix digest.  A checksum
  trip NEVER serves the bytes: the chain degrades to plain recompute
  and ``kv_checksum_failures`` increments (ARCHITECTURE invariant 13).
  One eviction clock spans HBM → host → disk, so every tier's
  overflow drops the globally coldest remnant.

Greedy outputs exactly match the contiguous server and per-request
``generate_tokens`` (tested) — paging changes memory shape only.
"""

from __future__ import annotations

import heapq
import itertools
import math
import time
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from ..kvstore import adapters as _kvadp
from ..kvstore import directory as _kvdir
from ..kvstore import transfer as _kvxfer
from ..models import lora_paged as _lorapg
from ..obs import compiles, pool_audit, steplog
from ..runtime import faults as _faults
from ..runtime.lease import Lease
from .continuous import ContinuousBatchingServer

__all__ = ["PagedContinuousServer"]

#: ``_producing`` owner sentinel for blocks whose content is an
#: in-flight host→device restore upload (real owners are slot ids
#: ≥ 0, so no slot's cancel/finish path can ever claim these).
RESTORING = -1


class PagedContinuousServer(ContinuousBatchingServer):
    """Continuous batching over a paged KV pool.

    ``total_blocks`` sizes the pool (excluding the scratch block);
    default covers half of ``slots × max_seq`` — the break-even point
    where paging admits the same worst case in half the HBM.
    """

    #: Default chunked-prefill slice width (tokens).  Chunked admission
    #: is the paged backend's DEFAULT mode: prompts longer than this
    #: admit through mixed prefill/decode steps (one append-attention
    #: slice folded into each decode dispatch) instead of stalling the
    #: batch for their whole prefill.  Pass ``chunk_prefill_tokens=0``
    #: to restore whole-bucket admission.
    DEFAULT_CHUNK_PREFILL_TOKENS = 256

    def __init__(self, config_name: str = "tiny", slots: int = 4,
                 max_seq: Optional[int] = None, chunk_steps: int = 8,
                 quantize: bool = False, eos_id: Optional[int] = None,
                 seed: int = 0, quantize_kv: bool = False,
                 block_size: int = 16,
                 total_blocks: Optional[int] = None,
                 enable_prefix_cache: bool = False,
                 lookahead: int = 1, adapters=None, lora_config=None,
                 params=None,
                 chunk_prefill_tokens: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 watchdog_s: float = 0.0, replica_mesh=None,
                 host_tier_blocks: Optional[int] = None,
                 restore_blocks_per_step: int = 4,
                 spill_dir: Optional[str] = None,
                 spill_blocks: Optional[int] = None,
                 spill_adopt: bool = True,
                 draft_config_name: Optional[str] = None,
                 draft_params=None, spec_k: int = 4,
                 draft_quantize: bool = False,
                 draft_mode: str = "auto", spec_ladder=None,
                 spec_adaptive: bool = False, automata=None,
                 compilation_cache_dir: Optional[str] = None,
                 compact_upload: bool = True,
                 ring_max: Optional[int] = None):
        self.block_size = block_size
        self._requested_blocks = total_blocks
        self.enable_prefix_cache = enable_prefix_cache
        #: Host-RAM demotion tier capacity in blocks (0/None disables
        #: the tier — eviction deletes, the pre-tier behavior).  Host
        #: rows are full kv-head width in the pool's native dtype, so
        #: a block costs the same bytes as on device.
        self.host_tier_blocks = int(host_tier_blocks or 0)
        #: Restore upload rate: host→device blocks landed per engine
        #: step (one batched scatter).  Bounds the per-step host work
        #: so a long restore overlaps many decode dispatches instead
        #: of stalling one.
        self.restore_blocks_per_step = max(1,
                                           int(restore_blocks_per_step))
        #: SSD spill tier (kvstore/spill.py): directory where host-RAM
        #: overflow demotes block rows instead of purging them —
        #: crash-durable, re-adopted at startup.  None disables (the
        #: two-tier behavior).
        self.spill_dir = str(spill_dir) if spill_dir else None
        #: Disk tier capacity in blocks; overflow drops the coldest
        #: remnant by the shared eviction clock.
        self.spill_blocks = int(spill_blocks) if spill_blocks else 1024
        #: Scan + re-adopt the spill directory at startup (the warm
        #: restart); off for pools that want a private scratch dir.
        self.spill_adopt = bool(spill_adopt)
        if chunk_prefill_tokens is None:
            chunk_prefill_tokens = self.DEFAULT_CHUNK_PREFILL_TOKENS
        super().__init__(config_name=config_name, slots=slots,
                         max_seq=max_seq, chunk_steps=chunk_steps,
                         quantize=quantize, eos_id=eos_id, seed=seed,
                         quantize_kv=quantize_kv, lookahead=lookahead,
                         adapters=adapters, lora_config=lora_config,
                         params=params,
                         chunk_prefill_tokens=chunk_prefill_tokens,
                         max_queue=max_queue, watchdog_s=watchdog_s,
                         replica_mesh=replica_mesh,
                         draft_config_name=draft_config_name,
                         draft_params=draft_params, spec_k=spec_k,
                         draft_quantize=draft_quantize,
                         draft_mode=draft_mode, spec_ladder=spec_ladder,
                         spec_adaptive=spec_adaptive, automata=automata,
                         compilation_cache_dir=compilation_cache_dir,
                         compact_upload=compact_upload,
                         ring_max=ring_max)

    # ------------------------------------------------------------- #
    # Layout hooks

    def _init_layout(self):
        block_size = self.block_size
        if self.max_seq % block_size:
            raise ValueError(
                f"max_seq {self.max_seq} not a multiple of block_size "
                f"{block_size}")
        # Prompt buckets must land on block boundaries: raise the
        # bucket floor to one block, and require the floor to be a
        # block multiple (buckets double from the floor, so every
        # bucket then is too).
        self._bucket_minimum = max(self._bucket_minimum, block_size)
        if self._bucket_minimum % block_size:
            raise ValueError(
                f"block_size {block_size} must divide the prompt "
                f"bucket floor {self._bucket_minimum}")
        # Chunked-prefill slices append straight into block chains, so
        # every slice boundary must land on a block boundary (the
        # append kernel's cached_len is block-aligned by construction).
        if self.chunk_prefill_tokens % block_size:
            raise ValueError(
                f"chunk_prefill_tokens {self.chunk_prefill_tokens} "
                f"must be a multiple of block_size {block_size} on "
                "the paged backend (slices land on block boundaries)")
        max_blocks = self.max_seq // block_size
        if self._composed:
            # Two kinds of row in one pool: a slot's table row is the
            # module's own layout, as wide as max_seq can make it.
            self._model.check_layout(self.config, block_size,
                                     self.chunk_prefill_tokens)
            max_blocks = self._model.table_blocks(
                self.config, self.max_seq, block_size)
        if self._requested_blocks is None:
            usable = max(max_blocks,
                         self.slots * max_blocks // 2)
        else:
            usable = self._requested_blocks
        self._refuse_unsupported(
            prefix_cache=self.enable_prefix_cache,
            host_tier=self.host_tier_blocks > 0,
            spill=self.spill_dir is not None)
        self.pool = self._model.init_paged_cache(
            self.config, usable + 1, block_size,
            quantize_kv=self.quantize_kv,            # +1: scratch
            slots=self.slots)
        self._tp_engine = None
        if self._mesh is not None:
            # TP replica: the pool becomes a GLOBAL jax.Array sharded
            # on its kv-head axis over the replica mesh; every model
            # dispatch below routes through the shard_map TPEngine.
            # Host-side block bookkeeping (tables, free lists, prefix
            # index, transfer export/import) keeps operating on the
            # full-width global view — jax resolves per-shard slices.
            self.pool = self._llama_tp.shard_pool(
                self.pool, self._mesh, self.replica_mesh.axis)
            rm = self.replica_mesh
            self._tp_engine = self._llama_tp.TPEngine(
                self.config, self._mesh, self.params, self.pool,
                axis=rm.axis,
                sp_axis=rm.sp_axis if rm.sp > 1 else None,
                ep_axis=rm.ep_axis if rm.ep > 1 else None,
                overlap=rm.overlap)
        if self._draft is not None:
            # Draft KV lives IN the paged tier (PR 17): its own pool
            # with the target's exact geometry (usable+1 blocks of
            # block_size), NAVIGATED BY THE TARGET'S BLOCK TABLES —
            # zero extra allocator bookkeeping, and the memory is
            # census-visible (``draft`` section of pool_census)
            # instead of a hidden slots×max_seq contiguous slab.
            # Sharing tables is safe because draft KV only ever
            # affects PROPOSAL QUALITY, never committed output
            # (acceptance always verifies against the target):
            # prefix-cache-shared blocks get identical draft content
            # (same tokens ⇒ same prefill), and any block-reuse
            # staleness costs at most a rejected proposal.
            self._draft.pop("cache", None)
            draft_pool = self._llama.init_paged_cache(
                self._draft["config"], usable + 1, block_size)
            if self._mesh is not None:
                # Replicated on the mesh (the draft runs the plain
                # jitted paged programs on every chip — no
                # collectives, identical proposal streams: the same
                # TP-parity argument as the contiguous draft cache).
                draft_pool = self._llama_tp.replicate(draft_pool,
                                                      self._mesh)
            self._draft["pool"] = draft_pool
        self.tables = np.zeros((self.slots, max_blocks), np.int32)
        self.total_blocks = usable
        self._free: List[int] = list(range(1, usable + 1))
        self._owned: List[List[int]] = [[] for _ in range(self.slots)]
        # Prefix cache state (content-addressed blocks):
        #   _index: chain-key -> block_id for every cached FULL prompt
        #     block (key = (parent_key, tokens-in-block tuple))
        #   _block_key / _refs: reverse map + per-block reference count
        #   _evictable: zero-ref cached blocks in LRU order
        #   _pending: per-slot (n_shared_blocks,) staged between
        #     _reserve_slot and _prefill_bucket
        self._index: dict = {}
        self._block_key: dict = {}
        self._refs: dict = {}
        #: chain-key -> adapter id that seeded it (hot unload/replace
        #: must purge exactly that adapter's cached blocks).
        self._key_seed: dict = {}
        self._evictable: "OrderedDict[bytes, int]" = OrderedDict()
        #: chain topology: child key -> parent key, and per-key count
        #: of INDEXED children (leaf-first eviction reads this).
        self._parent: dict = {}
        self._children: dict = {}
        self._pending_shared: List[int] = [0] * self.slots
        #: block -> slot whose chunked prefill has not yet written the
        #: block's content.  An admission whose hit walk reaches one
        #: is deferred: their keys are registered (so no duplicate
        #: block is indexed) but the KV only lands slice by slice over
        #: the next steps.  Cleared at _finish_prefill; purged on
        #: cancel.
        self._producing: dict = {}
        # Distributed KV-cache state (kvstore subsystem):
        #   _hex_key: directory-width hex16 -> full chain key (block
        #     EXPORT requests arrive with truncated keys)
        #   _depth: chain key -> position in its chain (1-based)
        #   _key_hits: chain key -> admission hit count (digest
        #     hotness signal; drives advertisement selection)
        #   _imported_keys: keys whose content arrived by transfer —
        #     the first admission adopting one counts a remote hit.
        self._hex_key: dict = {}
        self._depth: dict = {}
        self._key_hits: dict = {}
        self._imported_keys: set = set()
        # Tiered KV cache (host-RAM demotion tier):
        #   _host: chain key -> {"rows": {l<i>_<name>: (block_size,
        #     ...) ndarray}, "nbytes": int} for every DEMOTED block,
        #     insertion order = demotion order (leaf-first eviction
        #     demotes children before parents, so overflow popping the
        #     oldest entry always drops a chain's deepest remnant
        #     first — host chains stay rooted).  A key is in _index
        #     XOR _host, never both.  Demoted keys KEEP _depth,
        #     _parent, _key_seed, _hex_key, _key_hits: the chain stays
        #     addressable by hit walks, digests, and exports.
        #   _restoring: [{"key", "block", "rows", "group"}]
        #     host→device uploads waiting for _advance_restores; the
        #     blocks are allocated, indexed, ref-pinned, and
        #     _producing[block] = RESTORING.  Host-tier restores queue
        #     with group=None; async wire imports share a group dict
        #     (lease armed when the group's last block lands).
        #   _restored_keys: landed restores not yet adopted by an
        #     admission — the first adoption counts prefix_hits_host
        #     (mirrors _imported_keys / prefix_remote_hits).
        self._host: "OrderedDict[bytes, dict]" = OrderedDict()
        self._restoring: list = []
        self._restored_keys: set = set()
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_blocks_reused = 0
        self.prefix_evictions = 0
        self.prefix_remote_hits = 0
        self.kv_transfer_bytes = 0
        self.kv_transfer_ms = 0.0
        self.kv_transfer_failures = 0
        self.kv_demotions = 0
        self.kv_restores = 0
        self.kv_host_bytes = 0
        self.prefix_hits_host = 0
        # Fused transfer-engine counters (kvstore/transfer.py writes
        # them): device→host syncs paid by exports/demotions, host-side
        # staging time, and wire imports landed step-overlapped.
        self.kv_export_sync_count = 0
        self.kv_transfer_host_ms = 0.0
        self.kv_imports_async = 0
        # Durable SSD spill tier (kvstore/spill.py):
        #   _spill: chain key -> {"nbytes": int} for every block whose
        #     rows live ON DISK, insertion order = spill order under
        #     ONE shared eviction clock (host overflow pops its oldest
        #     demotion, so disk overflow keeps dropping the globally
        #     coldest remnant).  A key resolves in exactly one of
        #     _index / _host / _spill; spilled keys KEEP the same
        #     chain-identity maps demoted keys do.
        #   _adopted_keys: chains re-adopted from disk by a warm
        #     restart and not yet promoted — advertised with the
        #     digest's adopted flag so peers can tell a survivor from
        #     a live working set.
        self._spill: "OrderedDict[bytes, dict]" = OrderedDict()
        self._adopted_keys: set = set()
        self._evict_clock = 0
        #: Cached per-block HBM byte size (obs/pool_audit.py census).
        self._block_bytes_cache: Optional[int] = None
        self.kv_spills = 0
        self.kv_disk_bytes = 0
        self.kv_disk_restores = 0
        self.kv_checksum_failures = 0
        self.kv_adopted_chains = 0
        self.kv_prefetch_promotions = 0
        self.spill = None
        if self.spill_dir:
            from ..kvstore.spill import SpillStore
            self.spill = SpillStore(self.spill_dir,
                                    _kvxfer.pool_signature(self),
                                    self.block_size)
            if self.spill_adopt:
                self._adopt_spill()

    def _init_device_state(self):
        state = super()._init_device_state()
        # Block tables ride the resident state: admission/retirement
        # mark the slot dirty and the row merges in at the next
        # dispatch — no per-run table upload.
        state["tables"] = self._jnp.asarray(self.tables)
        return state

    def _host_state(self):
        host = super()._host_state()
        host["tables"] = self.tables
        return host

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def stats(self) -> dict:
        out = super().stats()
        out.update(
            prefix_hits=self.prefix_hits,
            prefix_misses=self.prefix_misses,
            prefix_blocks_reused=self.prefix_blocks_reused,
            prefix_evictions=self.prefix_evictions,
            prefix_remote_hits=self.prefix_remote_hits,
            kv_transfer_bytes=self.kv_transfer_bytes,
            kv_transfer_ms=round(self.kv_transfer_ms, 2),
            kv_transfer_failures=self.kv_transfer_failures,
            kv_demotions=self.kv_demotions,
            kv_restores=self.kv_restores,
            kv_host_blocks=len(self._host),
            kv_host_bytes=self.kv_host_bytes,
            restore_queue_depth=len(self._restoring),
            prefix_hits_host=self.prefix_hits_host,
            kv_export_sync_count=self.kv_export_sync_count,
            kv_transfer_host_ms=round(self.kv_transfer_host_ms, 2),
            kv_imports_async=self.kv_imports_async,
            kv_spills=self.kv_spills,
            kv_disk_blocks=len(self._spill),
            kv_disk_bytes=self.kv_disk_bytes,
            kv_disk_restores=self.kv_disk_restores,
            kv_checksum_failures=self.kv_checksum_failures,
            kv_adopted_chains=self.kv_adopted_chains,
            kv_prefetch_promotions=self.kv_prefetch_promotions,
            free_blocks=self.free_blocks,
            total_blocks=self.total_blocks,
            # What a slot holds beside its blocks (a recurrent model's
            # convolution windows and states), and the stack's layers.
            state_bytes_per_slot=self._model.state_bytes_per_slot(
                self.config),
            layer_kinds=",".join(
                f"{kind}={count}" for kind, count
                in self._model.layer_kinds(self.config).items()),
            kv_hbm_blocks=self.total_blocks - len(self._free),
            kv_hbm_bytes=(self.total_blocks - len(self._free))
            * self._block_nbytes(),
            # What the pool costs: a position's bytes over every layer
            # field as the pool lays them out, and the whole pool's
            # (the scratch block included).
            kv_bytes_per_position=self._block_nbytes()
            // self.block_size,
            kv_pool_bytes=(self.total_blocks + 1) * self._block_nbytes(),
        )
        pages = self._adapter_page_counts()
        out.update(
            adapter_pages_hbm=pages["hbm"],
            adapter_pages_host=pages["host"],
            adapter_pages_disk=pages["disk"],
            adapter_warm_loads=self.adapter_warm_loads,
            adapter_cold_loads=self.adapter_cold_loads,
            adapters_loaded_count=len(self._adapter_index),
        )
        if pool_audit.AUDITOR is not None:
            out.update(
                kv_audit_sweeps=pool_audit.AUDITOR.sweeps,
                kv_audit_violations=pool_audit.AUDITOR
                .violations_total,
            )
        return out

    # ------------------------------------------------------------- #
    # Memory accountant (obs/pool_audit.py): ground-truth census +
    # tier-flow hooks.  ALL host-side bookkeeping; nothing below
    # runs inside, or changes, a traced program (jaxpr + AST pinned
    # in tests/test_pool_audit.py).

    def _block_nbytes(self) -> int:
        """HBM bytes one pool block holds across every layer field.
        Host rows are gathered at full kv-head width in the pool's
        native dtype, so a demoted block's ``nbytes`` equals this —
        the equality the census's per-tier byte math leans on."""
        if self._block_bytes_cache is None:
            self._block_bytes_cache = sum(
                row_bytes for _field, _shape, _dtype, row_bytes
                in _kvxfer._field_layout(self))
        return self._block_bytes_cache

    def _flow(self, name: str, blocks: int,
              nbytes: Optional[int] = None) -> None:
        """Book one tier flow with the accountant (no-op pointer test
        when the auditor is uninstalled).  ``nbytes`` defaults to the
        HBM block size — host/disk sites pass their entry's bytes."""
        if pool_audit.AUDITOR is not None:
            if nbytes is None:
                nbytes = int(blocks) * self._block_nbytes()
            pool_audit.AUDITOR.flow(name, int(blocks), int(nbytes))

    def pool_census(self, max_records: int = 64) -> dict:
        """Byte-exact ground-truth pool census across the tier tower
        (the memory accountant's source of truth).  A host-side dict
        walk only — no device sync, safe to call from the ``(census)``
        wire command while the engine serves.  ``blocks`` carries up
        to ``max_records`` per-block attribution records (owner chain
        key, depth, tier, bytes, refcount, pin/producing/RESTORING
        state, adapter-seeded flag); the tier and state totals are
        always exact regardless of the cap."""
        block_bytes = self._block_nbytes()
        used = self.total_blocks - len(self._free)
        producing = restoring = 0
        for owner in self._producing.values():
            if owner == RESTORING:
                restoring += 1
            else:
                producing += 1
        pinned = evictable = 0
        for block in self._block_key:
            if block in self._producing:
                continue
            if self._refs.get(block, 0):
                pinned += 1
            else:
                evictable += 1
        private = sum(1 for blocks in self._owned for block in blocks
                      if block not in self._block_key)
        records = []
        for block, key in self._block_key.items():
            if len(records) >= max_records:
                break
            owner = self._producing.get(block)
            state = ("restoring" if owner == RESTORING
                     else "producing" if owner is not None
                     else "pinned" if self._refs.get(block, 0)
                     else "evictable")
            records.append(dict(
                block=block, tier="hbm",
                key=key.hex()[:_kvdir.HEX_KEY_CHARS],
                depth=self._depth.get(key, 0), bytes=block_bytes,
                refs=self._refs.get(block, 0), state=state,
                adapter=bool(self._key_seed.get(key, 0))))
        for tier, store in (("host", self._host),
                            ("disk", self._spill)):
            for key, entry in store.items():
                if len(records) >= max_records:
                    break
                records.append(dict(
                    tier=tier, key=key.hex()[:_kvdir.HEX_KEY_CHARS],
                    depth=self._depth.get(key, 0),
                    bytes=int(entry["nbytes"]), refs=0, state=tier,
                    clock=int(entry.get("clock", 0)),
                    adopted=key in self._adopted_keys,
                    adapter=bool(self._key_seed.get(key, 0))))
        try:
            dtype = next(iter(_kvxfer._field_layout(self)))[2].name
        except StopIteration:
            dtype = ""
        # Pool-resident draft KV (speculation v2, model mode): its own
        # SECTION, not a tier — the draft pool shadows the target's
        # block tables 1:1 (used count mirrors the target's) and never
        # participates in the prefix-cache/host/disk tier flows the
        # auditor balances, so the tier equations stay exact.
        draft_section = None
        draft_block_bytes = self._draft_block_nbytes()
        if draft_block_bytes:
            draft_section = dict(
                block_bytes=draft_block_bytes,
                total_blocks=self.total_blocks,
                blocks=used, bytes=used * draft_block_bytes)
        # Multi-tenant adapter view: weight-page residency per tier
        # (ADAPTER_SEED keys — a subset of the tier totals above, not
        # a new tier) and per-adapter live slot occupancy from the
        # host-side id mirror.  No device sync.
        adapter_section = dict(
            pages=self._adapter_page_counts(),
            slots=self.adapter_slot_counts())
        # Two kinds of row in one pool: of every slot's private blocks,
        # the ring of its window's exact rows and the rest, summaries
        # (a split of ``states.private``, not a tier).
        kinds = None
        if self._composed:
            ring, _ = self._model.block_kinds(self.config,
                                              self.block_size)
            exact = sum(min(len(blocks), ring) for blocks in self._owned)
            kinds = dict(window=exact, summary=private - exact)
        return dict(
            kinds=kinds,
            ts=time.time(), dtype=dtype, block_bytes=block_bytes,
            total_blocks=self.total_blocks,
            evict_clock=self._evict_clock,
            restore_queue_depth=len(self._restoring),
            adopted_chains=len(self._adopted_keys),
            draft=draft_section,
            adapters=adapter_section,
            tiers=dict(
                hbm=dict(blocks=used, bytes=used * block_bytes),
                host=dict(blocks=len(self._host),
                          bytes=int(self.kv_host_bytes)),
                disk=dict(blocks=len(self._spill),
                          bytes=int(self.kv_disk_bytes))),
            states=dict(free=len(self._free), private=private,
                        producing=producing, restoring=restoring,
                        pinned=pinned, evictable=evictable,
                        host=len(self._host), disk=len(self._spill)),
            blocks=records)

    def _pool_fault_check(self) -> None:
        """Pool-accounting corruption faults (``leak_block`` /
        ``skew_refcount``): deliberately unbalance the bookkeeping
        WITHOUT touching any row a request reads — serving stays
        bit-exact, and only the pool auditor can tell anything
        happened (the detection tests lean on exactly that)."""
        params = _faults.PLAN.check("leak_block", key="paged_pool")
        if params is not None and self._free:
            self._free.pop()          # no owner registered: a leak
        params = _faults.PLAN.check("skew_refcount", key="paged_pool")
        if params is not None:
            for block in self._block_key:
                self._refs[block] = self._refs.get(block, 0) \
                    + int(params.get("by", 2))
                break

    def _attention_blocks(self):
        # Real pool geometry: the kernel walks the slot's block table.
        return self.block_size, self.tables.shape[1]

    def _kv_geometry(self):
        head_dim, kv_heads, dtype = super()._kv_geometry()
        return head_dim, kv_heads // self.tp_degree, dtype

    def _attention_paths(self):
        """Decode walks the block table; admission appends slices of
        ``chunk_prefill_tokens`` (whole pow2 buckets when 0)."""
        from ..ops.paged_attention import decode_attention_path
        from ..ops.paged_prefill import prefill_attention_path
        chunk = self.chunk_prefill_tokens or self._bucket_minimum
        own = getattr(self._model, "attention_paths", None)
        if own is not None:
            # A pool the K/V kernels' dispatch does not describe.
            return own(self.config, self.block_size, chunk)
        geometry = self._kv_geometry()
        return (decode_attention_path(*geometry),
                prefill_attention_path(*geometry, self.block_size,
                                       chunk))

    def _scale_append_path(self) -> str:
        from ..ops.paged_attention import decode_scale_append_path
        if self._mesh is not None and self.quantize_kv:
            return "scatter"    # the shard_map engine's scans carry planes
        return decode_scale_append_path(*self._kv_geometry(),
                                        self.block_size)

    def _slice_key_blocks(self, start: int, width: int) -> int:
        """Key blocks x query tiles the append attention of one slice
        ``[start, start + width)`` has to visit, one layer's worth, at
        this server's (per-shard) head geometry."""
        from ..ops.paged_prefill import prefill_key_blocks
        config = self.config
        own = getattr(self._model, "slice_key_blocks", None)
        if own is not None:
            return own(config, start, width, self.block_size)
        return prefill_key_blocks(
            start, width, self.block_size, config.sliding_window,
            heads=config.n_heads // self.tp_degree,
            group=config.n_heads // config.n_kv_heads,
            itemsize=self._jnp.dtype(config.dtype).itemsize)

    def _blocks_for(self, rows: int) -> int:
        return math.ceil(rows / self.block_size)

    def _spec_headroom(self) -> int:
        """Rows past the live position a speculative verify may write:
        the (k+1)-token window lands at ``[pos, pos + k + 1)``, so a
        spec-enabled reservation covers k+1 rows beyond the plain
        worst case (the admission check already bounds prompt + new +
        k + 1 by max_seq, so this never overflows a table).  Sized by
        the LADDER TOP — adaptive rounds can only narrow."""
        if self._block_length:
            # Generation by block passes: the answer's last block is
            # written whole, up to a block past the asked length.
            return self._block_length
        return self._spec["k"] + 1 if self._spec is not None else 0

    def _worst_case_blocks(self, prompt_len: int, max_new: int) -> int:
        from .continuous import _bucket
        padded = min(_bucket(prompt_len, self._bucket_minimum),
                     self.max_seq)
        return self._slot_blocks(padded, prompt_len, max_new)

    def _slot_blocks(self, padded: int, prompt_len: int,
                     max_new: int) -> int:
        """Blocks a request holds from admission to release: the rows
        it can ever touch — the padded prompt bucket (prefill writes
        all its rows) plus every generated token, plus the speculative
        verify window's k+1 rows when a draft is configured — and
        never more than max_seq (submit() bounds prompt+new to
        max_seq-1, so the bucket-rounded sum may overshoot max_seq
        while the rows actually touched cannot).  Where the module
        keeps two kinds of row, its own count for the positions the
        request writes: a bucket's padding lands in the ring and in
        summary rows of the prompt's last window, which it holds
        anyway."""
        if self._composed:
            return self._model.slot_blocks(
                self.config, min(prompt_len + max_new, self.max_seq),
                self.block_size)
        return self._blocks_for(min(
            padded + max_new + self._spec_headroom(), self.max_seq))

    def _admission_reject(self, prompt_len: int, request):
        reason = super()._admission_reject(prompt_len, request)
        if reason:
            return reason
        # Never queue what can never run: a head request whose worst
        # case exceeds the WHOLE pool would defer forever and starve
        # the FIFO behind it.
        if self._worst_case_blocks(prompt_len,
                                   request.max_new_tokens) \
                > self.total_blocks:
            return "request_exceeds_pool"
        return None

    # ------------------------------------------------------------- #
    # Prefix cache (content-addressed full prompt blocks)

    def _chain_keys(self, prompt, adapter_id: int = 0) -> List[bytes]:
        """Chained content keys, one per FULL prompt block (vLLM's
        rolling-hash scheme, adapter-seeded).  Defined in
        :mod:`~..kvstore.directory` so the router and every replica
        compute byte-identical keys from tokens alone — the contract
        the cluster-wide prefix directory rests on."""
        return _kvdir.chain_keys(prompt, self.block_size, adapter_id)

    def _shareable_blocks(self, prompt_len: int) -> int:
        """Blocks safe to SHARE: full blocks strictly before position
        ``prompt_len - 1`` — the admission seed rewrites the last
        prompt position's KV row, and a rewrite (bit-identical in
        principle, batch-width rounding in practice) must never land
        in a block other requests read.  Also the TRANSFER bound: an
        imported block is never rewritten by the importer's admission
        seed, which is what makes transferred-prefix decode bit-exact
        (docs/ARCHITECTURE.md invariant 6)."""
        return _kvdir.shareable_blocks(prompt_len, self.block_size)

    def _purge_cached(self, key, block) -> None:
        self._index.pop(key, None)
        self._evictable.pop(key, None)
        self._block_key.pop(block, None)
        self._refs.pop(block, None)
        self._key_seed.pop(key, None)
        self._depth.pop(key, None)
        self._key_hits.pop(key, None)
        self._imported_keys.discard(key)
        hex_key = key.hex()[:_kvdir.HEX_KEY_CHARS]
        if self._hex_key.get(hex_key) == key:
            del self._hex_key[hex_key]
        parent = self._parent.pop(key, None)
        if parent is not None and parent in self._children:
            self._children[parent] -= 1
            if self._children[parent] <= 0:
                del self._children[parent]
        self._children.pop(key, None)
        self._free.append(block)
        self._flow("free", 1)

    def _evict_one(self) -> bool:
        """Evict ONE zero-ref cached block: the least-recently-used
        chain LEAF (no indexed children).  Leaf-first keeps chains
        rooted — no stale descendant bindings — and frees exactly one
        block per call instead of flushing a whole cached chain when a
        single block would do.  A leaf always exists: an evictable
        entry's indexed children are themselves evictable (owners of a
        child own the whole prefix path).

        With a host tier (or spill tier) configured, eviction DEMOTES
        instead of deleting: the block's rows copy down the tower and
        the chain key stays addressable (restored on the next hit).
        Positive-seeded KV chains (per-request adapter KV) still
        delete — their stacked indices are replica-local and hot
        unload must be able to purge them synchronously.  Adapter
        WEIGHT pages (``ADAPTER_SEED``) demote like base KV: a cold
        adapter sinking down the tower is the unified-paging win."""
        for key, block in self._evictable.items():          # LRU order
            if self._children.get(key, 0) == 0:
                if self._tier_enabled() \
                        and self._key_seed.get(key, 0) <= 0:
                    self._demote(key, block)
                else:
                    self._purge_cached(key, block)
                    self.prefix_evictions += 1
                return True
        return False

    # ------------------------------------------------------------- #
    # Tiered KV cache: host-RAM demotion tier + async restore.  ALL
    # host-side bookkeeping — no method here runs inside, or changes,
    # a traced serve-chunk program (jaxpr + AST guards in
    # tests/test_kv_tier.py).

    def _demote(self, key, block) -> None:
        """Move one zero-ref cached block's rows to the host tier and
        free its pool block.  The chain identity (_depth, _parent,
        _key_seed, _hex_key, _key_hits) survives — only the HBM
        binding drops.  The parent's indexed-children count decrements
        (leaf-first order then demotes the parent next), and host
        overflow discards the OLDEST demotion — a chain's deepest
        remnant, so host chains stay rooted."""
        rows = _kvxfer.gather_block_rows(self, [block])
        self._demote_rows(key, block,
                          {name: np.ascontiguousarray(stack[0])
                           for name, stack in rows.items()})

    def _tier_enabled(self) -> bool:
        """Eviction demotes (host RAM and/or disk) instead of
        deleting.  A disabled spill store (disk full, write error)
        with no host tier reverts eviction to plain deletion."""
        return self.host_tier_blocks > 0 or (
            self.spill is not None and self.spill.enabled)

    def _demote_rows(self, key, block, row_dict) -> None:
        entry = {"rows": row_dict}
        entry["nbytes"] = sum(int(r.nbytes)
                              for r in entry["rows"].values())
        # One eviction clock spans the whole tower: stamped here at
        # demotion, carried into the disk header, restored by
        # adoption — so overflow ordering survives a restart.
        self._evict_clock += 1
        entry["clock"] = self._evict_clock
        self._index.pop(key, None)
        self._evictable.pop(key, None)
        self._block_key.pop(block, None)
        self._refs.pop(block, None)
        parent = self._parent.get(key)
        if parent is not None and parent in self._children:
            self._children[parent] -= 1
            if self._children[parent] <= 0:
                del self._children[parent]
        self._free.append(block)
        self._host[key] = entry
        self.kv_demotions += 1
        self.kv_host_bytes += entry["nbytes"]
        self._flow("demote", 1, entry["nbytes"])
        self._host_overflow()

    def _host_overflow(self) -> None:
        """Pop host-tier overflow and SPILL it to disk as one
        crash-consistent block group (kvstore/spill.py: every file
        staged + fsync'd, then renamed) — the tower's bottom rung.
        Entries the spill cannot take (no store, store disabled by a
        write error, positive-seeded per-request adapter KV) purge
        for good.  Disk overflow
        then drops the oldest-clock remnant, keeping the same
        leaf-first rootedness the host tier's ordering gives."""
        excess = []
        while len(self._host) > self.host_tier_blocks:
            excess.append(self._host.popitem(last=False))
        if not excess:
            return
        spilled = self._spill_entries(
            [(key, entry) for key, entry in excess
             if self.spill is not None and self.spill.enabled
             and self._key_seed.get(key, 0) <= 0])
        for key, entry in excess:
            if key in spilled:
                self._spill[key] = {"nbytes": entry["nbytes"],
                                    "clock": entry.get("clock", 0)}
                self.kv_host_bytes -= entry["nbytes"]
                self.kv_spills += 1
                self.kv_disk_bytes += entry["nbytes"]
                self._flow("spill", 1, entry["nbytes"])
            else:
                self._purge_host_entry(key, entry)
        while len(self._spill) > self.spill_blocks:
            old_key, old_meta = self._spill.popitem(last=False)
            self._purge_spill_entry(old_key, old_meta)

    def _spill_entries(self, items) -> set:
        """Write ``[(key, host_entry)]`` to the spill store as ONE
        block group; returns the set of keys durably on disk (empty
        when the store is off, disabled, or the write failed — the
        caller purges those entries instead, degrading gracefully)."""
        if not items or self.spill is None:
            return set()
        group = []
        for key, entry in items:
            parent = self._parent.get(key)
            group.append((key.hex(), dict(
                parent=parent.hex() if parent is not None else "",
                depth=int(self._depth.get(key, 0)),
                key_seed=int(self._key_seed.get(key, 0)),
                hits=int(self._key_hits.get(key, 0)),
                clock=int(entry.get("clock", 0))), entry["rows"]))
        if not self.spill.put_group(group):
            return set()
        return {key for key, _entry in items}

    def _purge_host_entry(self, key, entry) -> None:
        """A host-tier entry leaves the cache FOR GOOD (overflow with
        nowhere lower to go): now its chain identity goes too — this
        is the true eviction the tier deferred."""
        self.kv_host_bytes -= entry["nbytes"]
        self.prefix_evictions += 1
        self._flow("purge_host", 1, entry["nbytes"])
        self._purge_tier_identity(key)

    def _purge_spill_entry(self, key, meta) -> None:
        """A disk-tier entry leaves the cache FOR GOOD (capacity
        overflow or a failed checksum): file and chain identity both
        go — the bottom of the tower has nowhere lower."""
        if self.spill is not None:
            self.spill.discard(key.hex())
        self.kv_disk_bytes -= meta["nbytes"]
        self._adopted_keys.discard(key)
        self.prefix_evictions += 1
        self._flow("purge_disk", 1, meta["nbytes"])
        self._purge_tier_identity(key)

    def _purge_tier_identity(self, key) -> None:
        """Drop a tier-resident key's chain identity (the shared tail
        of every host/disk purge)."""
        self._depth.pop(key, None)
        self._key_seed.pop(key, None)
        self._key_hits.pop(key, None)
        self._imported_keys.discard(key)
        hex_key = key.hex()[:_kvdir.HEX_KEY_CHARS]
        if self._hex_key.get(hex_key) == key:
            del self._hex_key[hex_key]
        self._parent.pop(key, None)
        self._children.pop(key, None)

    def _host_discard(self, key) -> None:
        """Drop a host/disk copy whose key is about to re-register in
        HBM (recompute admission, import, or seed) — identical bytes
        by construction, but a key must never resolve both ways.  Not
        an eviction: the content lives on in the pool."""
        entry = self._host.pop(key, None)
        if entry is not None:
            self.kv_host_bytes -= entry["nbytes"]
            self._flow("discard_host", 1, entry["nbytes"])
        meta = self._spill.pop(key, None)
        if meta is not None:
            self.kv_disk_bytes -= meta["nbytes"]
            self._adopted_keys.discard(key)
            if self.spill is not None:
                self.spill.discard(key.hex())
            self._flow("discard_disk", 1, meta["nbytes"])

    def _spill_rows(self, key) -> Optional[Dict]:
        """Checksum-verified rows of a spilled block, reconstructed in
        the pool's wire layout (bf16 as uint16 bit patterns — the
        restore scatter bitcasts and the export splice ships bit
        patterns anyway, so bytes are the whole contract).
        Non-destructive on success (exports read in place).  ANY
        verification failure purges the entry and returns None:
        corrupt KV never leaves this method (invariant 13)."""
        if self.spill is None or key not in self._spill:
            return None
        from ..kvstore import spill as _kvspill
        record = None
        try:
            record = self.spill.read(key.hex())
        except _kvspill.SpillCorruptionError:
            self.kv_checksum_failures += 1
        except _kvspill.SpillFormatError:
            pass
        rows = None
        if record is not None:
            rows = {}
            for field, shape, dtype, row_bytes in \
                    _kvxfer._field_layout(self):
                raw = record["rows"].get(field)
                if raw is None or raw.nbytes != row_bytes:
                    self.kv_checksum_failures += 1
                    rows = None
                    break
                wire = np.dtype(np.uint16) \
                    if dtype.name == _kvspill.BF16 else dtype
                rows[field] = raw.view(wire).reshape(shape)
        if rows is None:
            meta = self._spill.pop(key, None)
            if meta is not None:
                self._purge_spill_entry(key, meta)
            return None
        return rows

    def _take_spill(self, key) -> Optional[Dict]:
        """Destructive verified read for a restore: the rows leave the
        disk tier (the HBM registration supersedes the file).  Returns
        a host-entry-shaped dict, or None on a verification failure —
        the entry is purged and the caller degrades that chain tail to
        plain recompute (cold but correct, never wrong tokens)."""
        rows = self._spill_rows(key)
        if rows is None:
            return None
        meta = self._spill.pop(key)
        self.kv_disk_bytes -= meta["nbytes"]
        self._adopted_keys.discard(key)
        self.spill.discard(key.hex())
        # No flow booked here: the destination decides it —
        # _begin_restore books disk_restore (landed in HBM) or
        # disk_to_host (promotion could not fit).
        return {"rows": rows, "nbytes": meta["nbytes"]}

    def _adopt_spill(self) -> None:
        """Warm replica restart: inventory the spill directory and
        re-adopt every chain that is still ROOTED (depth 1 upward, no
        gaps — the hit walk only ever reaches contiguous prefixes).
        Adopted keys re-enter the chain-identity maps and the disk
        tier in the previous process's clock order, so overflow keeps
        dropping the globally coldest remnant across the restart.
        Rootless files are discarded; corrupt files were already
        deleted (and counted) by the scan.  Read-only over the
        adopted files themselves — a crash mid-adopt leaves the
        directory re-adoptable."""
        metas, corrupt = self.spill.scan()
        self.kv_checksum_failures += corrupt
        by_hex: Dict[str, dict] = {}
        for meta in metas:
            hex_key = str(meta.get("key", ""))
            if len(hex_key) == 64 \
                    and meta.get("key_seed", 0) \
                    in (0, _kvadp.ADAPTER_SEED) \
                    and int(meta.get("depth", 0)) >= 1:
                by_hex[hex_key] = meta
        adopted: Dict[str, dict] = {}
        for hex_key, meta in sorted(
                by_hex.items(), key=lambda kv: kv[1].get("depth", 0)):
            if int(meta["depth"]) == 1 \
                    or meta.get("parent", "") in adopted:
                adopted[hex_key] = meta
        for meta in metas:
            hex_key = str(meta.get("key", ""))
            if hex_key not in adopted:
                self.spill.discard(hex_key)
        for hex_key, meta in sorted(
                adopted.items(), key=lambda kv: kv[1].get("clock", 0)):
            key = bytes.fromhex(hex_key)
            depth = int(meta["depth"])
            self._depth[key] = depth
            # Adapter weight pages re-adopt under their sentinel seed,
            # so a crash restart is a WARM start for adapters too.
            self._key_seed[key] = int(meta.get("key_seed", 0))
            self._key_hits[key] = int(meta.get("hits", 0))
            self._hex_key[hex_key[:_kvdir.HEX_KEY_CHARS]] = key
            parent_hex = meta.get("parent", "")
            if parent_hex in adopted:
                self._parent[key] = bytes.fromhex(parent_hex)
            nbytes = int(meta.get("nbytes", 0))
            self._spill[key] = {"nbytes": nbytes,
                                "clock": int(meta.get("clock", 0))}
            self.kv_disk_bytes += nbytes
            self._adopted_keys.add(key)
            self._flow("adopt", 1, nbytes)
            self._evict_clock = max(self._evict_clock,
                                    int(meta.get("clock", 0)))
            if depth == 1:
                self.kv_adopted_chains += 1
        while len(self._spill) > self.spill_blocks:
            old_key, old_meta = self._spill.popitem(last=False)
            self._purge_spill_entry(old_key, old_meta)

    def prefetch_promote(self, prompt) -> bool:
        """Tier-aware prefetch: begin the async promotion of a
        demoted/spilled chain for ``prompt`` BEFORE its admission walk
        trips over it.  The router hints the owning replica at route
        time (``kv_tier_hint``), so the restore overlaps the request's
        queue wait instead of starting at its deferral.  Host-side
        bookkeeping only; returns True when a restore was queued."""
        if not self.enable_prefix_cache:
            return False
        prompt = np.asarray(prompt)
        keys = self._chain_keys(prompt)[
            :self._shareable_blocks(len(prompt))]
        shared: List[int] = []
        for key in keys:
            block = self._index.get(key)
            if block is None:
                break
            if block in self._producing:
                # Producing or already RESTORING: in flight — a second
                # promotion would double-register the chain.
                return False
            shared.append(block)
        if len(shared) == len(keys):
            return False            # fully resident: nothing to do
        key = keys[len(shared)]
        if key not in self._host and key not in self._spill:
            return False            # cold continuation: recompute
        if not self._begin_restore(keys, shared):
            return False
        self.kv_prefetch_promotions += 1
        return True

    def _begin_restore(self, keys, shared) -> bool:
        """Start an asynchronous promotion of the demoted tail of
        ``keys`` (everything past the ``shared`` HBM prefix) back into
        pool blocks.  Each host key registers under a freshly
        allocated block with ``_producing[block] = RESTORING`` — hit
        walks and exports treat it as a miss until the upload lands in
        :meth:`_advance_restores` — and its rows queue for upload.

        Returns True when the restore was queued (the caller DEFERS
        the admission; the FIFO head retries and adopts the chain once
        landed) or False when the pool cannot hold the segment right
        now (the caller admits as a plain miss and recomputes — cold
        but correct, and it cannot livelock)."""
        segment = []
        for position in range(len(shared), len(keys)):
            # Pop host entries FIRST: the eviction below may demote
            # more blocks, and an overflow purge must never race away
            # rows we are about to upload.  Disk entries splice in
            # where the host runs out — to this walk a disk tier is
            # just a slower host store.
            key = keys[position]
            entry = self._host.pop(key, None)
            if entry is None:
                if key not in self._spill:
                    break
                entry = self._take_spill(key)
                if entry is None:
                    break   # checksum trip: the tail recomputes
                entry["src"] = "disk"
            segment.append((position, key, entry))
        if not segment:
            return False
        # Pin the HBM prefix across the eviction (it must not demote
        # out from under the chain we are rebuilding onto it).
        for block in shared:
            self._refs[block] += 1
            self._evictable.pop(self._block_key[block], None)
        needed = len(segment)
        self._evict_until(needed)
        fits = needed <= len(self._free)
        blocks = [self._free.pop() for _ in range(needed)] \
            if fits else []
        if fits:
            self._flow("alloc", needed)
        for block in shared:
            self._refs[block] -= 1
            if self._refs[block] == 0:
                self._evictable[self._block_key[block]] = block
        if not fits:
            for position, key, entry in segment:
                # A failed promotion re-enters the host tier WARM (it
                # was just requested): a fresh clock tick both defers
                # its next overflow and keeps host insertion order
                # clock-ascending (the auditor's tower-monotonicity
                # check leans on that ordering).
                self._evict_clock += 1
                entry["clock"] = self._evict_clock
                self._host[key] = entry
                if entry.pop("src", None) == "disk":
                    # The disk bytes were consumed by _take_spill: the
                    # rows now live in the host tier instead (and may
                    # re-spill on its next overflow).
                    self.kv_host_bytes += entry["nbytes"]
                    self._flow("disk_to_host", 1, entry["nbytes"])
            self._host_overflow()
            return False
        for (position, key, entry), block in zip(segment, blocks):
            self._index[key] = block
            self._block_key[block] = key
            self._refs[block] = 1          # pinned until landed
            self._producing[block] = RESTORING
            if position > 0:
                parent = keys[position - 1]
                self._parent[key] = parent
                self._children[parent] = \
                    self._children.get(parent, 0) + 1
            src = entry.get("src")
            if src != "disk":
                self.kv_host_bytes -= entry["nbytes"]
                self._flow("restore", 1, entry["nbytes"])
            else:
                self._flow("disk_restore", 1, entry["nbytes"])
            self._restoring.append(dict(key=key, block=block,
                                        rows=entry["rows"],
                                        group=None, src=src))
        return True

    def _queue_import(self, key_blocks, per_block_rows,
                      group_info) -> None:
        """Queue an async wire import's blocks onto the restore
        landing queue (called by :func:`kvstore.transfer
        .import_payload` with ``async_import=True`` AFTER registering
        the keys ref-pinned).  Each block gets ``_producing[block] =
        RESTORING`` so hit walks defer instead of adopting half a
        chain, and the segment shares one group dict: when its last
        block lands, the import lease arms (refs stay 1 until an
        admission adopts the chain or the lease expires)."""
        group = dict(group_info)
        group["remaining"] = len(key_blocks)
        for (key, block), rows in zip(key_blocks, per_block_rows):
            self._producing[block] = RESTORING
            self._restoring.append(dict(key=key, block=block,
                                        rows=rows, group=group))

    def _advance_restores(self) -> None:
        """Land up to ``restore_blocks_per_step`` queued host→device
        uploads — tier restores and async wire imports share the
        queue — as ONE batched scatter.  Called at the top of every
        :meth:`step`, so the upload dispatch overlaps the decode
        chunk that follows (async dispatch, same discipline as
        chunked admission).  JAX program order makes the rows
        resident before any later read of the buffer, so the
        _producing sentinel clears immediately — a landed key is
        shareable the same step, and a not-yet-landed key is still a
        miss: no reader ever sees a half-landed chain."""
        if not self._restoring:
            return
        batch = self._restoring[:self.restore_blocks_per_step]
        del self._restoring[:len(batch)]
        _kvxfer.scatter_block_row_dicts(
            self, [entry["block"] for entry in batch],
            [entry["rows"] for entry in batch])
        for entry in batch:
            block = entry["block"]
            self._producing.pop(block, None)
            group = entry["group"]
            if group is None:
                # Host/disk-tier restore: cached again, MRU,
                # adoptable.
                self._refs[block] = 0
                self._evictable[entry["key"]] = block
                self._restored_keys.add(entry["key"])
                if entry.get("src") == "disk":
                    self.kv_disk_restores += 1
                else:
                    self.kv_restores += 1
                continue
            # Async wire import: the block stays ref-pinned; the
            # lease arms once the whole segment has landed.
            group["remaining"] -= 1
            if group["remaining"] == 0:
                self.kv_imports_async += 1
                Lease(group["lease_s"], group["label"],
                      lease_expired_handler=group["release"],
                      engine=group["engine"])

    def step(self) -> List:
        # Restores land BEFORE admission so a deferred head request
        # adopts freshly landed chains this very step.
        self._advance_restores()
        if _faults.PLAN is not None:
            self._pool_fault_check()
        out = super().step()
        # Audit sweep AFTER the dispatch: the auditor reads a settled
        # post-step pool (host-side only; see obs/pool_audit.py).
        if pool_audit.AUDITOR is not None:
            pool_audit.AUDITOR.maybe_sweep(self)
        return out

    def _select_victims(self, want: int) -> List:
        """Leaf-first LRU victim selection WITHOUT touching the
        index: repeatedly take the least-recently-used evictable
        entry whose indexed children are all already selected —
        selecting a leaf makes its parent selectable, so the order
        is exactly what ``want`` sequential :meth:`_evict_one` calls
        would produce.

        One pass: the LRU order is walked once, as far as the victims
        reach, and an entry that a selection has just turned into a
        leaf waits in a heap by its place in that order.  (Restarting
        the walk for every victim cost a fresh 12k-token document's
        admission a quarter of a second of host time in a pool of
        48,000 cached blocks, PR 31: chains are released root first,
        so every restart skipped a whole chain to reach its leaf.)"""
        victims: List = []
        pending: Dict = {}
        rank: Dict = {}             # walked entries: key -> LRU place
        walked: List = []           # LRU place -> (key, block)
        ready: List[int] = []       # places of selectable entries
        walk = iter(self._evictable.items())
        while len(victims) < want:
            while not ready:
                entry = next(walk, None)
                if entry is None:
                    return victims
                key = entry[0]
                rank[key] = len(walked)
                walked.append(entry)
                if self._children.get(key, 0) == pending.get(key, 0):
                    ready.append(rank[key])     # the largest so far
            # Nothing not yet walked can come before a walked entry.
            key, block = walked[heapq.heappop(ready)]
            victims.append((key, block))
            parent = self._parent.get(key)
            if parent is not None:
                pending[parent] = pending.get(parent, 0) + 1
                if parent in rank and self._children.get(parent, 0) \
                        == pending[parent]:
                    heapq.heappush(ready, rank[parent])
        return victims

    def _evict_until(self, needed: int) -> None:
        """Free pool blocks until ``needed`` are available.
        Demotions are BATCHED: victims are selected up front and
        their rows leave the device in ONE gather — per-block
        gathers cost a device sync each, ~24 of them per admission
        under longtail churn, and that per-step tax was bigger than
        the recompute the tier saves."""
        want = needed - len(self._free)
        if want <= 0:
            return
        demote = []
        for key, block in self._select_victims(want):
            if self._tier_enabled() \
                    and self._key_seed.get(key, 0) <= 0:
                demote.append((key, block))
            else:
                self._purge_cached(key, block)
                self.prefix_evictions += 1
        if demote:
            rows = _kvxfer.gather_block_rows(
                self, [block for _, block in demote])
            for position, (key, block) in enumerate(demote):
                self._demote_rows(
                    key, block,
                    {name: np.ascontiguousarray(stack[position])
                     for name, stack in rows.items()})
        while len(self._free) < needed:    # selection fell short
            if not self._evict_one():
                break

    def _reserve_slot(self, slot: int, padded: int, request) -> bool:
        prompt = np.asarray(request.prompt)
        needed = self._slot_blocks(padded, len(prompt),
                                   request.max_new_tokens)

        shared: List[int] = []
        keys: List = []
        adapter_id = self._adapter_id(request)
        if self.enable_prefix_cache:
            keys = self._chain_keys(
                prompt, adapter_id)[
                :self._shareable_blocks(len(prompt))]
            restore_host = restore_wait = False
            for key in keys:
                block = self._index.get(key)
                if block is None:
                    # A demoted continuation: restore it instead of
                    # recomputing work a lower tier still holds (host
                    # RAM or the spill directory — same machinery).
                    restore_host = key in self._host \
                        or key in self._spill
                    break
                if block in self._producing:
                    # In-flight chunked prefills register their keys
                    # at reservation but write content slice by slice
                    # — sharing before the content lands would read
                    # zeros.  WAIT for the producer, as for a
                    # RESTORING block (this chain's own promotion
                    # still landing): admitting now would recompute
                    # the very blocks in flight, and the slice queue
                    # serves the oldest prefill first, so the
                    # recomputation could not even start before the
                    # producer has finished.  (Until PR 31 a live
                    # prefill's blocks were taken for a miss: 64
                    # callers asking about one new 12k-token document
                    # prefilled it once each.)
                    restore_wait = True
                    break
                shared.append(block)
            if restore_wait:
                return False       # defer: the blocks land next steps
            if restore_host and self._begin_restore(keys, shared):
                # Defer WITHOUT pinning anything: the queue head
                # retries each step and adopts the chain once landed.
                # Decode in other slots keeps running throughout —
                # the restore rides _advance_restores, never a stall.
                return False
            # Every found block is used: _append_prefill bounds the
            # compile count by DECOMPOSING the uncached tail into
            # descending power-of-two pieces, so arbitrary prefix
            # lengths reuse log-many program shapes instead of being
            # rounded down (the old pow2 truncation threw away up to
            # half the hit).
        # Can the private blocks be had at all?  The hits that sit in
        # _evictable will be pinned, not evicted.  If not, defer
        # WITHOUT destroying cached prefixes for zero benefit, and
        # without touching the LRU order: a deferred request never
        # ran.
        private_needed = needed - len(shared)
        pinned_evictable = sum(
            1 for block in shared
            if self._block_key[block] in self._evictable)
        if private_needed > len(self._free) + len(self._evictable) \
                - pinned_evictable:
            return False
        # PIN the hits before any eviction (eviction must never free a
        # block we are about to reference).
        for block in shared:
            self._refs[block] += 1
            self._evictable.pop(self._block_key[block], None)
        self._evict_until(private_needed)
        private = [self._free.pop() for _ in range(private_needed)]
        if private:
            self._flow("alloc", len(private))
        blocks = shared + private
        self._owned[slot] = blocks
        self._pending_shared[slot] = len(shared)
        request.shared_tokens = len(shared) * self.block_size
        row = np.zeros(self.tables.shape[1], np.int32)
        row[:needed] = blocks
        self.tables[slot] = row
        if shared:
            self.prefix_hits += 1
            self.prefix_blocks_reused += len(shared)
            adopted = [key for key in keys[:len(shared)]
                       if key in self._imported_keys]
            if adopted:
                # First local use of peer-transferred blocks: the
                # warm start the kvstore transfer exists for.
                self.prefix_remote_hits += 1
                self._imported_keys.difference_update(adopted)
            restored = [key for key in keys[:len(shared)]
                        if key in self._restored_keys]
            if restored:
                # First adoption of blocks that came back from the
                # host tier: the hit the demotion preserved.
                self.prefix_hits_host += 1
                self._restored_keys.difference_update(restored)
            for key in keys[:len(shared)]:
                self._key_hits[key] = self._key_hits.get(key, 0) + 1
        elif keys:
            # Shareable prefix existed but nothing was cached for it.
            self.prefix_misses += 1
        # Register this prompt's remaining shareable blocks for future
        # requests.  ORDER DEPENDENCE: within one admission wave every
        # _reserve_slot runs before any prefill, so a later request in
        # the wave may pin keys registered here while the blocks still
        # hold garbage — safe ONLY because _prefill_and_insert runs
        # producers before their dependents (same-wave shared-prefix
        # overlaps keep admission order; disjoint chains carry no
        # ordering).  A key already indexed is never overwritten (that
        # would strand the old block in _evictable under a reused key —
        # a permanent leak), and ends the registration.
        if self.enable_prefix_cache:
            for position in range(len(shared), len(keys)):
                key = keys[position]
                if key in self._index:
                    # Another request's block holds this key and this
                    # one pins nothing of that chain.  Its later keys
                    # would be indexed as children of a block it does
                    # not hold — a cached block whose child outlives
                    # its last reference can never be reached
                    # leaf-first, and ``_evictable`` would count
                    # blocks no eviction frees (``pop from empty
                    # list`` below, first chip run of PR 31, when a
                    # live prefill's blocks still read as a miss).  So
                    # the rest of this prompt stays private.
                    break
                # Recomputing a chain the host tier still holds (the
                # restore could not fit): the fresh registration
                # supersedes the demoted copy — identical bytes, but
                # one key must never resolve both ways.
                self._host_discard(key)
                block = blocks[position]
                self._index[key] = block
                self._block_key[block] = key
                self._refs[block] = 1
                self._key_seed[key] = adapter_id
                self._depth[key] = position + 1
                self._hex_key[key.hex()[:_kvdir.HEX_KEY_CHARS]] = key
                if position > 0:
                    parent = keys[position - 1]
                    self._parent[key] = parent
                    self._children[parent] = \
                        self._children.get(parent, 0) + 1
        return True

    def _place_lora(self, lora_shared):
        """Paged layout under a replica mesh: the stacked factors lay
        out with the TPEngine's column sharding — A + scale replicated,
        B sharded on its output axis like the base weight it adapts
        (:func:`~..models.llama_tp.shard_lora`) — so the shard_map
        programs take them as global arrays with exact local slices."""
        if lora_shared is not None and self._mesh is not None:
            return self._llama_tp.shard_lora(
                lora_shared, self._mesh, self.replica_mesh.axis)
        return lora_shared

    def _invalidate_adapter_cache(self, index: int) -> None:
        """Hot unload/replace: purge every cached chain seeded by this
        stacked adapter id — its KV was computed with weights that no
        longer correspond to the id, and the id may be recycled.  The
        busy check already guarantees no live request pins these
        blocks (adapter-scoped keys ⇒ only that adapter's requests
        could), so each is zero-ref; the refs guard is defensive."""
        stale = [key for key, seed in self._key_seed.items()
                 if seed == index]
        for key in stale:
            block = self._index.get(key)
            if block is not None and not self._refs.get(block, 0):
                self._purge_cached(key, block)

    # ------------------------------------------------------------- #
    # Paged adapter storage (multi-tenant LoRA — S-LoRA's unified
    # paging).  An adapter's packed A/B factor bytes (models/
    # lora_paged.py) live as name-keyed chain pages in the SAME pool
    # as KV under ``_key_seed == ADAPTER_SEED``: census-visible,
    # booked through the 12 accountant flows, demoted/spilled/
    # restored/adopted by the exact tier machinery above.  Decode
    # NEVER reads these pages — serving always runs from the stacked
    # ``_lora_shared`` copy, so page movement is invisible to traced
    # programs (ARCHITECTURE invariant 21).  The payoff: an unloaded
    # adapter stays warm in some tier, `load_adapter(name)` restacks
    # it from pages with no client re-upload, and the digest's
    # adapter flag lets routers steer tenants at warm replicas.

    def _adapter_page_counts(self) -> Dict[str, int]:
        """ADAPTER_SEED page residency per tier — a subset of the
        census tier totals, never a new tier."""
        counts = dict(hbm=0, host=0, disk=0)
        for key, seed in self._key_seed.items():
            if seed != _kvadp.ADAPTER_SEED:
                continue
            if key in self._index:
                counts["hbm"] += 1
            elif key in self._host:
                counts["host"] += 1
            elif key in self._spill:
                counts["disk"] += 1
        return counts

    def _register_adapter_pages(self, name: str, adapter) -> int:
        """Layout hook (``load_adapter`` calls it after the stack
        commit): mirror the adapter's canonical packed bytes into
        pool pages.  Best-effort by design — a pool too tight to hold
        the pages changes nothing (the stacked copy serves; the
        adapter is just not warm-reloadable)."""
        if not self.enable_prefix_cache or self._lora_config is None:
            return 0
        data = _lorapg.pack_adapter(self.config, self._lora_config,
                                    adapter)
        return self.store_adapter_bytes(name, data)

    def store_adapter_bytes(self, name: str, data) -> int:
        """Write one packed adapter stream into freshly allocated
        pool pages keyed by ``name``'s chain, replacing any stale
        chain first.  Pages register zero-ref EVICTABLE (MRU end):
        from here on the shared eviction clock owns them.  Returns
        the page count (0 = pool too tight right now)."""
        layout = _kvxfer._field_layout(self)
        pages = _lorapg.split_pages(
            data, _lorapg.page_payload_nbytes(layout))
        if not pages:
            return 0
        keys = _kvadp.adapter_chain_keys(name, len(pages))
        self.drop_adapter_pages(name)
        needed = len(pages)
        self._evict_until(needed)
        if needed > len(self._free):
            return 0
        blocks = [self._free.pop() for _ in range(needed)]
        self._flow("alloc", needed)
        _kvxfer.scatter_block_row_dicts(
            self, blocks,
            [_lorapg.payload_to_row_dict(page, layout)
             for page in pages])
        for position, (key, block) in enumerate(zip(keys, blocks)):
            self._host_discard(key)   # a key never resolves two ways
            self._index[key] = block
            self._block_key[block] = key
            self._refs[block] = 0
            self._key_seed[key] = _kvadp.ADAPTER_SEED
            self._depth[key] = position + 1
            self._key_hits.setdefault(key, 0)
            self._hex_key[key.hex()[:_kvdir.HEX_KEY_CHARS]] = key
            if position > 0:
                parent = keys[position - 1]
                self._parent[key] = parent
                self._children[parent] = \
                    self._children.get(parent, 0) + 1
            self._evictable[key] = block
        return needed

    def drop_adapter_pages(self, name: str) -> int:
        """Purge ``name``'s page chain from every tier (weight
        replacement under the same name — stale bytes must never
        warm-load).  Plain unload does NOT call this: leaving pages
        resident is the warm-pool win."""
        dropped = 0
        for key in _kvadp.adapter_key_iter(name):
            if self._key_seed.get(key) != _kvadp.ADAPTER_SEED:
                break
            block = self._index.get(key)
            if block is not None:
                if self._refs.get(block, 0) \
                        or block in self._producing:
                    break          # defensive: never yank a busy page
                self._purge_cached(key, block)
            elif key in self._host:
                self._purge_host_entry(key, self._host.pop(key))
            elif key in self._spill:
                self._purge_spill_entry(key, self._spill.pop(key))
            else:
                break
            dropped += 1
        return dropped

    def _adapter_page_bytes(self, key) -> Optional[np.ndarray]:
        """One page's bytes from whichever tier holds it (gathered
        pool rows, a host entry's row dict, and the spill store's
        wire rows all view to the same bytes — transfer.py's
        byte-transparency).  None when absent or checksum-tripped."""
        layout = _kvxfer._field_layout(self)
        block = self._index.get(key)
        if block is not None and block not in self._producing:
            rows = _kvxfer.gather_block_rows(self, [block])
            return _lorapg.row_dict_to_payload(
                {name: stack[0] for name, stack in rows.items()},
                layout)
        entry = self._host.get(key)
        if entry is not None:
            return _lorapg.row_dict_to_payload(entry["rows"], layout)
        if key in self._spill:
            rows = self._spill_rows(key)
            if rows is not None:
                return _lorapg.row_dict_to_payload(rows, layout)
        return None

    def fetch_adapter_bytes(self, name: str) -> Optional[np.ndarray]:
        """Reassemble ``name``'s packed stream from pages in ANY mix
        of tiers.  Page 1's self-describing header bounds the walk;
        any missing page degrades to None (cold load — a partially
        purged chain never yields bytes)."""
        first = self._adapter_page_bytes(
            _kvadp.adapter_page_key(name, 0))
        if first is None:
            return None
        try:
            header_nbytes, payload_nbytes, _cfg = \
                _lorapg.parse_header(first)
        except ValueError:
            return None
        total = header_nbytes + payload_nbytes
        count = _lorapg.page_count(
            total, _lorapg.page_payload_nbytes(
                _kvxfer._field_layout(self)))
        pages = [first]
        for position in range(1, count):
            page = self._adapter_page_bytes(
                _kvadp.adapter_page_key(name, position))
            if page is None:
                return None
            pages.append(page)
        for key in _kvadp.adapter_chain_keys(name, count):
            self._key_hits[key] = self._key_hits.get(key, 0) + 1
        return _lorapg.join_pages(pages)[:total]

    def _fetch_adapter_pages(self, name: str):
        """Layout hook: the warm ``load_adapter(name)`` path —
        ``(lora_params, LoRAConfig)`` restacked from resident pages,
        or None (cold: the caller must supply factors)."""
        data = self.fetch_adapter_bytes(name)
        if data is None:
            return None
        return _lorapg.unpack_adapter(self.config, data)

    def adapter_residency(self, name: str) -> Optional[int]:
        """Worst tier across ``name``'s resident page chain (0=HBM,
        1=host, 2=disk) or None when page 1 is gone.  Best-effort —
        a mid-chain purge surfaces at fetch time, not here."""
        worst = None
        for key in _kvadp.adapter_key_iter(name):
            if self._key_seed.get(key) != _kvadp.ADAPTER_SEED:
                break
            if key in self._index:
                tier = 0
            elif key in self._host:
                tier = 1
            elif key in self._spill:
                tier = 2
            else:
                break
            worst = tier if worst is None else max(worst, tier)
        return worst

    def _state_slice(self, slot: int, prompt, start: int,
                     width: int) -> dict:
        """What a model module with per-slot recurrent state is told
        about a prefill slice beyond its tokens: whose state it carries
        and how many of its tokens advance it.  The prompt's LAST token
        does not (the first decode step processes it,
        :meth:`_activate_slot`), nor does the bucket's padding; a slice
        at ``start`` 0 begins from a zero state inside the program, so
        a reused slot needs no dispatch of its own.  Nothing, for a
        module without such state: its programs stay as they are."""
        if not self._model.RECURRENT_STATE:
            return {}
        jnp = self._jnp
        counted = max(0, min(width, len(prompt) - 1 - start))
        if start == 0 and len(prompt):
            self.counters["ssm_state_resets"] += 1
        self.counters["ssm_prefill_tokens"] += counted
        return dict(state_row=jnp.int32(slot),
                    valid_len=jnp.int32(counted))

    def _prefill_and_insert(self, admissions) -> None:
        """Append-attention admission: each request's chunk K/V lands
        straight in its own blocks and shared prefix blocks are only
        READ in place — there is no bucket cache, no pool gather and
        no scatter-back (asserted by the jaxpr guard in
        tests/test_paged_prefill.py).

        Ordering matters ONLY where a request's shared prefix contains
        blocks another admission in this same wave is about to write
        (registered in _reserve_slot, prefilled here): disjoint block
        chains run first in any order, dependent ones follow in
        admission order — producer before reader, asserted.  The
        invariant is regression-locked by
        test_prefix_cache_concurrent_slots_share_blocks (same-wave
        share, exact-output assertion)."""
        produced = {}       # block -> wave index that writes it here
        plans = []
        for index, (slot, request, prompt_padded, prompt_len) \
                in enumerate(admissions):
            n_shared = self._pending_shared[slot]
            n_total = prompt_padded.shape[1] // self.block_size
            for block in self._owned[slot][n_shared:n_total]:
                produced[block] = index
            plans.append((slot, request, prompt_padded, n_shared))
        independent, dependent = [], []
        for index, plan in enumerate(plans):
            slot, _, _, n_shared = plan
            deps = {produced[block]
                    for block in self._owned[slot][:n_shared]
                    if block in produced and produced[block] != index}
            (dependent if deps else independent).append(
                (index, plan, deps))
        ran = set()
        for index, plan, _ in independent:
            self._append_prefill(*plan)
            ran.add(index)
        for index, plan, deps in dependent:   # admission order kept
            assert deps <= ran, (
                "shared-prefix overlap requires the producing "
                f"admission {sorted(deps - ran)} to prefill before "
                f"wave index {index}")
            self._append_prefill(*plan)
            ran.add(index)

    def _append_prefill(self, slot: int, request, prompt_padded,
                        n_shared: int) -> None:
        """Prefill one admitted prompt by appending into its block
        chain, starting PAST the shared prefix (its blocks are read by
        the kernel's attention sweep, never copied).  The uncached
        tail runs as descending power-of-two pieces so arbitrary
        prefix lengths reuse log-many program shapes per bucket."""
        model, jnp = self._model, self._jnp
        self._pending_shared[slot] = 0
        block_size = self.block_size
        padded = prompt_padded.shape[1]
        kv_limit = padded // block_size
        tables_row = jnp.asarray(self.tables[slot:slot + 1])
        lora = self._request_lora(request)
        start = n_shared * block_size
        remaining = kv_limit - n_shared
        span = None
        if steplog.RECORDER is not None:
            span = steplog.RECORDER.begin(
                "paged_prefill", slot=slot, shared_blocks=n_shared,
                total_blocks=kv_limit)
        while remaining > 0:
            size = 1 << (remaining.bit_length() - 1)
            width = size * block_size
            chunk = prompt_padded[:, start:start + width]
            if compiles.LEDGER is not None:
                # pow2 piece widths ⇒ log-many prefill signatures per
                # bucket; any other width in the ledger is a breach.
                compiles.set_label("paged_prefill", f"w{width}")
            self._note_prefill(
                width, (request,),
                key_blocks=self._slice_key_blocks(start, width))
            self._note_slice_rows(slot, start, width, len(request.prompt))
            if self._tp_engine is not None:
                _, self.pool = self._tp_engine.prefill_append_paged(
                    self.params, jnp.asarray(chunk), self.pool,
                    tables_row, jnp.int32(start), lora=lora,
                    kv_limit=kv_limit)
            else:
                _, self.pool = model.prefill_append_paged(
                    self.params, jnp.asarray(chunk), self.pool,
                    tables_row, jnp.int32(start), self.config,
                    lora=lora, kv_limit=kv_limit, compute_logits=False,
                    **self._state_slice(slot, request.prompt, start,
                                        width))
            start += width
            remaining -= size
        # The span holds this prefill's enqueues (and, on a throttled
        # backend, the earlier pieces' compute blocks), so nothing of
        # it is charged to the host phase that runs next.
        if span is not None:
            span.end()
        if self._draft is not None:
            # Draft prompt KV for this slot's contiguous draft cache —
            # ALWAYS the whole padded prompt: the draft has no pool
            # and no prefix cache, so target-side block reuse never
            # shortens its prefill.
            self._prefill_draft_rows([slot], prompt_padded)

    # ------------------------------------------------------------- #
    # Chunked prefill: mixed prefill/decode steps

    def _begin_chunked_prefill(self, slot: int, request, prompt_padded,
                               prompt_len: int) -> None:
        """Chunked admission appends straight into the slot's block
        chain — no bucket ever exists, and a prefix-cache hit skips
        its shared blocks entirely (the first slice starts past
        them).  Blocks this slot will produce are marked in-flight so
        later admissions whose hit walk reaches them wait until the
        content lands."""
        n_shared = self._pending_shared[slot]
        self._pending_shared[slot] = 0
        n_total = prompt_padded.shape[1] // self.block_size
        for block in self._owned[slot][n_shared:n_total]:
            if block in self._block_key:
                self._producing[block] = slot
        # The adapter id must be resident BEFORE the first mixed
        # dispatch: serve_chunk_mixed slices the prefilling row's id
        # out of the device state.  The slot is decode-inactive, so
        # the early id is otherwise inert.
        self._adapter_ids[slot] = self._adapter_id(request)
        self._dirty[slot] = True
        self._prefilling[slot] = dict(
            request=request, prompt_padded=prompt_padded,
            prompt_len=prompt_len, start=n_shared * self.block_size,
            kv_limit=prompt_padded.shape[1] // self.block_size)

    def _note_slice_rows(self, slot: int, start: int, width: int,
                         prompt_len: int) -> None:
        """A prefill slice ``[start, start + width)`` is about to be
        dispatched: the rows it writes of the prompt.  The prompt's
        last token is the first decode step's (:meth:`_activate_slot`),
        and a bucket's padding is nobody's."""
        last = prompt_len - 1
        self._note_rows(slot, min(start, last), min(start + width, last))

    def _next_slice_width(self, prefill) -> int:
        """Next chunked-prefill slice: the largest power-of-two block
        count that fits both the remaining prompt and the configured
        chunk width.  Pow2 slices keep the compile-shape count GLOBAL
        (log2(chunk/block) widths total) — ``min(chunk, remaining)``
        would mint one program per distinct prefix-hit offset."""
        block_size = self.block_size
        remaining = (prefill["prompt_padded"].shape[1]
                     - prefill["start"]) // block_size
        cap = self.chunk_prefill_tokens // block_size
        return min(cap, 1 << (remaining.bit_length() - 1)) * block_size

    def _sp_window_width(self, prefill) -> int:
        """Sequence-parallel prefill window (2-D replica mesh): when
        the engine has an ``sp`` axis and the remaining un-prefilled
        prompt covers ``sp`` FULL ``chunk_prefill_tokens`` slices, one
        dispatch carries all ``sp`` slices — each shard prefills its
        own chunk, the window's K/V all-gathers over sp so every pool
        copy receives the full window (pool stays replicated on sp).

        Returns the window width in tokens, or 0 for "use the
        sequential ladder".  The window only ever replaces ``sp``
        consecutive EXACTLY-cap slices (cap is a power of two, so the
        pow2 ladder would emit cap for each of them), which keeps the
        slice sequence — and therefore the bitwise output — identical
        to the single-chip chunked admission; any shorter tail falls
        back to the ladder."""
        engine = self._tp_engine
        if engine is None or getattr(engine, "sp", 1) <= 1:
            return 0
        cap = self.chunk_prefill_tokens
        if not cap:
            return 0
        remaining = (prefill["prompt_padded"].shape[1]
                     - prefill["start"])
        window = engine.sp * cap
        return window if remaining >= window else 0

    def _advance_prefills(self) -> None:
        """With live decode work, chunked prefills ride the MIXED
        dispatch (one slice per chunk, inside the same jitted program
        as decode) — standalone advance here would double-prefill.
        Only when no decode can be scheduled do slices run standalone,
        one per prefilling slot per step.  SPECULATIVE rounds never
        run the mixed step (the verify chunk is its own program), so
        with speculation enabled — any draft mode — the slices always
        advance standalone, interleaved between spec rounds, one
        slice per step."""
        if not self._prefilling:
            return
        if self._spec is None and (self._plan_remaining() > 0).any():
            return
        model, jnp = self._model, self._jnp
        for slot in list(self._prefilling):
            state = self._prefilling[slot]
            start = state["start"]
            sp_width = self._sp_window_width(state)
            width = sp_width or self._next_slice_width(state)
            chunk = state["prompt_padded"][:, start:start + width]
            tables_row = jnp.asarray(self.tables[slot:slot + 1])
            lora = self._request_lora(state["request"])
            self._note_prefill(
                width, (state["request"],), sliced=True,
                key_blocks=self._slice_key_blocks(start, width))
            self._note_slice_rows(slot, start, width, state["prompt_len"])
            if sp_width:
                if compiles.LEDGER is not None:
                    # ONE window shape per (sp, cap) — the sp ladder
                    # adds a single signature, not one per offset.
                    compiles.set_label(
                        "paged_prefill",
                        f"sp{self._tp_engine.sp}w{width}")
                _, self.pool = self._tp_engine.prefill_append_sp(
                    self.params, jnp.asarray(chunk), self.pool,
                    tables_row, jnp.int32(start), lora=lora,
                    kv_limit=state["kv_limit"])
                self.counters["sp_prefill_dispatches"] += 1
            elif self._tp_engine is not None:
                _, self.pool = self._tp_engine.prefill_append_paged(
                    self.params, jnp.asarray(chunk), self.pool,
                    tables_row, jnp.int32(start), lora=lora,
                    kv_limit=state["kv_limit"])
            else:
                _, self.pool = model.prefill_append_paged(
                    self.params, jnp.asarray(chunk), self.pool,
                    tables_row, jnp.int32(start), self.config,
                    lora=lora,
                    kv_limit=state["kv_limit"], compute_logits=False,
                    **self._state_slice(slot, state["request"].prompt,
                                        start, width))
            state["start"] = start + width
            if state["start"] >= state["prompt_len"]:
                self._finish_prefill(slot, state)

    def _finish_prefill(self, slot: int, state) -> None:
        # The chain's content is complete: its blocks become shareable
        # by future admissions.  No bucket to seal (contrast the base
        # class) — activation alone flips the lane to decode.
        for block, owner in list(self._producing.items()):
            if owner == slot:
                del self._producing[block]
        del self._prefilling[slot]
        if self._draft is not None:
            # Whole-prompt draft prefill at the chunked finish (the
            # draft is small — one dispatch, no batch stall).
            self._prefill_draft_rows([slot], state["prompt_padded"])
        self._activate_slot(slot, state["request"],
                            state["prompt_padded"],
                            state["prompt_len"])

    def warm_prefill_ladder(self, buckets=None) -> int:
        """Pre-compile the chunked-prefill slice ladder: every pow2
        slice width up to ``chunk_prefill_tokens`` — plus the sp
        WINDOW width on a 2-D (tp × sp) replica mesh — for every
        prompt bucket's ``kv_limit``, dispatched once each against
        the scratch block (zero tables row, masked writes land in
        block 0), so a prefix-cache hit at an arbitrary offset or the
        first long-prompt admission never compiles mid-traffic and
        the ledger's steady-state-zero gate survives the multiplied
        2-D signature space.  The MIXED prefill+decode programs are
        warmed by ordinary warmup traffic (they need live decode
        state) — this walks only the standalone ladder, the shapes
        adaptive offsets can reach that a warmup wave may not.
        Returns the number of programs dispatched."""
        if self.slots_active or self._ring or self._prefilling:
            raise RuntimeError(
                "warm_prefill_ladder must run on an idle engine")
        if not self.chunk_prefill_tokens:
            return 0
        jnp = self._jnp
        block_size = self.block_size
        cap = self.chunk_prefill_tokens
        if buckets is None:
            buckets, b = [], self._bucket_minimum
            while b <= self.max_seq:
                buckets.append(b)
                b *= 2
        sp = getattr(self._tp_engine, "sp", 1) \
            if self._tp_engine is not None else 1
        dispatched = 0
        tables_row = jnp.zeros((1, self.tables.shape[1]), jnp.int32)
        for bucket in buckets:
            kv_limit = bucket // block_size
            widths = []
            w = block_size
            while w <= min(cap, bucket):
                widths.append(w)
                w *= 2
            if sp > 1 and sp * cap <= bucket:
                widths.append(sp * cap)
            # With adapters stacked, every width warms BOTH programs:
            # the adapter-free one (base requests keep it) and the
            # lora-gather one — an adapter request hitting a fresh
            # offset mid-traffic must not compile.  The warm lora uses
            # id 0 (the identity row): shapes, not values, key the
            # compile, and the masked writes land in scratch block 0
            # either way.
            loras = [None]
            if self._lora_shared is not None:
                loras.append(dict(ids=jnp.zeros((1,), jnp.int32),
                                  **self._lora_shared))
            for width in widths:
                is_window = width > cap
                tokens = jnp.zeros((1, width), jnp.int32)
                for lora in loras:
                    if compiles.LEDGER is not None:
                        compiles.set_label(
                            "paged_prefill",
                            f"sp{sp}w{width}" if is_window
                            else f"w{width}")
                    if is_window:
                        _, self.pool = \
                            self._tp_engine.prefill_append_sp(
                                self.params, tokens, self.pool,
                                tables_row, jnp.int32(0), lora=lora,
                                kv_limit=kv_limit)
                    elif self._tp_engine is not None:
                        _, self.pool = \
                            self._tp_engine.prefill_append_paged(
                                self.params, tokens, self.pool,
                                tables_row, jnp.int32(0), lora=lora,
                                kv_limit=kv_limit)
                    else:
                        _, self.pool = \
                            self._model.prefill_append_paged(
                                self.params, tokens, self.pool,
                                tables_row, jnp.int32(0), self.config,
                                lora=lora, kv_limit=kv_limit,
                                compute_logits=False,
                                **self._state_slice(0, (), 0, width))
                    dispatched += 1
        return dispatched

    def _release_slot(self, slot: int) -> None:
        for block in self._owned[slot]:
            if self._producing.pop(block, None) == slot:
                # Cancelled mid-prefill: the block's registered key
                # points at content that never fully landed — purge it
                # from the index (purge also returns it to the free
                # list).  Only this slot can hold a ref (the hit walk
                # skips producing blocks).
                key = self._block_key.get(block)
                if key is not None:
                    self._purge_cached(key, block)
                else:
                    self._free.append(block)
                    self._flow("free", 1)
                continue
            key = self._block_key.get(block)
            if key is None:
                self._free.append(block)        # plain private block
                self._flow("free", 1)
                continue
            self._refs[block] -= 1
            if self._refs[block] == 0:
                # Stays cached (index keeps it findable) but becomes
                # evictable under pool pressure, LRU order.
                self._evictable[key] = block
        self._owned[slot] = []
        self._pending_shared[slot] = 0
        self.tables[slot] = 0

    def _serve_chunk(self, state, steps: int, eos_id: int,
                     sampled: bool, rng_key, lora_shared):
        """Decode dispatch — MIXED when a chunked admission is in
        flight: the oldest prefilling slot's next slice and the decode
        chunk run as ONE jitted program
        (:func:`~..models.llama.serve_chunk_mixed`), so admission no
        longer stalls the running batch between chunks."""
        model, jnp = self._model, self._jnp
        slot = next(iter(self._prefilling), None) \
            if self._prefilling else None
        if slot is None:
            if self._tp_engine is not None:
                tokens_d, counts_d, new_state, self.pool = \
                    self._tp_engine.serve_chunk_paged(
                        self.params, state, self.pool, steps,
                        eos_id=eos_id, sampled=sampled,
                        rng_key=rng_key, lora_shared=lora_shared)
            else:
                tokens_d, counts_d, new_state, self.pool, *more = \
                    model.serve_chunk_paged(
                        self.params, state, self.pool, steps,
                        self.config, eos_id=eos_id, sampled=sampled,
                        rng_key=rng_key, lora_shared=lora_shared)
                self._chunk_counters = more[0] if more else None
            return tokens_d, counts_d, new_state
        prefill = self._prefilling[slot]
        start = prefill["start"]
        sp_width = self._sp_window_width(prefill)
        width = sp_width or self._next_slice_width(prefill)
        chunk = prefill["prompt_padded"][:, start:start + width]
        self._note_prefill(
            width, (prefill["request"],), sliced=True, mixed=True,
            key_blocks=self._slice_key_blocks(start, width))
        self._note_slice_rows(slot, start, width, prefill["prompt_len"])
        if self._dispatch_span is not None:
            self._dispatch_span.note(
                slice_slot=slot, slice_width=width,
                request_id=prefill["request"].request_id)
        if sp_width:
            # Mixed step with the slice run as an sp-sharded window:
            # sp chunks of this prompt prefill in ONE dispatch while
            # the decode part runs replicated over sp as usual.
            if compiles.LEDGER is not None:
                compiles.set_label(
                    "serve_chunk",
                    f"s{steps}sp{self._tp_engine.sp}w{width}")
            tokens_d, counts_d, new_state, self.pool = \
                self._tp_engine.serve_chunk_mixed(
                    self.params, state, self.pool, jnp.asarray(chunk),
                    jnp.int32(slot), jnp.int32(start), steps,
                    eos_id=eos_id, sampled=sampled, rng_key=rng_key,
                    lora_shared=lora_shared,
                    prefill_kv_limit=prefill["kv_limit"],
                    sp_shard=True)
            self.counters["sp_prefill_dispatches"] += 1
        elif self._tp_engine is not None:
            tokens_d, counts_d, new_state, self.pool = \
                self._tp_engine.serve_chunk_mixed(
                    self.params, state, self.pool, jnp.asarray(chunk),
                    jnp.int32(slot), jnp.int32(start), steps,
                    eos_id=eos_id, sampled=sampled, rng_key=rng_key,
                    lora_shared=lora_shared,
                    prefill_kv_limit=prefill["kv_limit"])
        else:
            tokens_d, counts_d, new_state, self.pool, *more = \
                model.serve_chunk_mixed(
                    self.params, state, self.pool, jnp.asarray(chunk),
                    jnp.int32(slot), jnp.int32(start), steps,
                    self.config, eos_id=eos_id, sampled=sampled,
                    rng_key=rng_key, lora_shared=lora_shared,
                    prefill_kv_limit=prefill["kv_limit"],
                    **self._state_slice(slot, prefill["request"].prompt,
                                        start, width))
            self._chunk_counters = more[0] if more else None
        prefill["start"] = start + width
        if prefill["start"] >= prefill["prompt_len"]:
            self._finish_prefill(slot, prefill)
        return tokens_d, counts_d, new_state

    # ------------------------------------------------------------- #
    # Speculative decoding on the paged path

    def _spec_verify(self, st, chunk, lora):
        """Pool-direct verify: the (slots, k+1) window's K/V appends
        straight into each slot's table-resolved blocks (ragged
        starts — no gather, no bucket, jaxpr-guarded in
        tests/test_spec_paged.py), logits come back
        for the acceptance kernel.  Inactive rows (chunked prefills in
        flight, free slots) write scratch block 0.  Rejected tails
        stay as stale rows behind the absolute-position mask; the
        commit consumer counts them via :meth:`_note_spec_rollback`."""
        if self._tp_engine is not None:
            logits, self.pool = self._tp_engine.verify_chunk_paged(
                self.params, chunk, self.pool, st["tables"],
                st["positions"], st["active"], lora=lora)
            return logits
        logits, self.pool = self._model.verify_chunk_paged(
            self.params, chunk, self.pool, st["tables"],
            st["positions"], st["active"], self.config, lora=lora)
        return logits

    def _note_spec_rollback(self, slot: int, advance: int,
                            width: int) -> None:
        """Count blocks the verify window touched BEYOND the committed
        frontier: rows ``[pos + advance, pos + width)`` hold rejected
        speculation.  Rollback is LOGICAL, not a free: worst-case
        reservation already owns these blocks for the request's own
        future tokens, the stale rows are unattendable (absolute-
        position mask) and are rewritten by later rounds before any
        position makes them reachable — and none of them are ever
        registered in the prefix index (_reserve_slot indexes only
        full blocks strictly before prompt_len-1), so speculated
        content can never be exported, matched, or demoted.  The
        counter measures discarded speculative write work."""
        pos = int(self.positions[slot])       # pre-advance mirror
        block_size = self.block_size
        last_written = (pos + width - 1) // block_size
        last_committed = (pos + advance - 1) // block_size
        self.spec_stats.rollback_blocks += max(
            0, last_written - last_committed)

    def _prefill_draft_rows(self, slots_list, prompts) -> None:
        """Pool-resident draft admission: prefill the whole padded
        prompt into a batch-sized contiguous bucket (the draft is
        small — one dispatch), then scatter each row into the slot's
        TARGET-table-resolved draft-pool blocks.  Bucket sizes are
        block multiples by construction (the paged bucket floor is
        ``block_size``), so the insert is exact."""
        draft, jnp = self._draft, self._jnp
        padded = prompts.shape[1]
        if compiles.LEDGER is not None:
            compiles.set_label("draft_prefill",
                               f"b{padded}x{len(slots_list)}")
        bucket = self._llama.init_cache(draft["config"],
                                        len(slots_list), padded)
        _, bucket = self._llama.prefill(
            draft["params"], jnp.asarray(prompts), bucket,
            draft["config"])
        tables = jnp.asarray(self.tables)
        for index, slot in enumerate(slots_list):
            row = [{key: buf[index:index + 1]
                    for key, buf in layer.items()} for layer in bucket]
            draft["pool"] = self._llama.paged_insert_prefix(
                draft["pool"], tables, row, jnp.int32(slot))

    def _draft_propose(self, st, k: int, draft_key):
        """Paged draft proposer: ``decode_chunk_paged`` against the
        draft pool, navigating the TARGET'S resident block tables
        (same geometry — see _init_layout).  Plain jitted even under
        a replica mesh: the draft is replicated, every chip computes
        the identical proposal stream (no collectives), so TP spec
        greedy stays bitwise the single-chip server's."""
        draft, llama = self._draft, self._llama
        if draft_key is not None:
            proposals, draft_logits, _, _, draft["pool"] = \
                llama.decode_chunk_paged(
                    draft["params"], st["token"], draft["pool"],
                    st["tables"], st["positions"], st["active"], k,
                    draft["config"], temperatures=st["temps"],
                    top_ps=st["tops"], rng_key=draft_key,
                    return_logits=True)
            return proposals, draft_logits
        proposals, _, _, draft["pool"] = llama.decode_chunk_paged(
            draft["params"], st["token"], draft["pool"], st["tables"],
            st["positions"], st["active"], k, draft["config"])
        return proposals, None

    def _draft_resync(self, st, resync, prev_positions,
                      prev_active) -> None:
        draft = self._draft
        _, draft["pool"] = self._llama.verify_chunk_paged(
            draft["params"], resync, draft["pool"], st["tables"],
            prev_positions + 1, prev_active, draft["config"])

    def _draft_block_nbytes(self) -> int:
        """HBM bytes one DRAFT-pool block holds across every layer
        field (0 without a pool-resident draft)."""
        if self._draft is None or "pool" not in self._draft:
            return 0
        total = 0
        for layer in self._draft["pool"]:
            for buf in layer.values():
                total += buf.nbytes // buf.shape[0]
        return int(total)

    # ------------------------------------------------------------- #
    # Distributed KV cache (kvstore subsystem) — ALL host-side: none
    # of these run inside, or change, a traced serve-chunk program
    # (jaxpr + AST guards in tests/test_kvstore.py).

    def prefix_digest(self, role: str = "decode",
                      max_entries: int = 64,
                      migrating: bool = False) -> str:
        """Compact advertisement of this replica's cached prefix
        blocks for the cluster directory: content-complete (not
        producing), base-model KV chains plus one flagged root entry
        per warm adapter page chain, hottest + deepest first,
        capped at ``max_entries`` (the EC share rides MQTT control
        topics — the digest must stay small).  Host-tier entries
        advertise with ``tier=1`` and spilled entries with ``tier=2``
        (plus the adopted flag for warm-restart survivors) so the
        router prices each rung: HBM hit > host restore > disk
        restore > recompute."""
        hits, depths, seeds = self._key_hits, self._depth, self._key_seed

        def ranked(keys, tier):
            """``(-hotness, -depth, key, tier)`` of the keys that may
            be advertised: what the digest is ordered by and nothing
            else, because this runs for EVERY cached key on the loop
            that drives the device (33 ms a digest at 48,000 cached
            blocks when each key also got its hex string, its refs and
            a tuple of eight, my chip run, PR 31).  Positive seeds
            (per-request adapter KV) never leave the replica.
            ADAPTER_SEED pages advertise their chain ROOT only,
            flagged in the 8th wire field — holding page 1 implies the
            whole chain (lora_paged header walk), and one digest slot
            per warm adapter keeps the EC share small."""
            for key in keys:
                seed = seeds.get(key, 0)
                if seed > 0 or (seed == _kvadp.ADAPTER_SEED
                                and depths.get(key, 0) != 1):
                    continue
                yield -hits.get(key, 0), -depths.get(key, 0), key, tier

        cached = self._index
        if self._producing:
            cached = (key for key, block in self._index.items()
                      if block not in self._producing)
        entries = []
        for hot, depth, key, tier in heapq.nsmallest(
                max_entries, itertools.chain(ranked(cached, 0),
                                             ranked(self._host, 1),
                                             ranked(self._spill, 2))):
            refs = self._refs.get(self._index[key], 0) if tier == 0 else 0
            entries.append((
                key.hex()[:_kvdir.HEX_KEY_CHARS], -depth, refs, -hot,
                tier, int(tier == 2 and key in self._adopted_keys), 0,
                int(seeds.get(key, 0) == _kvadp.ADAPTER_SEED)))
        return _kvdir.digest_encode(self.block_size, role, entries,
                                    migrating=int(migrating))

    def publish_live_chain(self, request) -> int:
        """Live-migration prepare: register a HELD request's chain —
        prompt plus every committed generated token, bounded by
        ``_shareable_blocks`` so the decode frontier's rewritten row
        never ships — in the prefix index, making it resolvable by
        ``kv_export`` exactly like a retired chain.  Returns the
        number of exportable blocks (0 = nothing shippable: cache
        off, adapter-seeded, or the chain is shorter than one block;
        the migration proceeds cold).  Registered blocks carry the
        slot's ref like any admission-registered key, so
        ``_release_slot`` at the request's (post-cutover) retirement
        leaves them cached-evictable — no new lifecycle."""
        self._refuse_unsupported(migration=True)
        if not self.enable_prefix_cache:
            return 0
        adapter_id = self._adapter_id(request)
        if adapter_id != 0:
            return 0        # adapter chains never cross replicas
        # Settle the in-flight ring so ``request.tokens`` (and the
        # pool rows behind it) are final before we advertise them.
        self._drain_ring()
        try:
            slot = self._requests.index(request)
        except ValueError:
            return 0        # finished while the ring drained
        full = np.concatenate(
            [np.asarray(request.prompt, np.int32).reshape(-1),
             np.asarray(request.tokens or [], np.int32)])
        keys = self._chain_keys(full)[
            :self._shareable_blocks(len(full))]
        owned = self._owned[slot]
        total = 0
        for position, key in enumerate(keys):
            existing = self._index.get(key)
            if existing is not None:
                if existing in self._producing:
                    break          # not content-complete yet
                total = position + 1
                continue           # already advertised (shared chain)
            if position >= len(owned):
                break
            block = owned[position]
            if block in self._producing:
                break
            # Same registration idiom as _reserve_slot: the slot's
            # hold IS the one ref; _release_slot's decrement parks
            # the block evictable when the request retires.
            self._host_discard(key)
            self._index[key] = block
            self._block_key[block] = key
            self._refs[block] = 1
            self._key_seed[key] = 0
            self._depth[key] = position + 1
            self._hex_key[key.hex()[:_kvdir.HEX_KEY_CHARS]] = key
            if position > 0:
                parent = keys[position - 1]
                self._parent[key] = parent
                self._children[parent] = \
                    self._children.get(parent, 0) + 1
            total = position + 1
        return total

    def prefix_keys_hex(self, prompt) -> List[str]:
        """Directory-width keys for a prompt's shareable blocks
        (base adapter — the only chains that cross replicas)."""
        return _kvdir.chain_keys_hex(prompt, self.block_size)

    def prefix_local_depth(self, prompt) -> int:
        """Longest locally-cached, content-complete prefix of
        ``prompt`` in blocks — what a warm-start fetch may SKIP
        requesting from the owner.  Host-tier AND spilled blocks count
        as local: a restore beats a wire transfer of the same
        bytes."""
        depth = 0
        for key in self._chain_keys(np.asarray(prompt))[
                :self._shareable_blocks(len(np.asarray(prompt)))]:
            block = self._index.get(key)
            if block is None:
                if key not in self._host and key not in self._spill:
                    break
            elif block in self._producing:
                break
            depth += 1
        return depth

    def kv_export_payload(self, keys_hex: List[str],
                          start_depth: int) -> Optional[Dict]:
        """Serve one export RPC: gather the requested chain segment's
        pool rows host-side.  Returns the wire dict or ``None`` (the
        segment is gone — caller answers with an error and the
        importer recomputes)."""
        self._refuse_unsupported(kv_transfer=True)
        started = time.perf_counter()
        payload = _kvxfer.export_payload(self, keys_hex, start_depth)
        if payload is None:
            self.kv_transfer_failures += 1
            return None
        self.kv_transfer_bytes += _kvxfer.payload_bytes(payload)
        self.kv_transfer_ms += (time.perf_counter() - started) * 1e3
        return payload

    def kv_import_payload(self, payload: Dict, engine=None,
                          lease_s: float = 30.0,
                          async_import: bool = False) -> int:
        """Adopt an exported segment into this pool under a lease;
        returns blocks imported (0 counts as a transfer failure —
        the caller falls back to local prefill, which is always
        correct, just colder).  ``async_import=True`` (the serving
        path) registers the keys behind the ``RESTORING`` sentinel
        and lands the rows a few blocks per step — see
        :func:`~..kvstore.transfer.import_payload`."""
        self._refuse_unsupported(kv_transfer=True)
        started = time.perf_counter()
        imported = _kvxfer.import_payload(self, payload,
                                          engine=engine,
                                          lease_s=lease_s,
                                          async_import=async_import)
        if imported:
            self.kv_transfer_bytes += _kvxfer.payload_bytes(payload)
            self.kv_transfer_ms += \
                (time.perf_counter() - started) * 1e3
        else:
            self.kv_transfer_failures += 1
        return imported
