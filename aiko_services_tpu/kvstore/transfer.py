"""Cross-replica KV block transfer: export/import of pool blocks.

A replica→replica RPC body: the owner resolves directory-width hex
keys through its full-key prefix index and gathers the table-resolved
pool rows through the FUSED STAGING BUFFER engine — one jitted
device-side gather across every layer and buffer concatenates the
selected rows' raw bytes into a single contiguous staging array,
pulled to host with ONE device sync (``kv_export_sync_count``); the
wire fields are zero-copy views of that buffer (bf16 rows view as
uint16 bit patterns in place — no per-field ``np.stack``, no
``ascontiguousarray`` re-copy).  A chain demoted to the owner's host
tier exports straight from its host rows — no promotion.  The
importer allocates blocks from its own pool (evicting — demoting,
when a host tier is configured — cold cached prefixes if needed),
assembles the inbound rows into one staging buffer HOST-side, uploads
it with ONE host→device transfer, writes every layer back with one
fused batched scatter (TP re-pin included), and registers the chain
keys in its prefix index under a lease, pinned until adopted by an
admission or released at expiry.

On the serving path imports are ASYNC and step-overlapped
(``async_import=True``): the keys register immediately behind the
tiered-cache ``RESTORING`` producing sentinel and the rows land a few
blocks per engine step through the same queue as host-tier restores —
decode never stalls on an inbound segment, no reader ever sees a
half-landed chain, and the lease arms only when the last block lands
(``kv_imports_async``).

The same fused primitives back the TIERED KV cache:
:func:`gather_block_rows` is the demotion copy (device→host, one
sync per victim batch), :func:`scatter_block_rows` /
:func:`scatter_block_row_dicts` the restore upload (host→device, one
upload per landing batch) — one codec, three movers (wire, demote,
restore), so bit-exactness is proved once.  The pre-fusion per-layer
implementations survive as ``*_legacy``: the reference of the
byte-identity tests (tests/test_kv_transfer_fast.py).

Shape discipline: the big fused gather/scatter programs compile once
per power-of-two id bucket (``_bucket_ids``).  Padding never crosses
the PCIe bus: a tiny slices-and-concatenate program trims the
duplicate rows DEVICE-side before the one host pull (export), and the
import uploads exactly the inbound rows, padding the staging
host-side with repeated last-row bytes (duplicate scatter ids write
identical content, so the pad is shape stability only).

Wire format (swag dict values; arrays ride the numpy codec tag):

======================  =============================================
``kv_keys``             json list of FULL (64-hex) chain keys,
                        contiguous — the request carries
                        directory-width hex16 keys, the response
                        full keys, so the importer registers blocks
                        under exactly the keys its own admission
                        walk will compute from the prompt
``kv_parent``           full hex of the key preceding ``kv_keys[0]``
                        (empty string at chain root)
``kv_start_depth``      chain depth of ``kv_parent`` (0 at root)
``kv_block_size``       pool block size (must match importer)
``kv_sig``              :func:`pool_signature` (layout handshake)
``kv_dtype``            source dtype name (bf16 travels as uint16
                        bit patterns — ``np.save`` cannot round-trip
                        ml_dtypes)
``kv_l<i>_<name>``      per-layer stacked rows, ``(n_blocks,
                        block_size, kv_heads, head_dim)`` for
                        ``k``/``v`` (+ ``ks``/``vs`` scale planes,
                        ``(n_blocks, block_size, kv_heads)``, on
                        int8 pools)
======================  =============================================

Transfers are base-model only (adapter id 0): stacked-adapter INDICES
are replica-local, so a key seeded by adapter 3 here may mean a
different adapter there — the digest never advertises them.

Bit-exactness: exported rows are the owner's pool bytes verbatim
(bf16, or int8 + f32 scales), and :func:`shareable_blocks` guarantees
an imported block is never rewritten by the importer's admission
seed — so greedy decode after an imported prefix exactly equals local
prefill (asserted for both pool dtypes in tests/test_kvstore.py; the
fused-vs-legacy byte identity in tests/test_kv_transfer_fast.py).

Tensor-parallel replicas: a TP replica's pool is a kv-head-sharded
global ``jax.Array``, but the wire format stays the FULL kv-head
width — the fused gather assembles full rows from the shards, the
fused scatter writes them back and re-pins the pool sharding.
Replicas with different TP degrees (including TP=1) therefore
exchange blocks with no layout negotiation beyond
:func:`pool_signature`, which is mesh-agnostic by construction
(tested: TP=2 → TP=4 greedy handoff is bit-exact in bf16 and int8).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from ..obs import pool_audit
from .directory import HEX_KEY_CHARS, chain_keys, shareable_blocks

__all__ = ["pool_signature", "export_payload", "import_payload",
           "payload_bytes", "drop_one_block", "seed_chain",
           "gather_block_rows",
           "scatter_block_rows", "scatter_block_row_dicts",
           "gather_block_rows_legacy", "scatter_block_rows_legacy"]

_BF16 = "bfloat16"


def pool_signature(server) -> str:
    """Layout handshake string: two pools may exchange blocks only
    when every field matches (mismatch means the bytes would be
    reinterpreted, silently corrupting attention)."""
    config = server.config
    return (f"{config.n_layers}:{config.n_kv_heads}:"
            f"{config.head_dim}:{int(server.quantize_kv)}:"
            f"{np.dtype(server.pool[0]['k'].dtype).name}")


def payload_bytes(payload: Dict) -> int:
    """Transferred tensor bytes (the MB/s numerator; codec/base64
    framing overhead excluded by convention)."""
    return sum(int(value.nbytes) for value in payload.values()
               if isinstance(value, np.ndarray))


def drop_one_block(payload: Dict) -> Optional[Dict]:
    """Chaos helper (the ``drop_migration_block`` fault point): trim
    the LAST block off an export payload — keys and every per-layer
    row stack — so the chain stays contiguous but arrives one block
    short.  The importer registers what it got and the resume's
    admission walk recomputes the missing tail: strictly a
    degradation, never a correctness hazard.  Returns ``None`` when
    the payload held a single block (nothing left to ship — the
    caller degrades to the ``kv_prefix_gone`` cold path)."""
    keys = list(payload.get("kv_keys", []))
    if len(keys) <= 1:
        return None
    trimmed = dict(payload)
    trimmed["kv_keys"] = keys[:-1]
    for field, value in payload.items():
        if field.startswith("kv_l") and isinstance(value, np.ndarray):
            trimmed[field] = value[:-1]
    return trimmed


def _pack(array: np.ndarray) -> np.ndarray:
    # np.save cannot round-trip ml_dtypes bfloat16 (loads as void16);
    # ship the bit pattern and record the dtype out of band.  The
    # legacy codec helper — the fused path views bit patterns in
    # place (:func:`_pack_view`) instead of re-copying.
    if array.dtype.name == _BF16:
        return array.view(np.uint16)
    return np.ascontiguousarray(array)


def _pack_view(array: np.ndarray) -> np.ndarray:
    """Zero-copy wire packing: bf16 views as its uint16 bit pattern
    without the contiguity re-copy ``_pack`` pays (staging views are
    contiguous by construction)."""
    if array.dtype.name == _BF16:
        if not array.flags["C_CONTIGUOUS"]:
            array = np.ascontiguousarray(array)
        return array.view(np.uint16)
    return array


def _unpack(array: np.ndarray, dtype_name: str,
            target_dtype) -> np.ndarray:
    if dtype_name == _BF16 and array.dtype == np.uint16:
        return array.view(np.dtype(target_dtype))
    return array


def _bucket_ids(blocks: List[int]) -> np.ndarray:
    """Pad a block-id list to the next power of two by REPEATING the
    last id.  Eager JAX compiles one gather/scatter executable per
    operand shape; demote/restore batch sizes vary per admission, and
    without bucketing every new size pays a ~100 ms compile — which
    dwarfed the recompute the host tier saves.  Repeating an id is
    shape-safe in both directions: gathered duplicates are trimmed
    DEVICE-side before the host pull (they never cross the bus), and
    scattered duplicates write the same row twice."""
    ids = np.asarray(blocks, np.int32)
    size = 1
    while size < len(ids):
        size *= 2
    if size > len(ids):
        ids = np.concatenate(
            [ids, np.full(size - len(ids), ids[-1], np.int32)])
    return ids


# ---------------------------------------------------------------- #
# Fused staging-buffer engine.  The pool crosses the host/device
# boundary as ONE contiguous uint8 staging array in field-major
# order: for every layer×buffer (sorted name order within a layer),
# the selected blocks' raw bytes sit in one contiguous span, so each
# host-side field is a zero-copy ``.view(dtype)`` of its span.  The
# big gather/scatter programs compile once per pow2 id bucket; the
# only shape-varying program is a trivial slices-and-concatenate
# trim, orders of magnitude cheaper to compile than the gather it
# feeds.

_JITS: Dict[str, object] = {}


def _field_layout(server) -> List[tuple]:
    """Ordered staging schema: ``(field, per-row shape, dtype,
    row_bytes)`` per layer buffer, sorted buffer name within layer —
    the exact iteration order of the traced programs below (jax
    pytree flattening sorts dict keys, so sorted order is the one
    order host and device agree on)."""
    layout = []
    # The block pools among what the engine donates to its programs
    # (a model module may keep per-slot state beside them).
    for layer, buffers in enumerate(
            server._model.kv_pool_layers(server.pool)):
        for name in sorted(buffers):
            buf = buffers[name]
            shape = tuple(int(s) for s in buf.shape[1:])
            dtype = np.dtype(buf.dtype)
            layout.append((f"l{layer}_{name}", shape, dtype,
                           int(np.prod(shape)) * dtype.itemsize))
    return layout


def _jit_gather(jax_mod, jnp_mod):
    fn = _JITS.get("gather")
    if fn is None:
        def program(pool, ids):
            parts = []
            for buffers in pool:
                for name in sorted(buffers):
                    rows = buffers[name][ids]
                    parts.append(jax_mod.lax.bitcast_convert_type(
                        rows, jnp_mod.uint8).reshape(-1))
            return jnp_mod.concatenate(parts)
        fn = jax_mod.jit(program)
        _JITS["gather"] = fn
    return fn


def _jit_trim(jax_mod, jnp_mod):
    # spans: static ((byte offset, bytes kept), ...) — one slice per
    # field dropping the pad duplicates, device-side.
    fn = _JITS.get("trim")
    if fn is None:
        def program(staging, spans):
            parts = [jax_mod.lax.slice(staging, (offset,),
                                       (offset + keep,))
                     for offset, keep in spans]
            return jnp_mod.concatenate(parts)
        fn = jax_mod.jit(program, static_argnums=(1,))
        _JITS["trim"] = fn
    return fn


def _jit_scatter(jax_mod, jnp_mod):
    fn = _JITS.get("scatter")
    if fn is None:
        def program(pool, ids, staging):
            padded = ids.shape[0]
            offset = 0
            new_pool = []
            for buffers in pool:
                new = {}
                for name in sorted(buffers):
                    buf = buffers[name]
                    shape = tuple(buf.shape[1:])
                    itemsize = np.dtype(buf.dtype).itemsize
                    nbytes = padded * int(np.prod(shape)) * itemsize
                    raw = jax_mod.lax.slice(staging, (offset,),
                                            (offset + nbytes,))
                    raw = raw.reshape(
                        (padded,) + shape
                        + ((itemsize,) if itemsize > 1 else ()))
                    new[name] = buf.at[ids].set(
                        jax_mod.lax.bitcast_convert_type(
                            raw, buf.dtype))
                    offset += nbytes
                new_pool.append(new)
            return new_pool
        # Donating the pool avoids a second pool-sized HBM allocation
        # during the scatter (safe: only the server holds the pool —
        # TPEngine stores specs, not buffers).  CPU ignores donation
        # and warns, so gate it.
        donate = (0,) if jax_mod.default_backend() != "cpu" else ()
        fn = jax_mod.jit(program, donate_argnums=donate)
        _JITS["scatter"] = fn
    return fn


def _account(server, syncs: int = 0, host_ms: float = 0.0) -> None:
    if syncs:
        server.kv_export_sync_count = \
            getattr(server, "kv_export_sync_count", 0) + syncs
    if host_ms:
        server.kv_transfer_host_ms = \
            getattr(server, "kv_transfer_host_ms", 0.0) + host_ms


def gather_block_bytes(server, blocks: List[int]):
    """Fused export gather: ONE jitted device-side gather over every
    layer/buffer into a single field-major staging array, duplicates
    trimmed device-side, pulled to host with ONE sync.  Returns
    ``(staging uint8 ndarray, layout)``."""
    started = time.perf_counter()
    jax_mod, jnp_mod = server._jax, server._jnp
    count = len(blocks)
    ids = jnp_mod.asarray(_bucket_ids(blocks))
    staged = _jit_gather(jax_mod, jnp_mod)(server.pool, ids)
    layout = _field_layout(server)
    padded = int(ids.shape[0])
    if padded != count:
        spans, offset = [], 0
        for _field, _shape, _dtype, row_bytes in layout:
            spans.append((offset, count * row_bytes))
            offset += padded * row_bytes
        staged = _jit_trim(jax_mod, jnp_mod)(staged, tuple(spans))
    staging = np.asarray(staged)       # the ONE device→host sync
    _account(server, syncs=1,
             host_ms=(time.perf_counter() - started) * 1e3)
    return staging, layout


def _staging_views(staging: np.ndarray, layout, count: int,
                   wire: bool = False) -> Dict[str, np.ndarray]:
    """Zero-copy per-field views of a (trimmed) staging buffer —
    native dtype, or the uint16 wire bit pattern for bf16 fields when
    ``wire``."""
    views, offset = {}, 0
    for field, shape, dtype, row_bytes in layout:
        nbytes = count * row_bytes
        flat = staging[offset:offset + nbytes]
        view_dtype = np.uint16 if wire and dtype.name == _BF16 \
            else dtype
        views[field] = flat.view(view_dtype).reshape((count,) + shape)
        offset += nbytes
    return views


def gather_block_rows(server, blocks: List[int]) -> Dict[str,
                                                         np.ndarray]:
    """Host copy of the pool rows for ``blocks``: ``{"l<i>_<name>":
    (n_blocks, block_size, ...)}`` in the pool's native dtype (bf16
    rows stay bf16, int8 rows keep their f32 scale planes — stored
    bytes are the pool bytes verbatim, which is what makes demotion →
    restore bit-exact).  Rides the fused staging engine: one device
    program, one sync, zero-copy views; on a TP replica the gather
    assembles full kv-head-width rows from every shard, exactly like
    the wire format."""
    staging, layout = gather_block_bytes(server, blocks)
    return _staging_views(staging, layout, len(blocks))


def gather_block_rows_legacy(server, blocks: List[int]) -> Dict[
        str, np.ndarray]:
    """Pre-fusion gather: one blocking ``np.asarray`` pull per
    layer×buffer.  Kept as the byte-identity tests' reference —
    never on the serving path."""
    count = len(blocks)
    ids = server._jnp.asarray(_bucket_ids(blocks))
    rows = {}
    for layer, buffers in enumerate(server.pool):
        for name, buf in buffers.items():
            rows[f"l{layer}_{name}"] = np.asarray(buf[ids])[:count]
    return rows


def _row_bytes_2d(array: np.ndarray) -> np.ndarray:
    """(n, ...) array → (n, row_bytes) uint8 view (copy only if the
    source is non-contiguous)."""
    return np.ascontiguousarray(array).view(np.uint8).reshape(
        array.shape[0], -1)


def _scatter_staged(server, blocks: List[int], layout,
                    fill) -> None:
    """Shared fused-import tail: allocate the PADDED field-major
    staging, let ``fill(field_index, region)`` write each field's
    ``(count, row_bytes)`` rows, replicate the last row into the pad
    span (duplicate ids write identical bytes), then ONE host→device
    upload and ONE fused multi-layer scatter.  TP pools re-pin their
    kv-head sharding afterwards, exactly like every other pool
    write."""
    started = time.perf_counter()
    jax_mod, jnp_mod = server._jax, server._jnp
    count = len(blocks)
    ids_host = _bucket_ids(blocks)
    padded = len(ids_host)
    staging = np.empty(
        padded * sum(row_bytes for *_rest, row_bytes in layout),
        np.uint8)
    offset = 0
    for index, (_field, _shape, _dtype, row_bytes) in \
            enumerate(layout):
        region = staging[offset:offset + padded * row_bytes]
        region = region.reshape(padded, row_bytes)
        fill(index, region[:count])
        if padded > count:
            region[count:] = region[count - 1]
        offset += padded * row_bytes
    shardings = None
    if getattr(server, "_mesh", None) is not None:
        shardings = [{name: getattr(buf, "sharding", None)
                      for name, buf in buffers.items()}
                     for buffers in server.pool]
    device = jnp_mod.asarray(staging)  # the ONE host→device upload
    server.pool = _jit_scatter(jax_mod, jnp_mod)(
        server.pool, jnp_mod.asarray(ids_host), device)
    if shardings is not None:
        # The scatter of a replicated staging must not leave a
        # gathered pool copy behind: re-pin each written buffer to
        # the pool's kv-head sharding (async dispatch, no sync).
        # Mesh-RANK-agnostic by construction: the recorded per-buffer
        # sharding carries whatever the pool was pinned to — 1-D tp
        # or a 2-D tp × sp/ep mesh (kv-heads sharded on tp,
        # replicated on the second axis) — so 2-D replicas import
        # wire blocks with no extra plumbing.
        for layer, buffers in enumerate(server.pool):
            server.pool[layer] = {
                name: server._jax.device_put(
                    buf, shardings[layer][name])
                if shardings[layer][name] is not None else buf
                for name, buf in buffers.items()}
    _account(server,
             host_ms=(time.perf_counter() - started) * 1e3)


def scatter_block_rows(server, blocks: List[int],
                       rows: Dict[str, np.ndarray]) -> None:
    """Write stacked host rows (the :func:`gather_block_rows` layout)
    back into pool ``blocks``: one host-side staging assembly, one
    H2D upload, one fused batched scatter across every layer buffer.
    Accepts native-dtype rows or their wire bit patterns (same
    bytes — the scatter bitcasts, never casts, so the no-op dtype
    cast the legacy path paid is structurally gone)."""
    count = len(blocks)
    layout = _field_layout(server)

    def fill(index, region):
        field, _shape, _dtype, row_bytes = layout[index]
        source = _row_bytes_2d(np.asarray(rows[field]))
        if source.shape != (count, row_bytes):
            raise ValueError(
                f"{field}: rows {source.shape} != "
                f"({count}, {row_bytes})")
        region[:] = source

    _scatter_staged(server, blocks, layout, fill)


def scatter_block_row_dicts(server, blocks: List[int],
                            row_dicts: List[Dict[str, np.ndarray]]
                            ) -> None:
    """Per-block variant of :func:`scatter_block_rows` for the
    restore/async-import landing queue: assembles the staging
    straight from each block's row dict — no intermediate
    ``np.stack`` per field."""
    count = len(blocks)
    layout = _field_layout(server)

    def fill(index, region):
        field, _shape, _dtype, row_bytes = layout[index]
        for position, row_dict in enumerate(row_dicts):
            source = np.ascontiguousarray(
                row_dict[field]).view(np.uint8).reshape(-1)
            if source.shape[0] != row_bytes:
                raise ValueError(
                    f"{field}[{position}]: {source.shape[0]} != "
                    f"{row_bytes} bytes")
            region[position] = source
        assert count == len(row_dicts)

    _scatter_staged(server, blocks, layout, fill)


def scatter_block_rows_legacy(server, blocks: List[int],
                              rows: Dict[str, np.ndarray]) -> None:
    """Pre-fusion scatter: one ``.at[ids].set`` plus one H2D upload
    per layer buffer.  Kept as the tests' reference — never on
    the serving path.  (The unconditional ``.astype`` the
    original paid is fixed here too: the cast is skipped when the
    host rows already match the pool dtype, which they always do on
    the demote→restore path.)"""
    jnp = server._jnp
    count = len(blocks)
    ids = jnp.asarray(_bucket_ids(blocks))
    for layer, buffers in enumerate(server.pool):
        written = {}
        for name, buf in buffers.items():
            data = np.asarray(rows[f"l{layer}_{name}"])
            if len(ids) > count:
                pad = np.repeat(data[-1:], len(ids) - count, axis=0)
                data = np.concatenate([data, pad], axis=0)
            value = jnp.asarray(data)
            if value.dtype != buf.dtype:
                value = value.astype(buf.dtype)
            new = buf.at[ids].set(value)
            if getattr(buf, "sharding", None) is not None \
                    and getattr(server, "_mesh", None) is not None:
                new = server._jax.device_put(new, buf.sharding)
            written[name] = new
        server.pool[layer] = written


def export_payload(server, keys_hex: List[str], start_depth: int,
                   fused: bool = True) -> Optional[Dict]:
    """Resolve ``keys_hex`` (a contiguous chain segment starting at
    depth ``start_depth + 1``) through the owner's prefix index and
    gather the pool rows.  A key demoted to the owner's host tier is
    served straight from its host rows — same bytes, no promotion, no
    pool pressure on the owner — and a key spilled to the owner's disk
    tier splices in through its checksum-verified read (a corrupt file
    fails the export instead of shipping bad KV).  Returns the wire
    dict, or ``None``
    when the owner no longer holds a usable segment (evicted since it
    was advertised, still producing, adapter-seeded, or depth
    drifted) — the caller answers with an error and the importer
    falls back to local prefill.

    ``fused`` (default) serves the wire fields as zero-copy views of
    the one-sync staging buffer; ``fused=False`` is the legacy
    per-layer gather + per-position splice, kept as the tests'
    reference."""
    start_depth = int(start_depth)
    host_tier = getattr(server, "_host", {})
    resolved: List[bytes] = []
    sources: List = []          # int pool block | host rows dict
    for offset, hex_key in enumerate(keys_hex):
        key = server._hex_key.get(str(hex_key)[:HEX_KEY_CHARS])
        if key is None:
            break
        block = server._index.get(key)
        if block is None:
            entry = host_tier.get(key)
            if entry is None:
                spill_rows = getattr(server, "_spill_rows", None)
                rows = spill_rows(key) \
                    if spill_rows is not None else None
                if rows is None:
                    break
                source = rows
            else:
                source = entry["rows"]
        elif block in server._producing:
            break                      # content not landed yet
        else:
            source = block
        if server._depth.get(key) != start_depth + offset + 1:
            break                      # not the chain we advertised
        if server._key_seed.get(key, 0) > 0:
            break    # per-request adapter KV: replica-local, never
            #          exported.  ADAPTER_SEED weight pages DO export
            #          (cross-replica adapter fetch) — flagged below.
        if resolved and server._parent.get(key) != resolved[-1]:
            break                      # chain discontinuity
        if resolved and server._key_seed.get(key, 0) \
                != server._key_seed.get(resolved[0], 0):
            break                      # KV / adapter pages never mix
        resolved.append(key)
        sources.append(source)
    if not resolved:
        return None
    parent = server._parent.get(resolved[0])
    payload: Dict = {
        "kv_keys": [key.hex() for key in resolved],
        "kv_parent": parent.hex() if parent else "",
        "kv_start_depth": start_depth,
        "kv_block_size": int(server.block_size),
        "kv_sig": pool_signature(server),
        "kv_dtype": np.dtype(server.pool[0]["k"].dtype).name,
    }
    if server._key_seed.get(resolved[0], 0):
        payload["kv_adapter"] = 1
    # The wire format is always the full kv-head width (TP-agnostic);
    # HBM rows gather through the fused staging buffer, host rows
    # splice in verbatim — both are the owner's pool bytes.
    hbm = [source for source in sources if isinstance(source, int)]
    if not fused:
        gathered = gather_block_rows_legacy(server, hbm) if hbm \
            else {}
        for layer, buffers in enumerate(server.pool):
            for name in buffers:
                field = f"l{layer}_{name}"
                stacked, cursor = [], 0
                for source in sources:
                    if isinstance(source, int):
                        stacked.append(_pack(gathered[field][cursor]))
                        cursor += 1
                    else:
                        # Host rows are native dtype; spill rows are
                        # already wire bit patterns — _pack makes the
                        # stack dtype-uniform either way.
                        stacked.append(_pack(np.asarray(source[field])))
                payload[f"kv_{field}"] = np.stack(stacked)
        return payload
    if hbm:
        staging, layout = gather_block_bytes(server, hbm)
        views = _staging_views(staging, layout, len(hbm), wire=True)
    else:
        layout, views = _field_layout(server), {}
    started = time.perf_counter()
    if len(hbm) == len(sources):
        # Pure-HBM segment (the common wire case): the payload fields
        # ARE the staging views — zero host copies past the one pull.
        for field, *_rest in layout:
            payload[f"kv_{field}"] = views[field]
    else:
        # Mixed HBM/host splice: one allocation per field, HBM
        # positions filled with a single vectorized assignment from
        # the staging views, host rows copied in place — no
        # per-position np.stack.
        hbm_at = np.array([position for position, source
                           in enumerate(sources)
                           if isinstance(source, int)], np.intp)
        for field, shape, dtype, _row_bytes in layout:
            wire_dtype = np.uint16 if dtype.name == _BF16 else dtype
            out = np.empty((len(sources),) + shape, wire_dtype)
            if len(hbm_at):
                out[hbm_at] = views[field]
            for position, source in enumerate(sources):
                if not isinstance(source, int):
                    out[position] = _pack_view(source[field])
            payload[f"kv_{field}"] = out
    _account(server, host_ms=(time.perf_counter() - started) * 1e3)
    return payload


def import_payload(server, payload: Dict, engine=None,
                   lease_s: float = 30.0, fused: bool = True,
                   async_import: bool = False) -> int:
    """Adopt an exported segment into ``server``'s pool + prefix
    index; returns the number of blocks imported (0 = nothing usable:
    layout mismatch, broken chain linkage, or pool too full even
    after eviction).

    Imported keys are registered ref-pinned under a
    :class:`~..runtime.lease.Lease` (released — made evictable — at
    expiry if no admission adopted them; ``engine=None`` skips the
    pin and registers them immediately evictable, the synchronous
    test mode).

    ``async_import=True`` (the serving path, requires ``engine`` and
    a tiered-queue server) registers the keys immediately behind the
    ``RESTORING`` producing sentinel and queues the rows to land a
    few blocks per engine step alongside host-tier restores — the
    step loop keeps producing while the segment lands, no reader
    ever resolves a half-landed chain, and the lease arms when the
    last block lands.  ``fused=False`` keeps the legacy per-layer
    scatter as the tests' reference (synchronous only)."""
    if str(payload.get("kv_sig")) != pool_signature(server) or \
            int(payload.get("kv_block_size", -1)) != server.block_size:
        return 0
    try:
        keys = [bytes.fromhex(str(k)) for k in
                payload.get("kv_keys", [])]
    except ValueError:
        return 0
    if not keys or any(len(k) != 32 for k in keys):
        return 0
    start_depth = int(payload.get("kv_start_depth", 0))
    parent: Optional[bytes] = None
    if start_depth > 0:
        try:
            parent = bytes.fromhex(str(payload.get("kv_parent", "")))
        except ValueError:
            return 0
        if server._index.get(parent) is None \
                or server._depth.get(parent) != start_depth:
            return 0       # local prefix evicted since the request
    # Skip the prefix another import/admission already landed; stop
    # at any later already-present key (never re-import, never fork).
    offset = 0
    while offset < len(keys):
        key = keys[offset]
        if server._index.get(key) is None \
                or server._index[key] in server._producing:
            break
        parent = key
        offset += 1
    fresh = keys[offset:]
    for index, key in enumerate(fresh):
        if key in server._index:
            fresh = fresh[:index]
            break
    if not fresh:
        return 0
    needed = len(fresh)
    if needed > len(server._free) + len(server._evictable):
        return 0
    # Validate + slice EVERY layer's rows before touching the pool or
    # the free list — an incomplete or misshapen payload rejects with
    # zero side effects (with a host tier, eviction demotes rather
    # than deletes, so even the _evict_until below destroys nothing
    # demotable).  Slices are views of the wire arrays: the fused
    # scatter consumes raw bytes, so no unpack copy is ever made.
    dtype_name = str(payload.get("kv_dtype", ""))
    layout = _field_layout(server)
    rows: Dict[str, np.ndarray] = {}
    for field, _shape, dtype, row_bytes in layout:
        data = payload.get(f"kv_{field}")
        if data is None or data.shape[0] < offset + needed:
            return 0
        sliced = np.asarray(data)[offset:offset + needed]
        if int(sliced.nbytes) != needed * row_bytes:
            return 0               # trailing-shape/dtype mismatch
        rows[field] = sliced if fused else _unpack(
            sliced, dtype_name, dtype)
    server._evict_until(needed)
    if needed > len(server._free):
        return 0
    blocks = [server._free.pop() for _ in range(needed)]
    if pool_audit.AUDITOR is not None:
        # The accountant's HBM inflow for imported blocks — their
        # tier-out happened on the exporting peer, not here.
        pool_audit.AUDITOR.flow("alloc", needed,
                                needed * server._block_nbytes())
    queue_async = bool(async_import) and engine is not None \
        and hasattr(server, "_queue_import")
    if not queue_async:
        if fused:
            scatter_block_rows(server, blocks, rows)
        else:
            scatter_block_rows_legacy(server, blocks, {
                field: _unpack(np.asarray(value), dtype_name,
                               dict((f, d) for f, _s, d, _r
                                    in layout)[field])
                for field, value in rows.items()})

    discard_host = getattr(server, "_host_discard", None)
    # Adapter weight pages import under their sentinel seed so the
    # importer can warm-load the adapter from them (and they keep
    # demoting/advertising as adapter pages, never as base KV).
    from .adapters import ADAPTER_SEED
    key_seed = ADAPTER_SEED if payload.get("kv_adapter") else 0
    imported: List[bytes] = []
    for index, key in enumerate(fresh):
        block = blocks[index]
        depth = start_depth + offset + index + 1
        if discard_host is not None:
            # Freshly imported content supersedes any demoted copy of
            # the same chain key (identical bytes by construction —
            # the index must just never resolve one key both ways).
            discard_host(key)
        server._index[key] = block
        server._block_key[block] = key
        server._refs[block] = 1
        server._key_seed[key] = key_seed
        server._depth[key] = depth
        server._hex_key[key.hex()[:HEX_KEY_CHARS]] = key
        server._imported_keys.add(key)
        if parent is not None:
            server._parent[key] = parent
            server._children[parent] = \
                server._children.get(parent, 0) + 1
        parent = key
        imported.append(key)

    def release(_uuid=None):
        for key in imported:
            block = server._index.get(key)
            if block is None or server._block_key.get(block) != key:
                continue               # already purged/re-owned
            if server._refs.get(block, 0) > 0:
                server._refs[block] -= 1
                if server._refs[block] == 0:
                    server._evictable[key] = block

    label = f"kv_import:{fresh[0].hex()[:8]}"
    if queue_async:
        per_block = [{field: rows[field][index]
                      for field, *_rest in layout}
                     for index in range(needed)]
        server._queue_import(
            list(zip(imported, blocks)), per_block,
            dict(engine=engine, lease_s=lease_s, release=release,
                 label=label))
    elif engine is not None:
        from ..runtime.lease import Lease
        Lease(lease_s, label, lease_expired_handler=release,
              engine=engine)
    else:
        release()
    return needed


def seed_chain(server, tokens, adapter_id: int = 0) -> int:
    """Test helper: allocate and REGISTER the shareable chain
    for ``tokens`` without prefilling (block content stays zeros) —
    lets transfer bandwidth be measured without paying an 8k-token
    prefill first.  Never used on the serving path."""
    tokens = np.asarray(tokens)
    block_size = server.block_size
    n = shareable_blocks(len(tokens), block_size)
    keys = chain_keys(tokens, block_size, adapter_id)[:n]
    registered = 0
    parent = None
    discard_host = getattr(server, "_host_discard", None)
    for position, key in enumerate(keys):
        if key in server._index:
            parent = key
            continue
        if discard_host is not None:
            discard_host(key)
        server._evict_until(1)
        if not server._free:
            break
        block = server._free.pop()
        if pool_audit.AUDITOR is not None:
            pool_audit.AUDITOR.flow("alloc", 1,
                                    server._block_nbytes())
        server._index[key] = block
        server._block_key[block] = key
        server._refs[block] = 0
        server._key_seed[key] = adapter_id
        server._depth[key] = position + 1
        server._hex_key[key.hex()[:HEX_KEY_CHARS]] = key
        if parent is not None:
            server._parent[key] = parent
            server._children[parent] = \
                server._children.get(parent, 0) + 1
        server._evictable[key] = block
        parent = key
        registered += 1
    return registered
