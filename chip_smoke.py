#!/usr/bin/env python3
"""chip_smoke.py — does the serving path still start on the chip?

One process, no arguments, one TPU: compiles every kernel of the paged
serving path at Llama-3-8B width and checks it against its jnp oracle,
then serves a handful of requests through the entry points a user
calls — an ``InferClient`` over the loopback transport to a
``ContinuousReplica`` around a ``PagedContinuousServer`` on a real
``EventEngine`` thread — with int8 weights from a seed and int8 KV.
It reports set-up facts (device, phase wall times, compile counts,
persistent-cache hits, peak memory) and claims no rate.  It exits 0
only if every phase passed; without an accelerator it exits non-zero
and prints no result line.

    python chip_smoke.py            one chip
    python chip_smoke.py --tp 4     one four-chip host, ReplicaMesh(tp=4)
    python chip_smoke.py --rehearsal
        tiny config on the CPU with the kernels interpreted — exists so
        the test suite can exercise this script's wiring, says so in
        its output, and is never chosen for the caller.

The model keeps every llama3_8b width and is cut in DEPTH
(:data:`SMOKE_LAYERS`): each distinct program is compiled from a
Python-unrolled layer stack, and the full 32 layers times the
admission ladder does not compile inside the smoke's time limit.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import threading
import time
import traceback

#: Depth of the smoke's llama3_8b (published depth: 32).
SMOKE_LAYERS = 4

#: Bounded waits (seconds): the first request of each shape compiles.
FUTURE_TIMEOUT_S = 600.0


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Everything size-shaped, so the rehearsal differs from the real
    run in numbers only."""
    config_name: str
    layers: int
    kv_heads: int            # per shard under --tp
    group: int
    head_dim: int
    slots: int
    max_seq: int
    chunk: int               # admission slice (tokens)
    short_prompt: int
    mid_prompt: int
    long_prompt: int
    shared_prefix: int
    max_new: int
    matmuls: tuple           # (K, N) decode weight shapes
    flash_len: int


BLOCK_SIZE = 16

FULL = Geometry(
    config_name="llama3_8b", layers=SMOKE_LAYERS, kv_heads=8, group=4,
    head_dim=128, slots=8, max_seq=2048 + 64 + 16, chunk=256,
    short_prompt=48, mid_prompt=700, long_prompt=1500,
    shared_prefix=320, max_new=48,
    matmuls=((4096, 4096), (4096, 14336), (14336, 4096),
             (4096, 128256)),
    flash_len=2048)

REHEARSAL = Geometry(
    config_name="tiny", layers=2, kv_heads=2, group=2, head_dim=32,
    slots=4, max_seq=256, chunk=32, short_prompt=9, mid_prompt=70,
    long_prompt=150, shared_prefix=48, max_new=8,
    matmuls=((128, 256),), flash_len=128)


class Report:
    """Phase bookkeeping: a failed check is printed with its traceback
    where it happened, the remaining checks still run (a chip call is
    too dear to learn one failure at a time), and any failure makes
    the exit code non-zero."""

    def __init__(self):
        self.failures = []
        self.phase_seconds = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"== {name}", flush=True)
        began = time.monotonic()
        try:
            yield
        except Exception:  # noqa: BLE001 - recorded, fails the run
            traceback.print_exc(file=sys.stdout)
            self.failures.append(name)
        self.phase_seconds[name] = round(time.monotonic() - began, 2)
        print(f"-- {name}: {self.phase_seconds[name]} s "
              f"(set-up wall time)", flush=True)

    def check(self, name: str, fn, *args):
        began = time.monotonic()
        try:
            detail = fn(*args)
        except Exception:  # noqa: BLE001 - recorded, fails the run
            traceback.print_exc(file=sys.stdout)
            self.failures.append(name)
            print(f"FAIL {name}", flush=True)
            return
        print(f"ok   {name}: {detail} "
              f"[{time.monotonic() - began:.1f} s incl. compile]",
              flush=True)


# --------------------------------------------------------------------------- #
# Phase 2: every kernel on the serving path against its oracle


def _max_err(got, want) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))


def _assert_close(got, want, tol: float, what: str) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{what}: non-finite values")
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol,
                               err_msg=what)
    return _max_err(got, want)


def _pool_case(rng, geo: Geometry, batch: int, max_blocks: int,
               quantized: bool):
    """A seeded pool in the serving layout with shuffled block tables:
    ``(pool dict, f32 copy of the same values for the oracle, tables)``.
    Block 0 is the reserved scratch block, as in the engine."""
    import jax.numpy as jnp
    import numpy as np
    n_blocks = batch * max_blocks + 1
    shape = (n_blocks, BLOCK_SIZE, geo.kv_heads, geo.head_dim)
    ids = rng.permutation(np.arange(1, n_blocks))
    tables = jnp.asarray(ids[:batch * max_blocks].reshape(
        batch, max_blocks), jnp.int32)
    if quantized:
        pool = {
            "k": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "v": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
            "ks": jnp.asarray(np.abs(rng.standard_normal(shape[:3]))
                              / 127.0 + 1e-3, jnp.float32),
            "vs": jnp.asarray(np.abs(rng.standard_normal(shape[:3]))
                              / 127.0 + 1e-3, jnp.float32)}
        return pool, dict(pool), tables
    pool = {"k": jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
            "v": jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)}
    # The oracle reads the SAME values widened to f32, so the
    # comparison sees the kernel's arithmetic and not the oracle's
    # bf16 rounding of its softmax weights.
    exact = {key: buf.astype(jnp.float32) for key, buf in pool.items()}
    return pool, exact, tables


def check_decode(geo: Geometry, quantized: bool, interpret: bool):
    """``paged_decode_attention`` vs ``paged_decode_reference``: ragged
    rows from one token to the whole table, block edges and mid-block
    tails.  f32 queries against the tests' tolerance; bf16 queries (the
    serving dtype) against bf16 rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from aiko_services_tpu.ops import paged_attention as pa
    rng = np.random.default_rng(2)
    max_blocks = geo.max_seq // BLOCK_SIZE
    top = max_blocks * BLOCK_SIZE - 1
    positions = jnp.asarray(
        [0, 15, 16, 100, top // 3 + 5, top - 16, top - 1, top
         ][:geo.slots], jnp.int32)
    batch = positions.shape[0]
    pool, exact, tables = _pool_case(rng, geo, batch, max_blocks,
                                     quantized)
    q = jnp.asarray(rng.standard_normal(
        (batch, geo.kv_heads, geo.group, geo.head_dim)), jnp.float32)
    scales = {"ks": pool["ks"], "vs": pool["vs"]} if quantized else {}
    errs, errs16 = [], []
    # No window, and one that leaves the long rows' first live block
    # mid-way through a group of the kernel's loop and mid-block.
    for window in (None, 5 * BLOCK_SIZE + 3):
        kernel = jax.jit(lambda q, pool: pa.paged_decode_attention(
            q, pool["k"], pool["v"], tables, positions,
            ks=pool.get("ks"), vs=pool.get("vs"), window=window,
            interpret=interpret))
        with jax.default_matmul_precision("highest"):
            want = pa.paged_decode_reference(
                q, exact["k"], exact["v"], tables, positions,
                window=window, **scales)
        errs.append(_assert_close(
            kernel(q, pool), want, 1e-4 if quantized else 2e-5,
            f"decode f32 q, window {window}"))
        got16 = kernel(q.astype(jnp.bfloat16), pool)
        errs16.append(_assert_close(got16, want, 2e-2,
                                    f"decode bf16 q, window {window}"))
    return (f"max|err| {max(errs):.2e} (f32 q), {max(errs16):.2e} "
            "(bf16 q), with and without a window")


def check_decode_append(geo: Geometry, interpret: bool):
    """``paged_decode_append`` vs the XLA scatter of the same token
    into the pool: live rows at a block's last key and a fresh block's
    first beside idle rows (scratch block 0, which the kernel leaves as
    it was).  Bit for bit: the kernel copies and selects, it computes
    nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from aiko_services_tpu.ops import paged_attention as pa
    rng = np.random.default_rng(5)
    pool, _, tables = _pool_case(rng, geo, geo.slots, 2, True)
    kv, hd = geo.kv_heads, geo.head_dim
    block_ids = tables[:, 0].at[1::3].set(0)
    offsets = jnp.asarray([BLOCK_SIZE - 1, 3, 0, 7, 9, 2, 15, 1
                           ][:geo.slots], jnp.int32)
    rows = {
        "k": jnp.asarray(rng.integers(-127, 128, (geo.slots, kv, hd)),
                         jnp.int8),
        "v": jnp.asarray(rng.integers(-127, 128, (geo.slots, kv, hd)),
                         jnp.int8),
        "ks": jnp.asarray(rng.random((geo.slots, kv)), jnp.float32),
        "vs": jnp.asarray(rng.random((geo.slots, kv)), jnp.float32)}
    width = pa.decode_scale_row(BLOCK_SIZE, kv)
    want = {}
    for key, buf in pool.items():
        want[key] = np.array(buf.at[block_ids, offsets].set(rows[key]))
        want[key][0] = np.asarray(buf[0])
    # Donated, as every serving program donates its pool: the call
    # overwrites it.  Called bare on buffers the caller keeps, XLA has
    # to copy the pools for the alias, and the TPU compiler aborts on a
    # copy into an output pinned to HBM (v5e, PR 29).
    got = jax.jit(
        lambda scan_pool: pa.paged_decode_append(
            scan_pool, rows, block_ids, offsets, interpret=interpret),
        donate_argnums=0)(
        dict(pool, ks=pool["ks"].reshape(-1, width),
             vs=pool["vs"].reshape(-1, width)))
    for key, buf in got.items():
        np.testing.assert_array_equal(
            np.asarray(buf).reshape(want[key].shape), want[key])
    live = int((block_ids != 0).sum())
    return f"bit-equal, {live} live rows of {geo.slots}"


def _append_case(rng, geo: Geometry, quantized: bool, width: int,
                 cached_lens, chunk_lens):
    import jax.numpy as jnp
    batch = len(cached_lens)
    max_blocks = geo.max_seq // BLOCK_SIZE
    pool, exact, tables = _pool_case(rng, geo, batch, max_blocks,
                                     quantized)
    shape = (batch, width, geo.kv_heads, geo.head_dim)
    q = jnp.asarray(rng.standard_normal(
        shape[:3] + (geo.group, geo.head_dim)), jnp.float32)
    # bf16-representable rows: both paths then store the same bytes
    # in a bf16 pool, and the oracle's f32 copy holds the same values.
    k_new = jnp.asarray(rng.standard_normal(shape),
                        jnp.bfloat16).astype(jnp.float32)
    v_new = jnp.asarray(rng.standard_normal(shape),
                        jnp.bfloat16).astype(jnp.float32)
    return (q, k_new, v_new, pool, exact, tables,
            jnp.asarray(cached_lens, jnp.int32),
            jnp.asarray(chunk_lens, jnp.int32))


def _check_append_like(entry, reference, case, tol_out: float,
                       interpret: bool, what: str):
    """Shared body of the append and verify checks: outputs of every
    REAL query row, and every pool row the call appended, within the
    interpret-mode tests' tolerance of the oracle's (for int8 rows that
    is equality)."""
    import jax
    import numpy as np
    q, k_new, v_new, pool, exact, tables, cached, chunk = case
    kernel = jax.jit(lambda q, k, v, pool: entry(
        q, k, v, pool, tables, cached, chunk, interpret=interpret))
    out, new_pool = kernel(q, k_new, v_new,
                           {key: buf.copy() for key, buf in pool.items()})
    # The oracle is jitted like the kernel path: both then see the same
    # compiled quantizer (XLA turns the eager path's division by 127
    # into a multiplication, one ulp apart).
    with jax.default_matmul_precision("highest"):
        want, want_pool = jax.jit(lambda q, k, v, pool: reference(
            q, k, v, pool, tables, cached, chunk))(q, k_new, v_new,
                                                   exact)
    errs = []
    tables_np = np.asarray(tables)
    landed = {key: np.asarray(buf) for key, buf in new_pool.items()}
    oracle = {key: np.asarray(buf) for key, buf in want_pool.items()}
    for row in range(out.shape[0]):
        real, start = int(chunk[row]), int(cached[row])
        if not real:
            continue
        errs.append(_assert_close(out[row, :real], want[row, :real],
                                  tol_out, f"{what} row {row}"))
        positions = np.arange(start, start + real)
        blocks = tables_np[row, positions // BLOCK_SIZE]
        offsets = positions % BLOCK_SIZE
        for key in landed:
            _assert_close(landed[key][blocks, offsets],
                          oracle[key][blocks, offsets], tol_out,
                          f"{what} pool[{key}] row {row}")
    q16 = q.astype("bfloat16")
    out16, _ = kernel(q16, k_new, v_new,
                      {key: buf.copy() for key, buf in pool.items()})
    err16 = max(_assert_close(out16[row, :int(chunk[row])],
                              want[row, :int(chunk[row])], 2e-2,
                              f"{what} bf16 q row {row}")
                for row in range(out.shape[0]) if int(chunk[row]))
    return (f"max|err| {max(errs):.2e} (f32 q), {err16:.2e} (bf16 q); "
            "appended pool rows match")


def check_append(geo: Geometry, quantized: bool, interpret: bool):
    """``paged_prefill_attention`` vs ``paged_prefill_reference`` at the
    admission slice width: a cold row, a row appending behind a cached
    prefix with a mid-block tail, and a short tail deep in the table."""
    import numpy as np
    from aiko_services_tpu.ops import paged_prefill as pp
    rng = np.random.default_rng(3)
    room = geo.max_seq - geo.chunk
    deep = (room // BLOCK_SIZE) * BLOCK_SIZE
    case = _append_case(
        rng, geo, quantized, geo.chunk,
        cached_lens=(0, min(geo.chunk, deep), deep),
        chunk_lens=(geo.chunk, geo.chunk - BLOCK_SIZE // 2,
                    BLOCK_SIZE + 7))
    return _check_append_like(pp.paged_prefill_attention,
                              pp.paged_prefill_reference, case,
                              1e-3 if quantized else 2e-5, interpret,
                              "append")


def check_verify(geo: Geometry, quantized: bool, interpret: bool):
    """``paged_verify_attention`` (speculative window, mid-block start,
    ragged and inactive rows) vs ``paged_prefill_reference``."""
    from aiko_services_tpu.ops import paged_prefill as pp
    import numpy as np
    rng = np.random.default_rng(4)
    deep = geo.max_seq - 3 * BLOCK_SIZE + 5
    case = _append_case(rng, geo, quantized, 5,
                        cached_lens=(BLOCK_SIZE * 2 + 5, deep, 0, 14),
                        chunk_lens=(5, 3, 0, 5))
    return _check_append_like(pp.paged_verify_attention,
                              pp.paged_prefill_reference, case,
                              1e-3 if quantized else 2e-5, interpret,
                              "verify")


def check_int8_matmul(geo: Geometry, shape, interpret: bool):
    """The Pallas int8 dequant-matmul vs its XLA lowering at a decode
    shape (m = the smoke's slot count), bf16 activations as served."""
    import jax.numpy as jnp
    import numpy as np
    from aiko_services_tpu.ops.quant import int8_matmul
    k, n = shape
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((geo.slots, k)), jnp.bfloat16)
    q = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    s = jnp.full((1, n), k ** -0.5 / 127.0, jnp.float32)
    got = int8_matmul(x, q, s, interpret=interpret)
    want = (jnp.dot(x, q.astype(x.dtype),
                    preferred_element_type=jnp.float32) * s).astype(
                        x.dtype)
    err = _assert_close(got, want, 2e-2, f"int8_matmul {k}x{n}")
    return f"max|err| {err:.2e}"


def check_flash(geo: Geometry, interpret: bool):
    """``flash_attention`` (GQA-native) at the prefill length vs
    ``attention_reference`` on repeated K/V, in bf16 — the dtype of
    every caller, and the one the kernel's default-precision matmuls
    contract exactly (f32 inputs would be rounded to bf16 by the MXU;
    the interpret-mode 1e-5 does not carry to the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from aiko_services_tpu.ops.attention import (attention_reference,
                                                 flash_attention)
    rng = np.random.default_rng(6)
    heads = geo.kv_heads * geo.group
    q = jnp.asarray(rng.standard_normal(
        (1, heads, geo.flash_len, geo.head_dim)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal(
        (1, geo.kv_heads, geo.flash_len, geo.head_dim)), jnp.bfloat16)
        for _ in range(2))
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=interpret))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = attention_reference(
            *(a.astype(jnp.float32) for a in (
                q, jnp.repeat(k, geo.group, axis=1),
                jnp.repeat(v, geo.group, axis=1))), causal=True)
    err = _assert_close(got, want, 3e-2, "flash bf16")
    return f"max|err| {err:.2e} (bf16)"


def kernel_phase(report: Report, geo: Geometry, interpret: bool):
    import jax.numpy as jnp
    from aiko_services_tpu.ops.paged_attention import (decode_scale_row,
                                                       kernel_serves)
    for quantized, label in ((False, "bf16 KV"), (True, "int8 KV")):
        dtype = jnp.int8 if quantized else jnp.bfloat16
        if not kernel_serves(geo.head_dim, geo.kv_heads, dtype,
                             interpret):
            # The dispatch takes the reference here and the path tag
            # says so (asserted in the serve phase): nothing to compile.
            print(f"note {label}: the paged kernels do not serve "
                  f"{geo.kv_heads} {label.split()[0]} kv heads per "
                  "shard (Mosaic's sublane tiling); the reference "
                  "does", flush=True)
            continue
        for name, fn in (("decode", check_decode),
                         ("append", check_append),
                         ("verify", check_verify)):
            if (fn is check_decode and quantized and not interpret
                    and not decode_scale_row(BLOCK_SIZE, geo.kv_heads)):
                print(f"note {label}: the decode kernel copies no "
                      f"scale rows for {geo.kv_heads} kv heads per "
                      "shard; the reference does", flush=True)
                continue
            report.check(f"{name} attention, {label}", fn, geo,
                         quantized, interpret)
    if decode_scale_row(BLOCK_SIZE, geo.kv_heads):
        report.check("decode append, int8 KV", check_decode_append,
                     geo, interpret)
    for shape in geo.matmuls:
        report.check(f"int8_matmul m={geo.slots} K,N={shape}",
                     check_int8_matmul, geo, shape, interpret)
    report.check(f"flash_attention len={geo.flash_len}", check_flash,
                 geo, interpret)


# --------------------------------------------------------------------------- #
# Phase 3: serve over the wire


def build_server(geo: Geometry, tp: int):
    """The paged server as a deployment builds it: int8 weights from a
    seed (a bf16 8B init does not fit one chip), int8 KV, prefix cache
    on, the default chunked admission, and a pool that holds every
    slot's worst case."""
    import jax
    from aiko_services_tpu.models import llama
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    from aiko_services_tpu.parallel.mesh import ReplicaMesh

    name = f"{geo.config_name}_smoke{geo.layers}"
    config = dataclasses.replace(llama.CONFIGS[geo.config_name],
                                 n_layers=geo.layers)
    llama.CONFIGS[name] = config
    params = llama.random_quantized_params(config, jax.random.PRNGKey(0),
                                           bits=8)
    max_seq = geo.max_seq + (-geo.max_seq) % BLOCK_SIZE
    return PagedContinuousServer(
        config_name=name, slots=geo.slots, max_seq=max_seq,
        chunk_steps=8, quantize=True, quantize_kv=True, params=params,
        block_size=BLOCK_SIZE,
        total_blocks=geo.slots * (max_seq // BLOCK_SIZE),
        enable_prefix_cache=True,
        chunk_prefill_tokens=(None if geo.chunk == 256 else geo.chunk),
        replica_mesh=ReplicaMesh(tp=tp) if tp > 1 else None)


def smoke_prompts(geo: Geometry, vocab: int):
    """Seeded prompts that between them cover: shorter than one
    admission slice, ~mid and ~long (mixed prefill/decode steps), two
    sharing a whole-block prefix, and one prompt sent twice."""
    import numpy as np
    rng = np.random.default_rng(7)

    def prompt(n):
        return rng.integers(1, vocab, n).astype(np.int32)

    shared = prompt(geo.shared_prefix)
    return {
        "short": prompt(geo.short_prompt),
        "mid": prompt(geo.mid_prompt),
        "long": prompt(geo.long_prompt),
        "prefix_a": np.concatenate([shared, prompt(geo.short_prompt)]),
        "prefix_b": np.concatenate([shared,
                                    prompt(geo.short_prompt + 5)]),
    }


def serve_phase(report: Report, geo: Geometry, tp: int):
    """Returns ``{name: tokens}`` of the served requests."""
    import uuid

    import jax
    import numpy as np
    from aiko_services_tpu.obs import compiles
    from aiko_services_tpu.orchestration.client import InferClient
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousReplica)
    from aiko_services_tpu.runtime import (Process, actor_args,
                                           compose_instance)
    from aiko_services_tpu.runtime.event import EventEngine

    ledger = compiles.LEDGER
    with report.phase("param build + server"):
        server = build_server(geo, tp)
        jax.block_until_ready(server.params)
    if "param build + server" in report.failures:
        return {}
    config = server.config

    # The event loop runs callbacks unguarded: an exception inside the
    # replica's pump (a compile error, say) kills the loop thread and
    # every future then just never resolves.  Keep the traceback.
    loop_errors = []

    def on_thread_error(args):
        loop_errors.append("".join(traceback.format_exception(
            args.exc_type, args.exc_value, args.exc_traceback)))

    threading.excepthook = on_thread_error
    engine = EventEngine()
    thread = engine.run_in_thread()
    broker = f"smoke-{uuid.uuid4().hex[:6]}"
    processes = []

    def make_process(pid):
        process = Process(namespace="smoke", hostname="chip",
                          pid=str(pid), engine=engine, broker=broker)
        processes.append(process)
        return process

    def wait(future, what):
        deadline = time.monotonic() + FUTURE_TIMEOUT_S
        while not future.done:
            if not thread.is_alive() or loop_errors:
                raise RuntimeError(
                    f"event loop died while waiting for {what}:\n"
                    + "\n".join(loop_errors))
            if time.monotonic() > deadline:
                raise TimeoutError(f"{what}: no response in "
                                   f"{FUTURE_TIMEOUT_S:.0f} s")
            time.sleep(0.05)
        if future.error is not None:
            raise RuntimeError(f"{what}: error {future.error!r}")
        return future

    tokens = {}
    try:
        replica = compose_instance(
            ContinuousReplica, actor_args("replica"),
            process=make_process(2), server=server)
        client = InferClient(make_process(9), replica.topic_in)

        with report.phase("warm-up compile"):
            base = ledger.snapshot()
            programs = server.warm_prefill_ladder()
            # Ordinary warm-up traffic compiles the decode and mixed
            # prefill+decode programs, which need live decode state.
            warm = client.submit(
                np.arange(1, geo.short_prompt + 1, dtype=np.int32),
                max_new_tokens=geo.max_new)
            wait(warm, "warm-up request")
            after = ledger.snapshot()
            print(f"   ladder programs dispatched: {programs}; "
                  f"compiles {after['compiles'] - base['compiles']}, "
                  "persistent-cache hits "
                  f"{after['cache_hits'] - base['cache_hits']}, misses "
                  f"{after['cache_misses'] - base['cache_misses']}",
                  flush=True)

        with report.phase("serve"):
            prompts = smoke_prompts(geo, config.vocab_size)
            # Wave 1: the cold twins, the streamed request among them.
            wave = {name: client.submit(
                prompts[name], max_new_tokens=geo.max_new,
                stream=(name == "mid"))
                for name in ("short", "mid", "long", "prefix_a")}
            for name, future in wave.items():
                wait(future, name)
            # Wave 2: the repeat and the prefix sharer, both of which
            # must find their blocks in the prefix cache.
            wave.update({name: client.submit(prompts[source],
                                             max_new_tokens=geo.max_new)
                         for name, source in (("short_again", "short"),
                                              ("prefix_b", "prefix_b"),
                                              ("long_again", "long"))})
            for name, future in wave.items():
                wait(future, name)
                got = [int(t) for t in future.tokens]
                tokens[name] = got
                if len(got) != geo.max_new:
                    raise AssertionError(
                        f"{name}: {len(got)} tokens, budget "
                        f"{geo.max_new}")
                if min(got) < 0 or max(got) >= config.vocab_size:
                    raise AssertionError(f"{name}: token outside the "
                                         "vocabulary")
            streamed = wave["mid"]
            if list(streamed.partial_tokens) != tokens["mid"]:
                raise AssertionError(
                    "streamed partials do not concatenate to the final "
                    f"tokens: {streamed.partial_tokens} vs "
                    f"{tokens['mid']}")
            for again, cold in (("short_again", "short"),
                                ("long_again", "long")):
                if tokens[again] != tokens[cold]:
                    raise AssertionError(
                        f"{again} differs from its cold twin: "
                        f"{tokens[again]} vs {tokens[cold]}")

            stats = server.stats()
            served = (stats["decode_attention_path"],
                      stats["prefill_attention_path"])
            print(f"   attention paths: decode={served[0]} "
                  f"prefill={served[1]}; scale append="
                  f"{stats['decode_scale_append_path']}; prefix_hits="
                  f"{stats['prefix_hits']}; requests "
                  f"{len(tokens)}; tokens "
                  f"{sum(map(len, tokens.values()))}", flush=True)
            # One chip must run the kernels.  Under --tp the tags are
            # the dispatch's answer for the per-shard pool (two int8
            # kv heads per shard take the reference) and are reported.
            if tp == 1 and served != ("kernel", "kernel"):
                raise AssertionError(
                    f"serving ran {served}, not the kernels")
            if not stats["prefix_hits"] > 0:
                raise AssertionError("no prefix-cache hit")
            if loop_errors:
                raise RuntimeError("event loop error:\n"
                                   + "\n".join(loop_errors))

        if tp > 1:
            with report.phase("four-chip placement"):
                check_placement(server, tp)
    finally:
        for process in reversed(processes):
            process.terminate()
        engine.terminate()
        thread.join(timeout=10)
        if thread.is_alive():
            report.failures.append("event loop thread did not stop")
    return tokens


def check_placement(server, tp: int):
    """The params' and the pool's shards sit on ``tp`` distinct
    devices, and every chip holds about its share of the weights —
    not everything on device 0."""
    import jax
    leaves = jax.tree_util.tree_leaves(server.params)
    biggest = max(leaves, key=lambda leaf: leaf.nbytes)
    for what, array in (("largest weight", biggest),
                        ("pool[0]['k']", server.pool[0]["k"])):
        devices = {shard.device for shard in array.addressable_shards}
        print(f"   {what} {array.shape}: shards on "
              f"{sorted(d.id for d in devices)}", flush=True)
        if len(devices) != tp:
            raise AssertionError(f"{what} lives on {len(devices)} "
                                 f"devices, not {tp}")
    per_device = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = per_device.get(
                shard.device.id, 0) + shard.data.nbytes
    total = sum(leaf.nbytes for leaf in leaves)
    for device, held in sorted(per_device.items()):
        print(f"   device {device}: {held / 2**20:.0f} MiB of weights "
              f"({held / total:.0%} of {total / 2**20:.0f} MiB)",
              flush=True)
    if max(per_device.values()) > 0.6 * total:
        raise AssertionError("one chip holds most of the weights")


def compare_tokens(tokens, expected_path: str):
    """Greedy tokens must equal another run's, request by request (the
    repo's bitwise tensor-parallel contract); a difference is reported
    at its first position, never tolerated."""
    with open(expected_path) as source:
        expected = json.load(source)
    differing = []
    for name in sorted(expected):
        want, got = expected[name], tokens.get(name)
        if got == want:
            print(f"   {name}: {len(want)} tokens equal", flush=True)
            continue
        first = next((i for i, (a, b) in enumerate(zip(got or [], want))
                      if a != b), min(len(got or []), len(want)))
        print(f"   {name}: FIRST DIFFERENCE at position {first}: got "
              f"{(got or [None] * (first + 1))[first:first + 4]} want "
              f"{want[first:first + 4]}", flush=True)
        differing.append(name)
    if differing:
        raise AssertionError(f"tokens differ from {expected_path}: "
                             + ", ".join(differing))


# --------------------------------------------------------------------------- #


def print_memory():
    import jax
    for device in jax.devices():
        stats = device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        limit = stats.get("bytes_limit")
        if peak is None:
            print(f"   device {device.id}: memory_stats unavailable")
            continue
        print(f"   device {device.id}: peak {peak / 2**30:.2f} GiB in "
              f"use of {limit / 2**30:.2f} GiB", flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel degree of the replica")
    parser.add_argument("--rehearsal", action="store_true",
                        help="tiny config, CPU, interpreted kernels")
    parser.add_argument("--tokens-out", default=None,
                        help="write the served tokens here as JSON")
    parser.add_argument("--expect-tokens", default=None,
                        help="a --tokens-out file of another run (one "
                             "chip, say) that this run's tokens must "
                             "equal")
    args = parser.parse_args(argv)
    if args.rehearsal:
        if args.tp != 1:
            parser.error("--rehearsal is single-device")
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["AIKO_DECODE_ATTENTION"] = "interpret"
        os.environ["AIKO_PREFILL_ATTENTION"] = "interpret"

    from aiko_services_tpu.obs import compiles
    cache_dir = compiles.entry_point_cache()

    import jax
    import jaxlib
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu_version}", flush=True)
    print(f"chip_smoke: compile cache {cache_dir} "
          f"(jax_compilation_cache_dir="
          f"{jax.config.jax_compilation_cache_dir})", flush=True)
    from aiko_services_tpu import native
    codec = ("native C (built on demand from native/sexpr_module.c)"
             if native.sexpr_native() is not None else "pure Python")
    print(f"chip_smoke: wire codec = {codec}", flush=True)

    if args.rehearsal:
        print("chip_smoke: REHEARSAL — tiny config on the CPU, kernels "
              "interpreted; this says nothing about the chip",
              flush=True)
        geo = REHEARSAL
    elif device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device['platform']!r} — not run", flush=True)
        return 2
    elif device["count"] < args.tp:
        print(f"chip_smoke: --tp {args.tp} needs {args.tp} chips; JAX "
              f"found {device['count']} — not run", flush=True)
        return 2
    else:
        geo = dataclasses.replace(FULL, kv_heads=FULL.kv_heads // args.tp)

    compiles.install(service="chip_smoke")
    report = Report()
    with report.phase("kernels vs oracles"):
        kernel_phase(report, geo, interpret=args.rehearsal)
    tokens = serve_phase(report, geo if args.rehearsal else FULL,
                         args.tp)
    if args.tokens_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.tokens_out)),
                    exist_ok=True)
        with open(args.tokens_out, "w") as out:
            json.dump(tokens, out)
    if args.expect_tokens:
        with report.phase(f"tokens equal {args.expect_tokens}"):
            compare_tokens(tokens, args.expect_tokens)

    ledger = compiles.LEDGER.snapshot()
    print("== set-up facts "
          f"({device['kind']} x{device['count']}; no rate is claimed)")
    for name, seconds in report.phase_seconds.items():
        print(f"   {name}: {seconds} s")
    print(f"   compiles {ledger['compiles']}, persistent-cache hits "
          f"{ledger['cache_hits']}, misses {ledger['cache_misses']}")
    print_memory()
    if report.failures:
        print("chip_smoke: FAILED: " + "; ".join(report.failures),
              flush=True)
        return 1
    result = {"ok": True, "device": device}
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
