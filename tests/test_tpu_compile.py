"""Compiles for a DESCRIBED TPU v5e (no chip attached): what the chip's
compiler refuses — a tile off Mosaic's tiling, a kernel over its VMEM
limit — and what it makes of the serving programs, checked here at no
chip time.  Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture, never at import (one
process at a time may load the TPU's library; every xdist worker
imports every test file), and every such test lives in THIS file so
that one worker loads it.  Code that asks ``jax.default_backend()``
still sees the CPU, so the tests steer it to its TPU branch themselves
and drop every jit cache afterwards (a trace made under that steer
must not be found by a CPU test of the same shapes)."""

import dataclasses
import functools
import json
import pathlib
import re

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as error:          # noqa: BLE001 — any refusal
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Steer the program's backend questions to their TPU answers for
    one test, and forget every trace made meanwhile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def _shaped(tree, sharding):
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=sharding), tree)


# --------------------------------------------------------------------------- #
# The append kernels at the widths the benchmark's cells serve

#: name → (batch, T, kv_heads, group, block, pool blocks, table width,
#: pool dtype, query dtype, window, kv_limit, verify)
APPEND_GEOMETRIES = {
    "mistral_int8_window": (1, 256, 8, 4, 16, 4609, 160, jnp.int8,
                            jnp.bfloat16, 4096, 128, False),
    "mixtral_int8": (1, 256, 8, 4, 16, 6145, 160, jnp.int8,
                     jnp.bfloat16, None, 128, False),
    "longdoc_int8_window_kv512": (1, 256, 8, 4, 16, 4609, 512, jnp.int8,
                                  jnp.bfloat16, 4096, 512, False),
    "nemotron_bf16_group16": (1, 256, 2, 16, 16, 9217, 144, jnp.bfloat16,
                              jnp.bfloat16, None, 64, False),
    "tp_shard_bf16_two_heads": (1, 256, 2, 4, 16, 4609, 160,
                                jnp.bfloat16, jnp.bfloat16, 4096, 128,
                                False),
    "f32_pool_f32_queries": (1, 128, 8, 4, 16, 1025, 64, jnp.float32,
                             jnp.float32, None, 64, False),
    "contiguous_view_block128_int8": (4, 256, 8, 4, 128, 65, 16,
                                      jnp.int8, jnp.bfloat16, None, None,
                                      False),
    "verify_int8_32_slots": (32, 5, 8, 4, 16, 4609, 160, jnp.int8,
                             jnp.bfloat16, 4096, None, True),
    "evabyte_int8_32_kv_heads": (1, 256, 32, 1, 16, 1537, 184, jnp.int8,
                                 jnp.bfloat16, None, None, False),
}


@pytest.mark.parametrize("name", sorted(APPEND_GEOMETRIES))
def test_append_kernels_compile_for_v5e(name, one_chip, as_on_tpu):
    """Write kernel + attention sweep through the TPU compiler: tiles
    on Mosaic's tiling, scoped VMEM inside its limit, and the sweep's
    call named ``paged_prefill_call`` with a 4-D result (the decode
    kernel's roofline metric looks for ``closed_call`` and a 3-D one)."""
    from aiko_services_tpu.ops import paged_prefill as pp
    (batch, T, kv, group, bs, n_blocks, max_blocks, pool_dt, q_dt,
     window, kv_limit, verify) = APPEND_GEOMETRIES[name]
    hd = 128
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = {"k": S((n_blocks, bs, kv, hd), pool_dt),
            "v": S((n_blocks, bs, kv, hd), pool_dt)}
    if pool_dt == jnp.int8:
        pool["ks"] = S((n_blocks, bs, kv), jnp.float32)
        pool["vs"] = S((n_blocks, bs, kv), jnp.float32)
    fn = pp.paged_verify_attention if verify else pp.paged_prefill_attention
    compiled = jax.jit(
        functools.partial(fn, window=window, kv_limit=kv_limit),
        donate_argnums=(3,)).lower(
        S((batch, T, kv, group, hd), q_dt), S((batch, T, kv, hd), q_dt),
        S((batch, T, kv, hd), q_dt), pool, S((batch, max_blocks), jnp.int32),
        S((batch,), jnp.int32), S((batch,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(
        r"^\s*%paged_prefill_call[.\d]* = \w+\[([\d,]+)\]\S* custom-call\(",
        text, re.M)
    assert calls and all(shape.count(",") == 3 for shape in calls), calls
    assert " while(" not in text


# --------------------------------------------------------------------------- #
# The decode step's scale append at the cells' widths


def _decode_attn_pattern():
    spec = (pathlib.Path(__file__).resolve().parent.parent / "benchmark"
            / "layer_metrics" / "decode_attn_roofline.json")
    return re.compile(json.loads(spec.read_text())["op_pattern"])


def _custom_call_lines(text, name):
    return [line.strip() for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%%%s[.\d]* = .* custom-call\(" % name,
                        line)]


#: name → (slots, kv heads, group, pool dtype, window, table width, pool
#: blocks, block, the width ``decode_keys_per_iteration`` gives): the
#: cells that call the K/V decode kernel, the contiguous view, and
#: the widest tile with int8 and f32 rows.
DECODE_GEOMETRIES = {
    "sdar30b_fixedlen_bf16_4x32": (64, 4, 32, jnp.bfloat16, None, 129,
                                   8193, 16, 256),
    "mistral7b_chat_int8_8x4_window": (32, 8, 4, jnp.int8, 4096, 160,
                                       4609, 16, 128),
    "nemotron3super_bf16_2x16": (64, 2, 16, jnp.bfloat16, None, 144, 9217,
                                 16, 512),
    "contiguous_view_block128_int8": (8, 8, 4, jnp.int8, None, 16, 129,
                                      128, 128),
    "contiguous_view_block128_bf16_2_heads": (8, 2, 16, jnp.bfloat16, None,
                                              16, 129, 128, 512),
    "int8_pool_block32_4_heads": (8, 4, 8, jnp.int8, None, 32, 513, 32, 256),
    "contiguous_view_block128_int8_4_heads": (8, 4, 8, jnp.int8, None, 16,
                                              129, 128, 256),
    "f32_pool_2_heads": (8, 2, 16, jnp.float32, None, 64, 1025, 16, 512),
    "f32_pool_32_heads": (8, 32, 1, jnp.float32, None, 64, 1025, 16, 128),
    # Float pools through the buffer's word rows at a stride of 4 word
    # rows (8 bf16 heads) and of 4 f32 heads, under a window.
    "bf16_pool_8_heads_4_rows_window": (32, 8, 4, jnp.bfloat16, 4096, 160,
                                        4609, 16, 128),
    "f32_pool_4_heads_window": (8, 4, 8, jnp.float32, 512, 64, 1025, 16,
                                256),
    # One query row a kv head: the all-heads form.
    "evabyte_files_int8_32x1": (8, 32, 1, jnp.int8, None, 184, 1537, 16,
                                128),
    "bf16_pool_8_heads_one_row_window": (8, 8, 1, jnp.bfloat16, 1024, 184,
                                         1537, 16, 128),
    "contiguous_view_block128_int8_16_heads_one_row": (
        8, 16, 1, jnp.int8, None, 16, 129, 128, 128),
}


#: The ``attend`` body each of them takes (``decode_attend_form``).
DECODE_FORMS = {
    "sdar30b_fixedlen_bf16_4x32": "word_rows",
    "nemotron3super_bf16_2x16": "word_rows",
    "contiguous_view_block128_bf16_2_heads": "word_rows",
    "f32_pool_2_heads": "word_rows",
    "bf16_pool_8_heads_4_rows_window": "word_rows",
    "f32_pool_4_heads_window": "word_rows",
    "mistral7b_chat_int8_8x4_window": "per_head",
    "contiguous_view_block128_int8": "per_head",
    "int8_pool_block32_4_heads": "per_head",
    "contiguous_view_block128_int8_4_heads": "per_head",
    "f32_pool_32_heads": "all_heads",
    "evabyte_files_int8_32x1": "all_heads",
    "bf16_pool_8_heads_one_row_window": "all_heads",
    "contiguous_view_block128_int8_16_heads_one_row": "all_heads",
}


@pytest.mark.parametrize("name", sorted(DECODE_GEOMETRIES))
def test_decode_kernel_compiles_for_v5e(name, one_chip, as_on_tpu):
    """The decode kernel at its width through the TPU compiler: the
    key buffers inside Mosaic's default scoped VMEM, copies from a
    loop into dynamic buffer rows, V groups zeroed from a loop, and
    the call still named ``closed_call`` with a 3-D result in the
    queries' dtype (what ``decode_attn_roofline`` looks for in a trace,
    where the line also prints the block table's type first)."""
    from aiko_services_tpu.ops import paged_attention as pa
    (slots, kv, group, pool_dt, window, table, n_blocks, bs,
     wide) = DECODE_GEOMETRIES[name]
    hd = 128
    form = pa.decode_attend_form(group, kv, bs, pool_dt)
    assert form == DECODE_FORMS[name]
    assert pa.decode_keys_per_iteration(table * bs, bs, kv, form) == wide
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = S((n_blocks, bs, kv, hd), pool_dt)
    scales = S((n_blocks, bs, kv), jnp.float32) \
        if pool_dt == jnp.int8 else None
    text = jax.jit(functools.partial(
        pa.paged_decode_attention, window=window)).lower(
        S((slots, kv, group, hd), jnp.bfloat16), pool, pool,
        S((slots, table), jnp.int32), S((slots,), jnp.int32), scales,
        scales).compile().as_text()
    calls = _custom_call_lines(text, "closed_call")
    assert len(calls) == 1
    assert re.search(r"= bf16\[%d,%d,128\]" % (slots, kv * group),
                     calls[0])
    assert " while(" not in text


#: name → (block, pool blocks, kv heads, rows): an int8 pool layer.
APPEND_GEOMETRIES_DECODE = {
    "mistral7b_chat_pool": (16, 4609, 8, 32),
    "mixtral8x7b_chat_pool": (16, 6145, 8, 32),
    "contiguous_view_block128": (128, 65, 8, 32),
    "evabyte_files_pool_32_kv_heads": (16, 1537, 32, 8),
}


@pytest.mark.parametrize("name", sorted(APPEND_GEOMETRIES_DECODE))
def test_decode_append_compiles_for_v5e(name, one_chip):
    """The decode step's append kernel through the TPU compiler inside
    a scan, as the decode programs hold it: single-row copies at
    dynamic rows and ``(kv, head_dim)`` int8 row copies into
    ``pool[block, offset]`` on Mosaic's tiling, all four pools written
    in place (no copy of a pool or a plane to satisfy the alias: no
    temporaries), the scan still the one ``while``, and a custom call
    named ``paged_decode_append`` that the decode kernel's roofline
    metric does not take for its own."""
    from aiko_services_tpu.ops import paged_attention as pa
    bs, n_blocks, kv, batch = APPEND_GEOMETRIES_DECODE[name]
    hd = 128
    n_rows = n_blocks * bs * kv // pa.decode_scale_row(bs, kv)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = {"k": S((n_blocks, bs, kv, hd), jnp.int8),
            "v": S((n_blocks, bs, kv, hd), jnp.int8),
            "ks": S((n_rows, 128), jnp.float32),
            "vs": S((n_rows, 128), jnp.float32)}
    rows = {"k": S((batch, kv, hd), jnp.int8),
            "v": S((batch, kv, hd), jnp.int8),
            "ks": S((batch, kv), jnp.float32),
            "vs": S((batch, kv), jnp.float32)}

    def steps(pool, rows, blocks, offsets):
        def body(pool, _):
            return pa.paged_decode_append(pool, rows, blocks,
                                          offsets), None
        return jax.lax.scan(body, pool, None, length=8)[0]

    compiled = jax.jit(steps, donate_argnums=(0,)).lower(
        pool, rows, S((batch,), jnp.int32), S((batch,), jnp.int32)
    ).compile()
    text = compiled.as_text()
    calls = _custom_call_lines(text, "paged_decode_append")
    assert len(calls) == 1
    assert not any(_decode_attn_pattern().search(line) for line in calls)
    assert ("output_to_operand_aliasing={{0}: (8, {}), {1}: (9, {}), "
            "{2}: (10, {}), {3}: (11, {})}") in calls[0]
    assert text.count(" while(") == 1
    assert not [line for line in text.splitlines()
                if re.search(r"= \(?(f32\[%d,128\]|s8\[%d,)\S* copy\("
                             % (n_rows, n_blocks), line)]
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# --------------------------------------------------------------------------- #
# The serving programs that hold the sweep and the decode scan


def _kernel_width(config):
    """A tiny config at the one head width the compiled kernels serve
    (head_dim 128), with kv heads that fill an int8 scale row."""
    return dataclasses.replace(config, d_model=1024, n_heads=8,
                               n_kv_heads=8)


def _pool_shaped_ops(text, n_blocks, rank):
    """copy / transpose instructions whose result is an array of
    ``rank`` dimensions led by the pool's block count.  (Not
    ``copy-start``: XLA's own asynchronous moves of a buffer between
    memory spaces keep its layout.)"""
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(r"= \(?\w+\[%d(,\d+){%d}\]" % (n_blocks, rank - 1),
                         line)
            and re.search(r" (copy|transpose)\(", line)]


def _scan_body(text):
    """The lines of the computation the program's one ``while`` runs
    as its body."""
    body = re.search(r" while\(.*body=(%[\w.-]+)", text).group(1)
    start = re.search(r"^%s \(.*\{$" % re.escape(body), text, re.M)
    return text[start.end():text.index("\n}", start.end())].splitlines()


def _scale_row_makers(lines, n_rows):
    """What in ``lines`` makes an array shaped like a scale plane's lane
    rows ``f32[n_rows,128]`` (tuple results included), as
    ``(opcode, instruction name)``.  Not the pure plumbing of a loop's
    state (``get-tuple-element``, ``bitcast``, ``parameter``)."""
    made = []
    for line in lines:
        found = re.match(
            r"\s*(?:ROOT )?%%([\w.-]+) = \(?f32\[%d,128\]\S*"
            r"(?:, [^=]*?)?\)? ([\w-]+)\(" % n_rows, line)
        if found and found.group(2) not in ("get-tuple-element",
                                            "bitcast", "parameter"):
            made.append((found.group(2), found.group(1)))
    return made


def test_one_step_chunk_compiles_with_the_append_in_line(one_chip,
                                                        as_on_tpu):
    """A chunk's one-step tail: XLA unrolls the scan, so the append
    kernel sits in the entry computation on the program's own donated
    pool.  It has to compile there too — on a pool the caller KEEPS the
    TPU compiler aborts (a copy for the alias into the K/V outputs
    pinned to HBM), which is why every serving program donates."""
    from aiko_services_tpu.models import llama
    config = _kernel_width(llama.CONFIGS["mistral_tiny"])
    slots, bs, n_blocks, table = 4, 16, 97, 24
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = _shaped(jax.eval_shape(
        lambda: llama.init_params(config, jax.random.PRNGKey(0))),
        one_chip)
    pool = _shaped(jax.eval_shape(
        lambda: llama.init_paged_cache(config, n_blocks, bs,
                                       quantize_kv=True)), one_chip)
    text = llama.decode_chunk_paged.lower(
        params, S((slots, 1), jnp.int32), pool,
        S((slots, table), jnp.int32), S((slots,), jnp.int32),
        S((slots,), jnp.bool_), 1, config).compile().as_text()
    assert text.count(" while(") == 0
    assert len(_custom_call_lines(text, "paged_decode_append")) \
        == config.n_layers
    assert not _pool_shaped_ops(text, n_blocks, 4)


@pytest.mark.parametrize("config_name", ["mistral_tiny", "moe_tiny"])
def test_serving_programs_hold_one_scan_and_no_pool_copy(
        config_name, one_chip, as_on_tpu):
    """``serve_chunk_mixed`` and ``decode_chunk_paged`` hold exactly ONE
    ``while`` (the decode scan: ``decode_step_ms`` reads "the one
    %while" of that program) and ``prefill_append_paged`` none; none
    copies or transposes a K/V pool, and the int8 scale planes are
    re-laid-out no more often than the write kernel and the decode
    scan's entry and exit already cost them (the attention sweep reads
    the planes where the write kernel left them).  Inside the scan's
    body nothing makes a scale plane but the append kernel, a call a
    layer that writes both planes in place — no scatter fusion over a
    plane, and no copy of one to satisfy the alias — and whatever XLA
    moves between memory spaces on its own (``copy-start``/``-done``,
    sliced or whole: asynchronous, layout kept)."""
    from aiko_services_tpu.models import llama
    config = _kernel_width(llama.CONFIGS[config_name])
    layers, slots, bs, n_blocks, table = config.n_layers, 4, 16, 97, 24
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = _shaped(jax.eval_shape(
        lambda: llama.init_params(config, jax.random.PRNGKey(0))),
        one_chip)
    pool = _shaped(jax.eval_shape(
        lambda: llama.init_paged_cache(config, n_blocks, bs,
                                       quantize_kv=True)), one_chip)
    state = {"token": S((slots, 1), jnp.int32),
             "positions": S((slots,), jnp.int32),
             "active": S((slots,), jnp.bool_),
             "remaining": S((slots,), jnp.int32),
             "temps": S((slots,), jnp.float32),
             "tops": S((slots,), jnp.float32),
             "adapter_ids": S((slots,), jnp.int32),
             "tables": S((slots, table), jnp.int32)}
    tokens = S((1, 128), jnp.int32)
    scalar = S((), jnp.int32)
    mixed = llama.serve_chunk_mixed.lower(
        params, state, pool, tokens, scalar, scalar, 4, config,
        prefill_kv_limit=16).compile().as_text()
    decode = llama.decode_chunk_paged.lower(
        params, state["token"], pool, state["tables"],
        state["positions"], state["active"], 4, config).compile().as_text()
    standalone = llama.prefill_append_paged.lower(
        params, tokens, pool, S((1, table), jnp.int32), scalar, config,
        kv_limit=16, compute_logits=False).compile().as_text()

    assert mixed.count(" while(") == 1
    assert decode.count(" while(") == 1
    assert standalone.count(" while(") == 0
    for text in (mixed, standalone):
        assert "%paged_prefill_call" in text       # the kernel path ran
    for text in (mixed, decode, standalone):
        assert not _pool_shaped_ops(text, n_blocks, 4)
    # Scale planes (n, bs, kv): in and out of the write kernel's layout,
    # k and v, a layer — what the parent of PR 27 already paid.
    assert len(_pool_shaped_ops(standalone, n_blocks, 3)) <= 4 * layers
    # The decode scan: planes to lane rows at its entry, back at its
    # exit, k and v, a layer (both budgets are met with equality) — and
    # nothing pool-shaped in between.
    for text, budget in ((decode, 4 * layers), (mixed, 8 * layers)):
        assert (len(_pool_shaped_ops(text, n_blocks, 3))
                + len(_pool_shaped_ops(text, n_blocks, 2))) <= budget
        body = _scan_body(text)
        appends = _custom_call_lines("\n".join(body), "paged_decode_append")
        assert len(appends) == layers
        assert not any(_decode_attn_pattern().search(line)
                       for line in appends)
        assert len(_custom_call_lines("\n".join(body),
                                      "closed_call")) == layers
        # XLA's own asynchronous moves of a plane between memory
        # spaces, whole or sliced (a ``ConcatBitcast`` custom call
        # glues the slices), are not the program's work.
        strangers = [
            (op, name) for op, name in _scale_row_makers(body, n_blocks)
            if not re.match(r"((copy|slice)-(start|done)|tuple)$", op)
            and not name.startswith(("paged_decode_append",
                                     "custom-call"))]
        assert not strangers


# --------------------------------------------------------------------------- #
# The latent-attention kernels and programs at mistralsmall4.docs' sizes

#: The cell: 64 slots, tables of 16,896 / 16 entries, a pool of 49,152
#: blocks (+ scratch) of 16 rows of 384 values, 32 heads, rank 256.
LATENT_CELL = dict(slots=64, table=1056, n_blocks=49153, bs=16, width=384,
                   heads=32, rank=256)


def _scoped_vmem_asked(refused) -> float:
    """Bytes the TPU compiler names when it refuses a kernel for its
    scoped VMEM limit."""
    size, unit = re.search(r"Scoped allocation with size ([\d.]+)([KMG])",
                           str(refused.value)).groups()
    return float(size) * 2 ** {"K": 10, "M": 20, "G": 30}[unit]


def test_latent_kernels_compile_for_v5e(one_chip, as_on_tpu):
    """The decode kernel with the cell's 64 x 1,056 block tables as
    prefetched scalars (270 KB: twelve times the largest a paged
    kernel here had taken), the prefill kernel at every slice width
    under its raised VMEM limit, and the append in both its forms, in
    place: through the TPU compiler, no temporaries."""
    from aiko_services_tpu.ops import latent_attention as la
    g = LATENT_CELL
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pool = S((g["n_blocks"], g["bs"], g["width"]), jnp.bfloat16)
    attend = dict(rank=g["rank"], sm_scale=0.1)
    decode = jax.jit(functools.partial(la.latent_decode_attention,
                                       **attend)).lower(
        S((g["slots"], g["heads"], g["width"]), jnp.bfloat16), pool,
        S((g["slots"], g["table"]), jnp.int32),
        S((g["slots"],), jnp.int32)).compile()
    calls = _custom_call_lines(decode.as_text(), "closed_call")
    assert len(calls) == 1
    # What decode_attn_roofline's pattern asks of the trace's text: a
    # 3-D bf16 result and the block tables first.
    assert re.search(r"%closed_call[.\d]* = bf16\[64,32,256\]", calls[0])
    assert "operand_layout_constraints={s32[64,1056]" in calls[0]
    # The decode program's scoped VMEM at the width it chose for 32
    # query rows, as the TPU compiler states it when refused: the two
    # key buffers of one iteration and little else (what a wide
    # iteration's temporaries spill is on the compiler's own stack).
    keys = la.keys_per_iteration(g["heads"], g["bs"])
    assert keys == la.MAX_KEYS_PER_ITERATION
    buffers = 2 * keys * g["width"] * 2
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(la, "VMEM_LIMIT_BYTES", 64 * 2**10)
        jax.clear_caches()
        with pytest.raises(Exception, match="scoped vmem limit") as refused:
            jax.jit(functools.partial(la.latent_decode_attention,
                                      **attend)).lower(
                S((g["slots"], g["heads"], g["width"]), jnp.bfloat16),
                pool, S((g["slots"], g["table"]), jnp.int32),
                S((g["slots"],), jnp.int32)).compile()
    need = _scoped_vmem_asked(refused)
    assert buffers <= need * 1.01 and need <= buffers + 256 * 2**10
    # A prefill tile at the width keys_per_iteration gives its rows:
    # inside VMEM_LIMIT_BYTES, and refused under Mosaic's default 16 MiB
    # with the compiler naming what it asks for: its state (the f32
    # accumulator and the lane-replicated max and sum), the two key
    # buffers, the slice's rows and the query and result tiles twice
    # (the grid's pipeline), then the temporaries of one iteration
    # (scores, weights and their bf16 form).
    widths = {}
    for tokens in (256, 128, 64, 32, 16):
        rows = min(tokens, la.PREFILL_Q_TILE) * g["heads"]
        keys = widths[tokens] = la.keys_per_iteration(rows, g["bs"])

        def compiled():
            return jax.jit(functools.partial(
                la.latent_prefill_attention, **attend)).lower(
                S((tokens, g["heads"], g["width"]), jnp.bfloat16),
                S((tokens, g["width"]), jnp.bfloat16), pool,
                S((g["table"],), jnp.int32), S((), jnp.int32)).compile()

        assert len(_custom_call_lines(compiled().as_text(),
                                      "latent_prefill_call")) == 1
        held = (rows * g["rank"] * 4 + 2 * rows * la.LANES * 4
                + 2 * keys * g["width"] * 2 + tokens * g["width"] * 2
                + 2 * rows * (g["width"] + g["rank"]) * 2)
        temporaries = rows * keys * la.ITERATION_BYTES_PER_SCORE
        assert temporaries <= la.ITERATION_VMEM_BYTES
        assert held + temporaries <= la.VMEM_LIMIT_BYTES
        if held + temporaries <= 16 * 2**20:
            continue
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(la, "VMEM_LIMIT_BYTES", 16 * 2**20)
            jax.clear_caches()
            with pytest.raises(Exception,
                               match="scoped vmem limit") as refused:
                compiled()
        jax.clear_caches()
        need = _scoped_vmem_asked(refused)
        assert held <= need * 1.01 and need <= la.VMEM_LIMIT_BYTES
    assert widths == {256: 512, 128: 512, 64: 512, 32: 1024, 16: 1024}

    def steps(pool, rows, blocks, offsets, whole, whole_ids):
        def body(pool, _):
            return la.latent_append(pool, rows, blocks, offsets), None
        pool = la.latent_append(pool, whole, whole_ids)
        return jax.lax.scan(body, pool, None, length=2)[0]

    compiled = jax.jit(steps, donate_argnums=(0,)).lower(
        pool, S((g["slots"], g["width"]), jnp.bfloat16),
        S((g["slots"],), jnp.int32), S((g["slots"],), jnp.int32),
        S((16, g["bs"], g["width"]), jnp.bfloat16),
        S((16,), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(_custom_call_lines(text, "latent_append")) == 2
    assert not _pool_shaped_ops(text, g["n_blocks"], 3)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_latent_decode_reads_rows_and_expands_nothing(one_chip, as_on_tpu):
    """The decode and the mixed program of the latent module at the
    published widths (two layers, four held experts): one scan each
    (the mixed program's over the steps behind its first, which
    carries the slice); in its body a ``latent_append`` and a
    ``closed_call`` a layer and no copy of the pool; and nowhere an
    array with the context on an axis — no expanded key or value of a
    slot's table (16,896 positions) exists, per head or otherwise."""
    from aiko_services_tpu.models import mistral4
    g = LATENT_CELL
    config = mistral4.Mistral4Config(
        vocab_size=4096, d_model=4096, n_layers=2, n_heads=32,
        q_lora_rank=1024, kv_lora_rank=256, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=128, n_experts=128, moe_top_k=4,
        d_ff=2048, d_shared=2048, experts_held=(0, 4), rope_factor=128.0,
        rope_original_max=8192, max_seq_len=1048576)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = _shaped(jax.eval_shape(lambda: mistral4.quantize_params(
        mistral4.init_params(config, jax.random.PRNGKey(0)))), one_chip)
    pool = _shaped(jax.eval_shape(lambda: mistral4.init_paged_cache(
        config, 4097, g["bs"])), one_chip)
    slots, table = g["slots"], g["table"]
    state = {"token": S((slots, 1), jnp.int32),
             "positions": S((slots,), jnp.int32),
             "active": S((slots,), jnp.bool_),
             "remaining": S((slots,), jnp.int32),
             "temps": S((slots,), jnp.float32),
             "tops": S((slots,), jnp.float32),
             "adapter_ids": S((slots,), jnp.int32),
             "tables": S((slots, table), jnp.int32)}
    scalar = S((), jnp.int32)
    decode = mistral4.serve_chunk_paged.lower(
        params, state, pool, 2, config).compile()
    mixed, cell_mixed = (mistral4._mixed_program.lower(
        params, state, pool, S((1, 256), jnp.int32), scalar, scalar, steps,
        config, -1, False, None).compile() for steps in (3, 2))
    for compiled in (decode, mixed):
        text = compiled.as_text()
        assert text.count(" while(") == 1
        body = "\n".join(_scan_body(text))
        assert len(_custom_call_lines(body, "closed_call")) == 2
        assert len(_custom_call_lines(body, "latent_append")) == 2
        assert not _pool_shaped_ops(text, 4097, 3)
        context = table * g["bs"]
        assert not re.search(r"\[(\d+,)*(%d|%d,%d)(,\d+)*\]"
                             % (context, table, g["bs"]), text)
        # What a step holds beside weights and pool: megabytes.
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    # The cell's own mixed program (``chunk_steps`` 2): the scan behind
    # the first step has one trip and XLA inlines it, so the program is
    # two steps in line.  A slice's logits are not computed (the
    # prompt's last token is the first decode step's), so its LAST
    # layer appends its rows and attends nothing: the prefill kernel
    # runs in the layers before it.  The held experts' matmuls see the
    # slice's 256 rows only together with the first step's 64: three
    # fusions a merged layer, and the slots' rows alone in that step's
    # last layer and in the second step.
    # Outside a scan: (decode kernels, appends, layers of 64 rows).
    for compiled, inline in ((mixed, (2, 4, 1)), (cell_mixed, (4, 6, 3))):
        text = compiled.as_text()
        assert len(_custom_call_lines(text, "latent_prefill_call")) == 1
        entry = text[text.index("\nENTRY "):]
        wide = {rows: len(re.findall(
            r" = (?:f32|bf16)\[%d,4,2048\]\S* fusion\(" % rows, entry))
            for rows in (256, 320, 64)}
        assert wide[256] == 0 and wide[320] == 2, wide
        assert (len(_custom_call_lines(entry, "closed_call")),
                len(_custom_call_lines(entry, "latent_append"))) \
            == inline[:2] and wide[64] >= inline[2], wide
    assert cell_mixed.as_text().count(" while(") == 0


# --------------------------------------------------------------------------- #
# The block-pass programs at sdar30b.fixedlen's sizes


def test_block_pass_programs_compile_for_v5e(one_chip, as_on_tpu):
    """``models/sdar.py`` at the cell's widths (2 of its 12 layers): the
    chunk of passes is ONE ``while`` whose body writes a block's rows
    by scatter and attends with one decode-kernel call a layer (a 3-D
    bfloat16 result, ``B x heads`` query rows a slot: what
    ``decode_attn_roofline`` finds), the slices run the append kernel
    under the block-causal mask, and nothing copies or transposes a
    K/V pool."""
    from benchmark.builders import sdar_moe as builder
    from aiko_services_tpu.models import sdar
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1]
                      / "benchmark/configs/"
                      "sdar-30b-a3b-chat-l12e32.json").read_text())
    cfg = dict(cfg, num_hidden_layers=2)
    config = builder.program_config("sdar_compile_test", cfg)
    slots, n_blocks, table = 64, 1025, 129
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = _shaped(jax.eval_shape(
        lambda: builder.build_params(cfg, 1)), one_chip)
    pool = _shaped(jax.eval_shape(
        lambda: sdar.init_paged_cache(config, n_blocks, 16)), one_chip)
    state = {"token": S((slots, 1), jnp.int32),
             "positions": S((slots,), jnp.int32),
             "active": S((slots,), jnp.bool_),
             "remaining": S((slots,), jnp.int32),
             "temps": S((slots,), jnp.float32),
             "tops": S((slots,), jnp.float32),
             "adapter_ids": S((slots,), jnp.int32),
             "tables": S((slots, table), jnp.int32)}
    state.update({name: S(leaf.shape, leaf.dtype) for name, leaf
                  in sdar.block_slot_state(config, slots).items()})
    tokens, scalar = S((1, 256), jnp.int32), S((), jnp.int32)
    chunk = sdar.serve_chunk_paged.lower(
        params, state, pool, 3, config).compile().as_text()
    mixed = sdar._mixed_program.lower(
        params, state, pool, tokens, scalar, scalar, 3, config, -1,
        False, None, 64).compile().as_text()
    for text in (chunk, mixed):
        assert text.count(" while(") == 1
        assert not _pool_shaped_ops(text, n_blocks, 4)
        calls = _custom_call_lines("\n".join(_scan_body(text)),
                                   "closed_call")
        assert len(calls) == config.n_layers
        for line in calls:
            assert re.search(r"= bf16\[64,128,128\]", line)
    assert "%paged_prefill_call" in mixed
    assert "%paged_prefill_call" not in chunk


# --------------------------------------------------------------------------- #
# Two kinds of row in one pool, at evabyte.files' sizes


def test_two_kinds_of_row_programs_compile_for_v5e(one_chip, as_on_tpu):
    """``models/evabyte.py`` at the cell's widths (2 of its 32 layers,
    32 int8 kv heads, tables of 184, a pool of 1,536 blocks): the chunk
    of steps is ONE ``while`` whose body appends twice a layer (the
    step's row, the chunk's summary) and attends with one decode-kernel
    call a layer over the composed table (a 3-D bfloat16 result: what
    ``decode_attn_roofline`` finds); the slices run the append kernels
    at the composed position; nothing copies or transposes a K/V pool,
    and the scale planes are re-laid-out at the scan's entry and exit
    alone, as :mod:`llama`'s are."""
    from benchmark.builders import evabyte as builder
    from aiko_services_tpu.models import evabyte
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1]
                      / "benchmark/configs/evabyte-6.5b.json").read_text())
    cfg = dict(cfg, num_hidden_layers=2)
    config = builder.program_config("evabyte_compile_test", cfg)
    layers, slots, n_blocks = config.n_layers, 8, 1537
    table = evabyte.table_blocks(config, 12800, 16)
    assert table == 184
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = _shaped(jax.eval_shape(
        lambda: builder.build_params(cfg, 1)), one_chip)
    pool = _shaped(jax.eval_shape(
        lambda: evabyte.init_paged_cache(config, n_blocks, 16,
                                         quantize_kv=True)), one_chip)
    state = {"token": S((slots, 1), jnp.int32),
             "positions": S((slots,), jnp.int32),
             "active": S((slots,), jnp.bool_),
             "remaining": S((slots,), jnp.int32),
             "temps": S((slots,), jnp.float32),
             "tops": S((slots,), jnp.float32),
             "adapter_ids": S((slots,), jnp.int32),
             "tables": S((slots, table), jnp.int32)}
    tokens, scalar = S((1, 256), jnp.int32), S((), jnp.int32)
    chunk = evabyte.serve_chunk_paged.lower(
        params, state, pool, 8, config).compile().as_text()
    mixed = evabyte._mixed_program.lower(
        params, state, pool, tokens, scalar, scalar, 8, config, -1,
        False, None).compile().as_text()
    standalone = evabyte._prefill_program.lower(
        params, tokens, pool, S((1, table), jnp.int32), scalar, config,
        False).compile().as_text()
    assert standalone.count(" while(") == 0
    for text in (mixed, standalone):
        assert "%paged_prefill_call" in text       # the kernel path ran
    for text in (chunk, mixed, standalone):
        assert not _pool_shaped_ops(text, n_blocks, 4)
    for text, budget in ((chunk, 4 * layers), (mixed, 8 * layers)):
        assert text.count(" while(") == 1
        assert (len(_pool_shaped_ops(text, n_blocks, 3))
                + len(_pool_shaped_ops(text, n_blocks, 2))) <= budget
        body = "\n".join(_scan_body(text))
        assert len(_custom_call_lines(body, "paged_decode_append")) \
            == 2 * layers
        calls = _custom_call_lines(body, "closed_call")
        assert len(calls) == layers
        for line in calls:
            assert re.search(r"= bf16\[8,32,128\]", line)
        # The chunk's summary: one kernel call a layer, named as
        # ``eva_summarise_roofline`` looks for it, reading the pools
        # where they are.
        assert len(_custom_call_lines(body, "eva_summarise")) == layers
    # The decode chunk parks no pool on chip (the mixed program's slice
    # half still does: PERF.md section 7).
    assert not re.search(r"= \(s8\[1537,16,32,128\][^=]*copy-start\(",
                         chunk)

