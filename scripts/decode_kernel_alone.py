#!/usr/bin/env python3
"""The K/V decode kernel alone on the chip: one ``closed_call`` at the
shapes of the cells that run it (PERF.md section 6, PR 40): this
tree's kernel, with ``--widths`` at other widths, and with ``--against
PATH`` (repeatable) another copy of ``ops/paged_attention.py`` before
it: a checkout's root (``git archive <commit> | tar -x -C DIR``) or
the file itself.  ``PATH:{json}`` fills the copy's ``_LAB`` dict where
it has one (a builder's copy with diagnostic builds behind knobs; this
tree's kernel has none).

    chiprun -- python scripts/decode_kernel_alone.py --against DIR

A width is set for the sweep by the module's two constants, as no
argument sets it in the program.  Prints one JSON line a build: the
``attend`` body the build's ``decode_attend_form`` picks there, ms a
call (median and least of 8 timings of ONE jit of ``--calls`` calls,
each call with queries of its own: a Pallas call has no side effects,
so XLA merges calls on the same operands and a jit of identical calls
times one), microseconds per 128 live keys, the share of the 819 GB/s
the needed K/V bytes would take, and the largest gap to the first
build's result.  A reading carries the jit's dispatch and the slices
between calls: compare builds, do not read a call's time off one.
Needs the TPU; here on the CPU ``--interpret --tiny`` rehearses the
control flow."""
import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

from aiko_services_tpu.ops import paged_attention as pa       # noqa: E402

#: name -> (slots, kv heads, group, pool dtype, window, table width,
#: pool blocks): the cells' geometries (benchmark/configs, traffic).
GEOMETRIES = {
    "sdar30b.fixedlen": (64, 4, 32, jnp.bfloat16, None, 129, 8193),
    "mistral7b.chat": (32, 8, 4, jnp.int8, 4096, 160, 4609),
    "mistral7b.idle": (32, 8, 4, jnp.int8, 4096, 160, 4609),
    "mistral7b.full_batch": (32, 8, 4, jnp.int8, 4096, 160, 4609),
    "nemotron3super.reason": (64, 2, 16, jnp.bfloat16, None, 144, 9217),
    # one query row a kv head: the all-heads form of the kernel
    "evabyte.files": (8, 32, 1, jnp.int8, None, 184, 1537),
    "evabyte.full": (8, 32, 1, jnp.int8, None, 184, 1537),
}
BLOCK, HEAD_DIM, HBM_BYTES_PER_S = 16, 128, 819e9


def row_lengths(name, slots, rng):
    """Keys a slot holds, as the cell's traffic leaves them mid-run."""
    if name == "sdar30b.fixedlen":
        # prompt lognormal median 192 (32-1,024) plus a uniform part
        # of an answer of 256-1,024 in steps of 64
        prompt = np.clip(np.exp(rng.normal(np.log(192), 0.8, slots)),
                         32, 1024)
        answer = rng.integers(4, 17, slots) * 64
        return np.clip((prompt + rng.uniform(0, 1, slots) * answer
                        ).astype(np.int64), 33, 2047)
    if name == "mistral7b.chat":            # 2 live rows, 30 idle slots
        return np.concatenate([[600, 580], np.arange(30) % BLOCK])
    if name == "mistral7b.idle":            # every slot idle
        return np.arange(slots) % BLOCK
    if name == "mistral7b.full_batch":
        return np.full(slots, 1000)
    if name == "evabyte.files":
        # 6 live rows of 600-2,816 composed rows (a window's 2,048 and
        # the summaries behind it), 2 idle slots at scratch positions
        return np.concatenate([rng.integers(600, 2817, 6) - 1,
                               np.arange(6, 8) % BLOCK])
    if name == "evabyte.full":
        return np.full(slots, 2815)
    lengths = np.clip(rng.normal(490, 150, slots).astype(np.int64),
                      40, 2000)
    lengths[:3] = np.arange(3)              # three idle
    return lengths


def case(name, tiny):
    slots, kv, group, dtype, window, table, blocks = GEOMETRIES[name]
    rng = np.random.default_rng(40)
    positions = row_lengths(name, slots, rng).astype(np.int32)
    if tiny:
        blocks = slots * table // 2 + 1
    need = positions // BLOCK + 1
    ids = rng.permutation(np.arange(1, blocks))
    tables = np.zeros((slots, table), np.int32)
    at = 0
    for r in range(slots):
        if name.startswith(("mistral7b", "evabyte")) and positions[r] < BLOCK:
            continue                        # idle: scratch block 0
        tables[r, :need[r]] = ids[at:at + need[r]]
        at += need[r]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    shape = (blocks, BLOCK, kv, HEAD_DIM)
    q = jax.random.normal(keys[0], (slots, kv, group, HEAD_DIM),
                          jnp.bfloat16)
    if dtype == jnp.int8:
        k = jax.random.randint(keys[1], shape, -127, 128, jnp.int8)
        v = jax.random.randint(keys[2], shape, -127, 128, jnp.int8)
        ks = jax.random.uniform(keys[3], shape[:3], jnp.float32,
                                0.005, 0.02)
        vs = ks * 1.5
    else:
        k = jax.random.normal(keys[1], shape, dtype)
        v = jax.random.normal(keys[2], shape, dtype)
        ks = vs = None
    live = np.minimum(positions + 1, window or positions.max() + 1)
    per_key = 2 * kv * (HEAD_DIM * jnp.dtype(dtype).itemsize
                        + (4 if ks is not None else 0))
    return dict(operands=(q, k, v, jnp.asarray(tables),
                          jnp.asarray(positions), ks, vs),
                window=window, need_bytes=int(live.sum()) * per_key,
                groups=int(np.ceil(live / 128).sum()))


def load(path, loaded={}):
    """Another copy of ``ops/paged_attention.py`` (a checkout's root
    or the file), beside this tree's package (it imports nothing else
    of its own that moved)."""
    if os.path.isdir(path):
        path = os.path.join(path, "aiko_services_tpu/ops/paged_attention.py")
    if path not in loaded:
        spec = importlib.util.spec_from_file_location(
            f"aiko_services_tpu.ops.other_paged_attention_{len(loaded)}",
            path)
        loaded[path] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(loaded[path])
    return loaded[path]


def form_of(module, name):
    """The ``attend`` body ``module``'s kernel takes at ``name``'s
    geometry, by its own deciding function (a copy from before PR 43
    is not handed the pool's dtype; one from before PR 42 has one
    body)."""
    _, kv, group, dtype, *_ = GEOMETRIES[name]
    decide = getattr(module, "decode_attend_form", None)
    if decide is None:
        return "per_head"
    try:
        return decide(group, kv, BLOCK, dtype)
    except TypeError:
        return decide(group, kv, BLOCK)


def timed(call, operands, calls, reps):
    """(result of the first call, median s a call, least s a call)."""
    q, rest = operands[0], operands[1:]
    shift = (jnp.arange(calls, dtype=jnp.float32) / 32).astype(q.dtype)
    queries = q[None] + shift[:, None, None, None, None]

    @jax.jit
    def many(queries, *rest):
        total = 0.0
        for i in range(calls):
            out = call(queries[i], *rest)
            total = total + out[0, 0, 0, 0].astype(jnp.float32)
        return total
    jax.block_until_ready(many(queries, *rest))
    times = []
    for _ in range(reps):
        began = time.perf_counter()
        jax.block_until_ready(many(queries, *rest))
        times.append((time.perf_counter() - began) / calls)
    return (jax.jit(call)(q, *rest), float(np.median(times)),
            float(np.min(times)))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--against", action="append", default=[],
                        metavar="PATH[:JSON]")
    parser.add_argument("--widths", default="",
                        help="e.g. 128,256,512,1024")
    parser.add_argument("--only", default="")
    parser.add_argument("--calls", type=int, default=16)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--interpret", action="store_true")
    args = parser.parse_args()
    print("device", jax.devices()[0].device_kind, flush=True)
    builds = []
    for build in args.against:
        path, _, knobs = build.partition(":")
        builds.append((build, load(path), None, json.loads(knobs or "{}")))
    builds.append(("this tree", pa, None, {}))
    builds += [(f"this tree, {w} keys", pa, int(w), {})
               for w in args.widths.split(",") if w]
    shipped = (pa.MAX_DECODE_KEYS_PER_ITERATION,
               pa.DECODE_HEAD_TILES_PER_ITERATION)
    for name in GEOMETRIES:
        if args.only and name not in args.only.split(","):
            continue
        made = case(name, args.tiny)
        floor_ms = made["need_bytes"] / HBM_BYTES_PER_S * 1e3
        want = None
        for build, module, width, knobs in builds:
            if hasattr(module, "_LAB"):
                module._LAB.clear()
                module._LAB.update(knobs)
            if width is not None:
                pa.MAX_DECODE_KEYS_PER_ITERATION = width
                pa.DECODE_HEAD_TILES_PER_ITERATION = width   # unbound

            def call(q, k, v, tables, positions, ks, vs, module=module):
                return module.closed_call.__wrapped__(
                    q, k, v, tables, positions, ks, vs,
                    window=made["window"], sm_scale=HEAD_DIM ** -0.5,
                    interpret=args.interpret)
            try:
                out, seconds, least = timed(
                    call, made["operands"],
                    *((2, 1) if args.interpret else (args.calls, 8)))
            except Exception as error:       # a refused build is a finding
                print(json.dumps(dict(shape=name, build=build,
                                      form=form_of(module, name),
                                      refused=str(error)[:400])), flush=True)
                continue
            finally:
                (pa.MAX_DECODE_KEYS_PER_ITERATION,
                 pa.DECODE_HEAD_TILES_PER_ITERATION) = shipped
            out = np.asarray(out.astype(jnp.float32))
            want = out if want is None else want
            print(json.dumps(dict(
                shape=name, build=build, form=form_of(module, name),
                ms=round(seconds * 1e3, 4),
                least_ms=round(least * 1e3, 4),
                us_per_128_keys=round(seconds * 1e6 / made["groups"], 4),
                roofline_pct=round(100 * floor_ms / (seconds * 1e3), 2),
                gap_to_first=float(np.abs(out - want).max()))), flush=True)


if __name__ == "__main__":
    main()
