#!/usr/bin/env python3
"""The K/V decode kernel through the TPU compiler WITHOUT the chip:
compile one ``closed_call`` for a described v5e at a cell's geometry
with the compiler's bundle dump on, and count what it scheduled — total
bundles, bundles by loop depth, and its most frequent operations
(PERF.md section 6, PR 42; section 7, after PR 35 (c)).

    python scripts/decode_kernel_bundles.py --only evabyte.files \\
        --against DIR_OR_FILE

``--against`` (repeatable) is another copy of ``ops/paged_attention.py``
as ``scripts/decode_kernel_alone.py`` takes it.  One process a build:
the dump directory is the compiler's for the life of a process (and
with the dump on, this installation's compiler aborts once the
kernel's files are written).  A count ranks variants; it is not a
time and is never written as one.  A dump is some 300 MB: it goes to a temporary directory and is
removed."""
import argparse
import collections
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUNDLE = re.compile(r"\s*(?:0x[0-9a-f]+|\d+)\s+:\s*(>*)\s*\{(.*)\}")
OPCODE = re.compile(r"=\s*([a-z_0-9.]+)")


def _alone():
    """``scripts/decode_kernel_alone.py``: the cells' geometries and
    the loader of another copy of the kernel."""
    if os.path.join(ROOT, "scripts") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import decode_kernel_alone
    return decode_kernel_alone


def compile_one(name, path):
    """Child: lower and compile the kernel at ``name``'s geometry."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    alone = _alone()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    module = alone.load(path) if path else alone.pa
    slots, kv, group, dtype, window, table, blocks = alone.GEOMETRIES[name]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = shaped((blocks, alone.BLOCK, kv, alone.HEAD_DIM), dtype)
    scales = (shaped(pool.shape[:3], jnp.float32)
              if dtype == jnp.int8 else None)
    def decode_kernel_alone(q, k, v, tables, positions, ks, vs):
        # The dump names the kernel's files after this function.
        return module.closed_call.__wrapped__(
            q, k, v, tables, positions, ks, vs, window=window,
            sm_scale=alone.HEAD_DIM ** -0.5, interpret=False)

    jax.jit(decode_kernel_alone).lower(
        shaped((slots, kv, group, alone.HEAD_DIM), jnp.bfloat16), pool,
        pool, shaped((slots, table), jnp.int32),
        shaped((slots,), jnp.int32), scales, scales).compile()


def form_of(name, path):
    """The ``attend`` body the build takes at ``name``'s geometry (the
    parent stays off the TPU compiler: the children hold it)."""
    alone = _alone()
    return alone.form_of(alone.load(path) if path else alone.pa, name)


def summary(dump):
    """Counts from the kernel's final bundles in ``dump``."""
    kernel, = [path for path in glob.glob(
        dump + "/*decode_kernel_alone*-final_bundles.txt")
        if "schedule" not in path]
    depth = collections.Counter()
    ops = collections.Counter()
    for line in open(kernel):
        found = BUNDLE.match(line)
        if not found:
            continue
        depth[len(found.group(1))] += 1
        for instruction in found.group(2).split(";;"):
            opcode = OPCODE.search(instruction)
            if opcode:
                ops[opcode.group(1)] += 1
    return dict(
        bundles=sum(depth.values()),
        by_loop_depth={str(level): depth[level] for level in sorted(depth)},
        most_operations=dict(ops.most_common(12)))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--against", action="append", default=[],
                        metavar="PATH")
    parser.add_argument("--only", default="evabyte.files")
    parser.add_argument("--child", nargs=2, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        compile_one(args.child[0], args.child[1] or None)
        return
    for name in args.only.split(","):
        for build in args.against + [""]:
            with tempfile.TemporaryDirectory() as dump:
                env = dict(os.environ, JAX_PLATFORMS="cpu",
                           LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                           "--xla_jf_dump_llo_text=true")
                done = subprocess.run(
                    [sys.executable, __file__, "--child", name, build],
                    env=env, capture_output=True, text=True)
                # The compiler writes the kernel's bundles and then
                # aborts on a report template this installation lacks:
                # what counts is whether the bundles are there.
                if glob.glob(dump + "/*decode_kernel_alone*-final_bundles.txt"):
                    out = summary(dump)
                else:
                    out = dict(refused=done.stderr[-600:])
                print(json.dumps(dict(shape=name,
                                      build=build or "this tree",
                                      form=form_of(name, build), **out)),
                      flush=True)


if __name__ == "__main__":
    main()
