#!/usr/bin/env bash
# Fast pre-merge checks: the static sweeps plus the observability
# tier-1 guards.  Cheap by construction (~a minute on CPU) — the full
# tier-1 run stays `python -m pytest tests/ -q -m 'not slow'`
# (ROADMAP.md); this script is what a pre-commit hook or a PR bot can
# afford to run on every push.
#
#   scripts/ci_checks.sh            # everything
#   scripts/ci_checks.sh --static   # AST sweeps only (no jax)
set -euo pipefail

cd "$(dirname "$0")/.."

echo "== obs_lint: switchboard guards + jit-dir purity =="
python scripts/obs_lint.py

if [[ "${1:-}" == "--static" ]]; then
    echo "ci_checks: static checks OK (skipped pytest guards)"
    exit 0
fi

echo "== tier-1 obs guards (jaxpr purity, ledger, flight, doctor) =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest -q \
    -m 'not slow' -p no:cacheprovider \
    tests/test_obs.py tests/test_compiles.py tests/test_flight.py \
    tests/test_pool_audit.py

echo "== 2-D mesh smoke: tp=2 x sp=2 prefill parity + zero steady compiles =="
# The invariant-19 gate on every push: a tp=2 x sp=2 replica on the
# virtual 8-device CPU mesh must emit BITWISE single-chip greedy
# tokens through the sp-window prefill path, with the whole shape
# ladder pre-warmed so the steady phase compiles NOTHING.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" python - <<'EOF2'
import numpy as np
from aiko_services_tpu.obs import compiles
from aiko_services_tpu.orchestration.continuous import DecodeRequest
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from aiko_services_tpu.parallel.mesh import ReplicaMesh


def serve(mesh):
    server = PagedContinuousServer(
        config_name="tiny_tp", slots=2, max_seq=256, chunk_steps=3,
        seed=5, block_size=16, chunk_prefill_tokens=32,
        quantize_kv=True, replica_mesh=mesh)
    rng = np.random.default_rng(9)
    for i, (plen, new) in enumerate(((150, 5), (40, 4))):
        server.submit(DecodeRequest(
            request_id=f"r{i}",
            prompt=rng.integers(1, 1024, plen).astype(np.int32),
            max_new_tokens=new))
    return server, {r.request_id: r.tokens
                    for r in server.run_until_drained()}


_, want = serve(None)
ledger = compiles.install(service="ci-mesh2d")
server = PagedContinuousServer(
    config_name="tiny_tp", slots=2, max_seq=256, chunk_steps=3,
    seed=5, block_size=16, chunk_prefill_tokens=32,
    quantize_kv=True, replica_mesh=ReplicaMesh(tp=2, sp=2))
assert server.warm_prefill_ladder() > 0
rng = np.random.default_rng(9)
requests = [(150, 5), (40, 4)]
for i, (plen, new) in enumerate(requests):
    server.submit(DecodeRequest(
        request_id=f"r{i}",
        prompt=rng.integers(1, 1024, plen).astype(np.int32),
        max_new_tokens=new))
got = {r.request_id: r.tokens for r in server.run_until_drained()}
assert got == want, "tp=2 x sp=2 diverged from single chip"
assert server.counters["sp_prefill_dispatches"] > 0,     "sp window never fired"
ledger.fence()
rng = np.random.default_rng(9)
for i, (plen, new) in enumerate(requests):
    server.submit(DecodeRequest(
        request_id=f"s{i}",
        prompt=rng.integers(1, 1024, plen).astype(np.int32),
        max_new_tokens=new))
server.run_until_drained()
assert ledger.steady_compiles == 0,     f"{ledger.steady_compiles} steady-state compiles on the 2-D mesh"
print("mesh2d smoke: parity OK, zero steady compiles")
EOF2

echo "== migration smoke: in-process live migrate + zero steady compiles post-cutover =="
# Drain-free live migration on every push: two mid-decode migrations
# through a 2-replica rig.  The first warms the whole migration path
# (prepare, KV export/import, resume admission, post-cutover decode);
# after the fence, the second must cut over EXACTLY (concatenated
# partials == final, no lost/duplicated tokens) while compiling
# NOTHING — the destination's first post-cutover step rides the
# warmed ladder.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF3'
import time
import uuid

import numpy as np

from aiko_services_tpu.obs import compiles
from aiko_services_tpu.orchestration.client import InferClient
from aiko_services_tpu.orchestration.continuous import ContinuousReplica
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from aiko_services_tpu.orchestration.serving import ReplicaRouter
from aiko_services_tpu.registry import Registrar
from aiko_services_tpu.runtime import (
    Process, actor_args, compose_instance,
)
from aiko_services_tpu.runtime.event import EventEngine


def wait(predicate, timeout_s, what):
    deadline = time.time() + timeout_s
    while not predicate():
        if time.time() > deadline:
            raise TimeoutError(what)
        time.sleep(0.01)


ledger = compiles.install(service="ci-migration")
engine = EventEngine()
thread = engine.run_in_thread()
broker = f"ci-mig-{uuid.uuid4().hex[:6]}"
processes = []


def make_process(pid):
    process = Process(namespace="cimig", hostname="h", pid=str(pid),
                      engine=engine, broker=broker)
    processes.append(process)
    return process


try:
    registrar = Registrar(process=make_process(1))
    wait(lambda: registrar.state == "primary", 10, "registrar")
    replicas = [
        compose_instance(
            ContinuousReplica, actor_args(f"replica_{i}"),
            process=make_process(2 + i),
            server=PagedContinuousServer(
                config_name="tiny", slots=4, chunk_steps=2, seed=0,
                enable_prefix_cache=True, max_queue=64),
            kv_fetch_timeout_s=2.0)
        for i in range(2)]
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=make_process(8),
                              kv_transfer=True)
    wait(lambda: router.share["replicas"] == 2, 30, "discovery")
    client = InferClient(make_process(9), f"{router.topic_path}/in")
    rng = np.random.default_rng(3)

    def migrated_request(tag):
        prompt = rng.integers(1, 1024, 18).astype(np.int32)
        future = client.submit(prompt, max_new_tokens=32, stream=True)
        wait(lambda: len(future.partial_tokens) >= 3 or future.done,
             120, f"{tag}: first tokens")
        assert not future.done, f"{tag}: finished before migrate"
        source = router._inflight[future.request_id]["replica"]
        dest = next(r.topic_path for r in replicas
                    if r.topic_path != source)
        router.process.message.publish(f"{router.topic_path}/in",
                                       f"(migrate {source} {dest})")
        client.wait(future, timeout=120.0)
        assert future.error is None, (tag, future.error)
        assert future.partial_tokens == future.tokens, tag
        return future

    # Warm both replicas' programs AND the whole migration path
    # (export, wire, import, resume admission, post-cutover decode).
    for replica in replicas:
        assert replica.server.warm_prefill_ladder() > 0
        warm_client = InferClient(replica.process, replica.topic_in)
        warm = warm_client.submit(
            rng.integers(1, 1024, 18).astype(np.int32),
            max_new_tokens=12)
        warm_client.wait(warm, timeout=120.0)
        assert warm.error is None, warm.error
    migrated_request("warmup-migration")
    assert router.counters["migrations_completed"] == 1, \
        dict(router.counters)

    ledger.fence()
    migrated_request("steady-migration")
    assert router.counters["migrations_completed"] == 2, \
        dict(router.counters)
    assert ledger.steady_compiles == 0, \
        f"{ledger.steady_compiles} steady-state compiles after cutover"
    print("migration smoke: 2 exact cutovers, zero steady compiles")
finally:
    for process in reversed(processes):
        try:
            process.terminate()
        except Exception:  # noqa: BLE001
            pass
    engine.terminate()
    thread.join(timeout=5)
EOF3

echo "== multi-tenant smoke: 2 replicas, 4 adapters, census exact, zero steady compiles across adapter swap =="
# The invariant-21 gate on every push: adapter factor pages live in
# the SAME audited pool as KV (census exact, zero audit violations,
# swept every step), a heterogeneous base+3-adapter batch decodes on
# each replica, one adapter warm-loads cross-replica from the other's
# pages, and an unload → warm-reload adapter swap compiles NOTHING in
# the steady phase while reproducing the pre-swap tokens exactly.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - <<'EOF4'
import numpy as np

from aiko_services_tpu.models.lora import LoRAConfig
from aiko_services_tpu.obs import compiles, metrics, pool_audit
from aiko_services_tpu.orchestration.continuous import DecodeRequest
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from aiko_services_tpu.tools.loadgen import _noisy_loadgen_adapter

auditor = pool_audit.install(service="ci-mtenant", sweep_every=1)
ledger = compiles.install(service="ci-mtenant")

lora_config = LoRAConfig(rank=4, alpha=8.0, targets=("wq", "wv"))
replica_a, replica_b = (
    PagedContinuousServer(config_name="tiny", slots=4, max_seq=64,
                          chunk_steps=2, seed=0, total_blocks=96,
                          enable_prefix_cache=True)
    for _ in range(2))
config = replica_a.config
# Home placement: evens cold-upload to A, odds to B — 4 tenants.
for tenant, server in ((0, replica_a), (2, replica_a),
                       (1, replica_b), (3, replica_b)):
    server.load_adapter(
        f"tenant-{tenant}",
        _noisy_loadgen_adapter(config, lora_config, 100 + tenant),
        lora_config)
# Cross-replica warm path: B pulls tenant-0's factor PAGES out of A's
# pool and warm-loads them — no client re-upload anywhere.
pages = replica_a.fetch_adapter_bytes("tenant-0")
assert pages is not None, "tenant-0 pages missing from A's pool"
replica_b.store_adapter_bytes("tenant-0", pages)
replica_b.load_adapter("tenant-0")
assert replica_b.adapter_warm_loads == 1, "warm load not counted"

rng = np.random.default_rng(7)
prompts = [rng.integers(1, 1024, 12).astype(np.int32)
           for _ in range(4)]


def heterogeneous_batch(server, tag, adapters):
    for index, adapter in enumerate(adapters):
        server.submit(DecodeRequest(
            request_id=f"{tag}{index}", prompt=prompts[index],
            max_new_tokens=6, adapter=adapter))
    finished = {r.request_id: r.tokens
                for r in server.run_until_drained()}
    assert len(finished) == len(adapters), (tag, sorted(finished))
    return finished


MIXED_B = (None, "tenant-0", "tenant-1", "tenant-3")
heterogeneous_batch(replica_a, "a", (None, "tenant-2"))
want = heterogeneous_batch(replica_b, "warm", MIXED_B)
# Warm the whole swap path: unload zeroes the stacked row, the warm
# reload re-stacks from the paged copy into the recycled id.
replica_b.unload_adapter("tenant-3")
replica_b.load_adapter("tenant-3")
heterogeneous_batch(replica_b, "warm2", MIXED_B)

ledger.fence()
replica_b.unload_adapter("tenant-3")
replica_b.load_adapter("tenant-3")
got = heterogeneous_batch(replica_b, "steady", MIXED_B)
assert {key.replace("steady", "warm"): tokens
        for key, tokens in got.items()} == want, \
    "adapter swap changed greedy tokens"
assert ledger.steady_compiles == 0, \
    f"{ledger.steady_compiles} steady-state compiles across the swap"

for server in (replica_a, replica_b):
    assert auditor.sweep(server) == [], "census reconciliation failed"
    census = server.pool_census()
    assert census["adapters"]["pages"].get("hbm", 0) > 0, \
        "adapter pages missing from census"
assert auditor.violations_total == 0
assert metrics.REGISTRY.snapshot()[
    "aiko_kv_audit_violations_total"] == 0
print("multi-tenant smoke: heterogeneous decode OK, census exact, "
      "zero steady compiles across adapter swap")
EOF4

echo "ci_checks: OK"
