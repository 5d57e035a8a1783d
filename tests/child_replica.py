"""Child process for cross-OS-process SERVING tests.

Run as ``python -m tests.child_replica``: connects to the MQTT broker
named by AIKO_MQTT_HOST/AIKO_MQTT_PORT, optionally hosts the Registrar
(CHILD_REGISTRAR=1), composes a ModelReplica serving the tiny
Llama-architecture model, prints READY, and serves until killed — a
one-chip serving worker as LifeCycleManager/ProcessManager would spawn
it.

CHILD_CONTINUOUS=1 instead composes a streaming ContinuousReplica
(continuous-batching server, fixed seed so every child produces the
same greedy completion) for the failover tests.  AIKO_FAULTS is
honoured through the fault module's env bootstrap — the chaos test
hands one child a ``kill_replica`` schedule and expects the other to
finish its work."""

import os
import sys


def main():
    # A test child serves on the CPU whatever the machine holds (one
    # process per chip: the chip is not this child's to take); force
    # the CPU backend the way conftest does.
    import jax
    jax.config.update("jax_platforms", "cpu")

    from aiko_services_tpu.orchestration.serving import (
        ModelReplica, make_llama_infer,
    )
    from aiko_services_tpu.registry import Registrar
    from aiko_services_tpu.runtime import (
        Process, actor_args, compose_instance,
    )
    from aiko_services_tpu.runtime.event import EventEngine

    engine = EventEngine()
    process = Process(engine=engine, transport="mqtt")
    if os.environ.get("CHILD_REGISTRAR") == "1":
        Registrar(process=process)
    name = os.environ.get("CHILD_REPLICA_NAME", "replica")
    if os.environ.get("CHILD_CONTINUOUS") == "1":
        from aiko_services_tpu.orchestration.continuous import (
            ContinuousBatchingServer, ContinuousReplica,
        )
        server = ContinuousBatchingServer(
            config_name="tiny", slots=2, max_seq=64, chunk_steps=3,
            seed=0, max_queue=64, watchdog_s=10.0)
        compose_instance(ContinuousReplica, actor_args(name),
                         process=process, server=server)
    else:
        compose_instance(
            ModelReplica, actor_args(name), process=process,
            infer=make_llama_infer("tiny", max_new_tokens=4))
    print("READY", flush=True)
    engine.loop()


if __name__ == "__main__":
    sys.exit(main())
