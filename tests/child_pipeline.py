"""Child process for cross-OS-process integration tests.

Run as ``python -m tests.child_pipeline [pipeline.json]``: connects to
the MQTT broker named by AIKO_MQTT_HOST/AIKO_MQTT_PORT, hosts the
Registrar (unless ``CHILD_REGISTRAR=0`` — a fleet needs only one
primary; extras become secondaries anyway) plus the callee pipeline —
the built-in ``p_remote`` (PE_Double) by default, or any pipeline
definition JSON given as argv[1] — prints READY, and serves until
killed.  This is the role a second machine plays in the reference's
multitude setup (reference examples/pipeline/multitude/run_large.sh
drives 10 such processes against mosquitto)."""

import os
import sys


def main():
    # A test child runs its jax-backed elements on the CPU whatever
    # the machine holds (one process per chip: the chip is not this
    # child's to take) — force CPU the way conftest does, before any
    # backend init.
    import jax
    jax.config.update("jax_platforms", "cpu")
    from aiko_services_tpu.pipeline import (
        Pipeline, load_pipeline_definition, parse_pipeline_definition,
    )
    from aiko_services_tpu.registry import Registrar
    from aiko_services_tpu.runtime import (
        Process, compose_instance, pipeline_args,
    )
    from aiko_services_tpu.runtime.event import EventEngine

    definition = {
        "version": 0, "name": "p_remote", "runtime": "python",
        "graph": ["(PE_Double)"],
        "elements": [{
            "name": "PE_Double",
            "input": [{"name": "i", "type": "int"}],
            "output": [{"name": "i", "type": "int"}],
            "parameters": {},
            "deploy": {"local": {"module": "tests.pipeline_elements",
                                 "class_name": "PE_Double"}},
        }],
    }
    if len(sys.argv) > 1:
        parsed = load_pipeline_definition(sys.argv[1])
    else:
        parsed = parse_pipeline_definition(definition)
    engine = EventEngine()
    process = Process(engine=engine, transport="mqtt")
    if os.environ.get("CHILD_REGISTRAR", "1") != "0":
        Registrar(process=process)
    compose_instance(
        Pipeline, pipeline_args(parsed.name, definition=parsed),
        process=process)
    print("READY", flush=True)
    engine.loop()


if __name__ == "__main__":
    sys.exit(main())
