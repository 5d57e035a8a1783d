#!/usr/bin/env python
"""Multitude: the distributed pipeline load harness.

Reference parity: ``examples/pipeline/multitude/run_large.sh`` — N
chained pipelines, each hop crossing process boundaries, driven at a
target frame rate; the reference's note says ~50 Hz was the "maximum
frame rate before falling behind" for 10 chained pipelines.

Two modes:

* default — N simulated processes over the loopback broker (one OS
  process, N Process instances, shared event engine).  Measures the
  engine's in-process ceiling; NOT apples-to-apples with the
  reference's number.
* ``--cross-process`` — the honest comparison: the built-in MQTT broker
  plus N−1 real OS child processes (one pipeline each), every hop
  crossing a real TCP socket; the head counts ROUND-TRIP completions
  (frame travels the whole chain and the response chains back).

Run:  python examples/multitude/run_multitude.py [--pipelines 10]
      [--frames 500] [--cross-process]
"""

import os
import subprocess
import sys
import time

sys.path.insert(0, ".")

import click                                        # noqa: E402

from aiko_services_tpu.pipeline import (            # noqa: E402
    Pipeline, parse_pipeline_definition,
)
from aiko_services_tpu.registry import Registrar    # noqa: E402
from aiko_services_tpu.runtime import (             # noqa: E402
    Process, compose_instance, pipeline_args,
)
from aiko_services_tpu.runtime.event import EventEngine  # noqa: E402

MODULE = "tests.pipeline_elements"


def chain_definition(index: int, total: int):
    """Pipeline i: PE_Add -> (remote hop to pipeline i+1) or sink."""
    elements = [{
        "name": "PE_Add",
        "input": [{"name": "i", "type": "int"}],
        "output": [{"name": "i", "type": "int"}],
        "parameters": {"amount": 1},
        "deploy": {"local": {"module": MODULE, "class_name": "PE_Add"}},
    }]
    if index < total - 1:
        elements.append({
            "name": "PE_Next",
            "input": [{"name": "i", "type": "int"}],
            "output": [{"name": "i", "type": "int"}],
            "deploy": {"remote": {"service_filter":
                                  {"name": f"mt_{index + 1}"}}},
        })
        graph = ["(PE_Add PE_Next)"]
    else:
        graph = ["(PE_Add)"]
    return {"version": 0, "name": f"mt_{index}", "runtime": "python",
            "graph": graph, "elements": elements}


def make_chain_pipeline(index, total, process):
    definition = parse_pipeline_definition(chain_definition(index, total))
    return compose_instance(
        Pipeline, pipeline_args(f"mt_{index}", definition=definition),
        process=process)


def run_child(index: int, total: int):
    """Child mode: host pipeline mt_{index} over MQTT and serve."""
    engine = EventEngine()
    process = Process(engine=engine, transport="mqtt")
    make_chain_pipeline(index, total, process)
    print("READY", flush=True)
    engine.loop()


def run_cross_process(pipelines: int, frames: int):
    import queue
    from aiko_services_tpu.transport import MqttBroker

    broker = MqttBroker(port=0)
    namespace = f"mt{broker.port}"
    os.environ["AIKO_MQTT_HOST"] = broker.host
    os.environ["AIKO_MQTT_PORT"] = str(broker.port)
    # Children are pinned to the CPU: this is a control-plane demo, and
    # a chip belongs to one process at a time — N children must not
    # contend for it (nor take it from a parent that holds it).
    env = dict(os.environ, AIKO_NAMESPACE=namespace, JAX_PLATFORMS="cpu")

    children = []
    try:
        engine = EventEngine()
        process = Process(namespace=namespace, engine=engine,
                          transport="mqtt")
        Registrar(process=process)
        thread = engine.run_in_thread()

        script = os.path.abspath(__file__)
        for i in range(1, pipelines):
            child = subprocess.Popen(
                [sys.executable, script, "--child", str(i),
                 "--pipelines", str(pipelines)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True)
            children.append(child)
        for child in children:
            assert child.stdout.readline().strip() == "READY"

        head = make_chain_pipeline(0, pipelines, process)
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(p is not None for p in head.remote_proxies.values()):
                break
            time.sleep(0.05)
        else:
            raise RuntimeError("chain never fully discovered")

        out = queue.Queue()
        head.create_stream("load", queue_response=out,
                           grace_time=300.0)

        def pump(count):
            """Bounded in-flight round-trips through the whole chain."""
            posted = received = 0
            max_in_flight = 32
            while received < count:
                while posted < count and \
                        posted - received < max_in_flight:
                    head.post_frame("load", {"i": 0})
                    posted += 1
                out.get(timeout=60)
                received += 1

        warmup = min(50, frames // 5)
        pump(warmup)
        started = time.perf_counter()
        pump(frames)
        elapsed = time.perf_counter() - started
        rate = frames / elapsed
        print(f"multitude CROSS-PROCESS: {pipelines} chained pipelines "
              f"({pipelines} OS processes, built-in MQTT broker), "
              f"{frames} round-trip frames in {elapsed:.2f}s "
              f"= {rate:.0f} frames/sec sustained "
              f"(reference: ~50 Hz one-way, run_large.sh:7,20)")
        engine.terminate()
        thread.join(timeout=2)
        return rate
    finally:
        for child in children:
            child.terminate()
        for child in children:
            try:
                child.wait(timeout=5)
            except subprocess.TimeoutExpired:
                child.kill()
        broker.stop()


def run_loopback(pipelines: int, frames: int):
    engine = EventEngine()
    broker = "multitude"
    registrar_process = Process(namespace="mt", hostname="h", pid="0",
                                engine=engine, broker=broker)
    registrar = Registrar(process=registrar_process)
    thread = engine.run_in_thread()
    while registrar.state != "primary":
        time.sleep(0.05)

    chain = []
    for i in range(pipelines):
        process = Process(namespace="mt", hostname="h", pid=str(i + 1),
                          engine=engine, broker=broker)
        chain.append(make_chain_pipeline(i, pipelines, process))

    # Wait for every remote hop to resolve.
    deadline = time.time() + 15
    while time.time() < deadline:
        if all(all(p is not None for p in pipe.remote_proxies.values())
               for pipe in chain):
            break
        time.sleep(0.05)

    head = chain[0]
    head.create_stream("load")
    # Completion detection: count tail pipeline's processed frames
    # (streams auto-create down the chain on first frame).
    tail = chain[-1]
    start_count = tail._frames_processed

    warmup = min(50, frames // 5)
    for _ in range(warmup):
        head.post_frame("load", {"i": 0})
    while tail._frames_processed - start_count < warmup:
        time.sleep(0.01)

    start_count = tail._frames_processed
    started = time.perf_counter()
    for _ in range(frames):
        head.post_frame("load", {"i": 0})
    while tail._frames_processed - start_count < frames:
        time.sleep(0.01)
    elapsed = time.perf_counter() - started
    rate = frames / elapsed
    print(f"multitude IN-PROCESS (loopback broker; not apples-to-apples "
          f"with the reference): {pipelines} chained pipelines, "
          f"{frames} frames end-to-end in {elapsed:.2f}s "
          f"= {rate:.0f} frames/sec sustained "
          f"(reference: ~50 Hz cross-process, run_large.sh:7,20)")
    engine.terminate()
    thread.join(timeout=2)


@click.command()
@click.option("--pipelines", default=10)
@click.option("--frames", default=500)
@click.option("--cross-process", is_flag=True, default=False)
@click.option("--child", default=None, type=int, hidden=True)
def main(pipelines, frames, cross_process, child):
    if child is not None:
        run_child(child, pipelines)
    elif cross_process:
        run_cross_process(pipelines, frames)
    else:
        run_loopback(pipelines, frames)


if __name__ == "__main__":
    main()
