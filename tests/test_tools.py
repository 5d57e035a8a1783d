"""Tools: media converters, dashboard plugin frames, video elements."""

import numpy as np
import pytest

from aiko_services_tpu.runtime.service import ServiceFields
from aiko_services_tpu.tools.convert import images_to_video, video_to_images
from aiko_services_tpu.tools.dashboard_plugins import find_plugin


def fields(name="svc", protocol="…/pipeline:0"):
    return ServiceFields(topic_path="test/h/1/1", name=name,
                         protocol=protocol, transport="loopback",
                         owner="t", tags=[])


def test_images_to_video_roundtrip(tmp_path):
    cv2 = pytest.importorskip("cv2")
    rng = np.random.default_rng(0)
    for i in range(5):
        image = rng.integers(0, 255, (32, 48, 3), dtype=np.uint8)
        cv2.imwrite(str(tmp_path / f"img_{i:03d}.png"), image)
    video = str(tmp_path / "out.mp4")
    assert images_to_video(str(tmp_path / "img_*.png"), video) == 5
    out_dir = str(tmp_path / "frames")
    assert video_to_images(video, out_dir) == 5


def test_converters_missing_inputs(tmp_path):
    pytest.importorskip("cv2")
    with pytest.raises(FileNotFoundError):
        images_to_video(str(tmp_path / "none_*.png"),
                        str(tmp_path / "x.mp4"))
    with pytest.raises(FileNotFoundError):
        video_to_images(str(tmp_path / "missing.mp4"), str(tmp_path))


def test_dashboard_plugin_matching():
    plugin = find_plugin(fields(protocol="aiko/pipeline:0"))
    assert plugin is not None
    lines = plugin(fields(), {"lifecycle": "ready", "streams": 2,
                              "elements": {"PE_0": "ready"}})
    text = "\n".join(lines)
    assert "ready" in text and "PE_0" in text
    assert find_plugin(fields(protocol="aiko/registrar:2")) is not None
    assert find_plugin(fields(protocol="aiko/other:0")) is None


def test_dashboard_plugin_name_beats_protocol():
    from aiko_services_tpu.tools.dashboard_plugins import dashboard_plugin

    @dashboard_plugin(name="special")
    def special_plugin(fields_, variables):
        return ["special"]

    assert find_plugin(
        fields(name="special", protocol="aiko/pipeline:0")
    ) is special_plugin


def test_video_show_headless(tmp_path):
    """VideoShow must not raise on headless hosts."""
    from aiko_services_tpu.elements import VideoShow
    from aiko_services_tpu.pipeline.stream import Stream, StreamEvent
    from aiko_services_tpu.runtime.context import pipeline_element_args

    from aiko_services_tpu.runtime import compose_instance
    show = compose_instance(
        VideoShow, pipeline_element_args("VideoShow"))
    stream = Stream(stream_id="s")
    image = np.zeros((8, 8, 3), np.uint8)
    event, outputs = show.process_frame(stream, images=[image])
    assert event == StreamEvent.OKAY
    assert outputs["images"][0] is image


def _dashboard_env(engine, broker):
    """A registrar + a live actor + a DashboardState over loopback."""
    from aiko_services_tpu.registry import Registrar
    from aiko_services_tpu.runtime import (
        Process, actor_args, compose_instance,
    )
    from aiko_services_tpu.runtime.actor import Actor
    from aiko_services_tpu.tools.dashboard import DashboardState

    reg_process = Process(namespace="dash", hostname="h", pid="1",
                          engine=engine, broker=broker)
    Registrar(process=reg_process)
    engine.advance(4.0)
    actor_process = Process(namespace="dash", hostname="h", pid="2",
                            engine=engine, broker=broker)
    actor = compose_instance(Actor, actor_args("victim"),
                             process=actor_process)
    dash_process = Process(namespace="dash", hostname="h", pid="3",
                           engine=engine, broker=broker)
    state = DashboardState(dash_process)
    engine.drain()
    return state, actor


def test_dashboard_kill_service_control(engine):
    """Operator kill: the dashboard publishes (terminate) and the
    selected service stops and is evicted (reference
    dashboard.py:565-648)."""
    state, actor = _dashboard_env(engine, "dashkill")
    names = [f.name for f in state.services()]
    assert "victim" in names
    state.select(names.index("victim"))
    target = state.kill_selected()
    assert target == actor.topic_path
    engine.drain()
    engine.advance(1.0)
    assert "victim" not in [f.name for f in state.services()]


def test_dashboard_set_log_level_control(engine):
    """Operator log level: (log_level DEBUG) round-trips into the
    service's logger and EC share."""
    import logging
    state, actor = _dashboard_env(engine, "dashlog")
    names = [f.name for f in state.services()]
    state.select(names.index("victim"))
    assert state.set_log_level("debug") == actor.topic_path
    engine.drain()
    assert actor.share["log_level"] == "DEBUG"
    assert actor.logger.level == logging.DEBUG


def test_dashboard_plugin_action_runs(engine):
    """Plugin-frame actions: the pipeline plugin's stop action reaches
    the pipeline over the wire and destroys its streams."""
    from aiko_services_tpu.pipeline import (
        Pipeline, parse_pipeline_definition,
    )
    from aiko_services_tpu.runtime import (
        Process, compose_instance, pipeline_args,
    )
    from aiko_services_tpu.registry import Registrar
    from aiko_services_tpu.tools.dashboard import DashboardState

    broker = "dashact"
    reg_process = Process(namespace="dash", hostname="h", pid="1",
                          engine=engine, broker=broker)
    Registrar(process=reg_process)
    engine.advance(4.0)
    pipe_process = Process(namespace="dash", hostname="h", pid="2",
                           engine=engine, broker=broker)
    doc = {
        "version": 0, "name": "p_dash", "runtime": "python",
        "graph": ["(PE_Emit)"],
        "elements": [{
            "name": "PE_Emit",
            "input": [{"name": "i", "type": "int"}],
            "output": [{"name": "i", "type": "int"}],
            "parameters": {},
            "deploy": {"local": {"module": "tests.pipeline_elements",
                                 "class_name": "PE_Emit"}},
        }],
    }
    pipeline = compose_instance(
        Pipeline,
        pipeline_args("p_dash", definition=parse_pipeline_definition(doc)),
        process=pipe_process)
    pipeline.create_stream("s1", grace_time=0)
    dash_process = Process(namespace="dash", hostname="h", pid="3",
                           engine=engine, broker=broker)
    state = DashboardState(dash_process)
    engine.drain()

    names = [f.name for f in state.services()]
    state.select(names.index("p_dash"))
    state.open_variables()
    actions = state.plugin_actions()
    assert "s" in actions
    assert pipeline.streams
    assert state.run_plugin_action("s") is True
    engine.drain()
    assert not pipeline.streams


def test_profiler_actor_commands(engine, tmp_path):
    """profile_start/stop drive jax.profiler and surface the trace dir
    in the share; double-start and stop-without-start are safe."""
    import os
    from aiko_services_tpu.tools import ProfilerActor
    from aiko_services_tpu.runtime import (
        Process, actor_args, compose_instance,
    )
    from aiko_services_tpu.utils.sexpr import generate

    process = Process(namespace="test", hostname="h", pid="77",
                      engine=engine, broker="prof")
    actor = compose_instance(ProfilerActor, actor_args("prof0"),
                             process=process)
    trace_dir = str(tmp_path / "trace")
    process.message.publish(actor.topic_in,
                            generate("profile_start", [trace_dir]))
    engine.advance(0.1)
    assert actor.share["profiling"] is True
    # Double start: warns, stays on the first capture.
    process.message.publish(actor.topic_in,
                            generate("profile_start", ["/tmp/other"]))
    engine.advance(0.1)
    assert actor._trace_dir == trace_dir
    process.message.publish(actor.topic_in, generate("profile_stop"))
    engine.advance(0.1)
    assert actor.share["profiling"] is False
    assert actor.share["last_trace_dir"] == trace_dir
    assert os.path.isdir(trace_dir)
    # Trace content written (plugins/profile/... on CPU backends too).
    found = any(files for _, _, files in os.walk(trace_dir))
    assert found, "no trace files captured"
    # Stop without start: safe no-op.
    process.message.publish(actor.topic_in, generate("profile_stop"))
    engine.advance(0.1)


def test_profiler_status_and_reset_commands(engine, tmp_path):
    """(profile_status) echoes running/idle + the trace dir on
    topic_out; (profile_reset) force-clears an orphaned session and is
    safe to fire when nothing is running."""
    from aiko_services_tpu.tools import ProfilerActor
    from aiko_services_tpu.runtime import (
        Process, actor_args, compose_instance,
    )
    from aiko_services_tpu.utils.sexpr import generate, parse

    process = Process(namespace="test", hostname="h", pid="78",
                      engine=engine, broker="profstat")
    actor = compose_instance(ProfilerActor, actor_args("prof1"),
                             process=process)
    statuses = []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "profile_status":
            statuses.append(params)

    process.add_message_handler(handler, actor.topic_out)

    process.message.publish(actor.topic_in, generate("profile_status"))
    engine.advance(0.1)
    assert statuses == [["idle", ""]]

    trace_dir = str(tmp_path / "trace")
    process.message.publish(actor.topic_in,
                            generate("profile_start", [trace_dir]))
    engine.advance(0.1)
    process.message.publish(actor.topic_in, generate("profile_status"))
    engine.advance(0.1)
    assert statuses[1] == ["running", trace_dir]

    # Reset while a capture is live: the process-global session is
    # force-stopped and the actor's state clears — the next start
    # owns a fresh session instead of warning "already running".
    process.message.publish(actor.topic_in, generate("profile_reset"))
    engine.advance(0.1)
    assert actor._trace_dir is None
    assert actor.share["profiling"] is False
    process.message.publish(actor.topic_in, generate("profile_status"))
    engine.advance(0.1)
    assert statuses[2][0] == "idle"

    # Reset with nothing running: safe no-op (stop_trace raises
    # internally and is swallowed).
    process.message.publish(actor.topic_in, generate("profile_reset"))
    engine.advance(0.1)
    assert actor.share["profiling"] is False

    # After the reset the profiler is usable again end to end.
    redo_dir = str(tmp_path / "trace2")
    process.message.publish(actor.topic_in,
                            generate("profile_start", [redo_dir]))
    engine.advance(0.1)
    assert actor.share["profiling"] is True
    process.message.publish(actor.topic_in, generate("profile_stop"))
    engine.advance(0.1)
    assert actor.share["last_trace_dir"] == redo_dir


def test_profiler_mixin_adopts_commands_on_any_actor(engine):
    """ProfilerMixin wires the four profile_* commands into an
    arbitrary Actor subclass via _init_profiler."""
    from aiko_services_tpu.tools.profiler import ProfilerMixin
    from aiko_services_tpu.runtime import (
        Actor, Process, actor_args, compose_instance,
    )

    class Worker(ProfilerMixin, Actor):
        def __init__(self, context, process=None):
            super().__init__(context, process)
            self._init_profiler()

    process = Process(namespace="test", hostname="h", pid="79",
                      engine=engine, broker="profmix")
    worker = compose_instance(Worker, actor_args("worker0"),
                              process=process)
    for command in ("profile_start", "profile_stop",
                    "profile_status", "profile_reset"):
        assert command in worker._command_handlers
    assert worker.share["profiling"] is False


def test_trainer_plugin_view_and_actions():
    from types import SimpleNamespace
    from aiko_services_tpu.tools.dashboard_plugins import (
        find_plugin, find_plugin_actions,
    )

    fields = SimpleNamespace(name="trainer0", protocol="trainer:0",
                             topic_path="ns/h/1/2")
    plugin = find_plugin(fields)
    assert plugin is not None
    lines = plugin(fields, {"state": "running", "step": 42,
                            "loss": 3.14, "tokens_per_sec": 1000})
    text = "\n".join(lines)
    assert "step:       42" in text and "loss:       3.14" in text
    actions = find_plugin_actions(fields)
    assert set(actions) == {"p", "r", "c"}

    published = []
    process = SimpleNamespace(message=SimpleNamespace(
        publish=lambda topic, payload: published.append((topic,
                                                         payload))))
    actions["p"][1](process, fields, {})
    assert published == [("ns/h/1/2/in", "(pause)")]


def test_model_replica_and_profiler_plugins():
    from types import SimpleNamespace
    from aiko_services_tpu.tools.dashboard_plugins import find_plugin

    fields = SimpleNamespace(name="rep0", protocol="model_replica:0",
                             topic_path="ns/h/1/0")
    plugin = find_plugin(fields)
    assert plugin is not None
    lines = plugin(fields, {"lifecycle": "ready", "requests_served": 7,
                            "slots": 4, "slots_active": 3,
                            "queue_depth": 2})
    text = "\n".join(lines)
    assert "served:    7" in text
    assert "slots:     3/4 active" in text
    assert "queued:    2" in text

    fields = SimpleNamespace(name="prof0", protocol="profiler:0",
                             topic_path="ns/h/1/1")
    plugin = find_plugin(fields)
    lines = plugin(fields, {"profiling": False,
                            "last_trace_dir": "/tmp/t",
                            "last_trace_seconds": 1.5})
    assert any("1.5s" in line for line in lines)


def test_ci_checks_static_runs_and_names_only_files_that_exist():
    """Nothing else runs ``scripts/ci_checks.sh``, so a file it names
    that a PR deleted would go unnoticed until someone's pre-commit
    hook failed.  ``--static`` is the no-jax subset (seconds)."""
    import pathlib
    import re
    import subprocess

    repo = pathlib.Path(__file__).resolve().parent.parent
    script = repo / "scripts" / "ci_checks.sh"
    named = set(re.findall(
        r"\b(?:scripts|tests|aiko_services_tpu|benchmark|examples)"
        r"/[\w/.-]*\w", script.read_text()))
    assert "scripts/obs_lint.py" in named and "tests/test_obs.py" in named
    assert [path for path in sorted(named)
            if not (repo / path).exists()] == []
    done = subprocess.run(["bash", str(script), "--static"], cwd=repo,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stdout + done.stderr
    assert "static checks OK" in done.stdout
