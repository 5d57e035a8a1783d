"""DP replica serving: router discovery, round-robin, failover, and the
llama replica end-to-end (loopback broker, virtual clock)."""

import numpy as np

from aiko_services_tpu.orchestration.serving import (
    ModelReplica, ReplicaRouter, make_llama_infer,
    make_speculative_infer,
)
from aiko_services_tpu.pipeline.codec import decode_swag, encode_swag
from aiko_services_tpu.registry import Registrar
from aiko_services_tpu.runtime import (
    Process, actor_args, compose_instance,
)
from aiko_services_tpu.utils.sexpr import generate, parse


def make_process(engine, pid, broker="serve"):
    return Process(namespace="test", hostname="h", pid=str(pid),
                   engine=engine, broker=broker)


def collect_responses(process, topic, into):
    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "infer_response":
            into.append((params[0], decode_swag(params[1])))
    process.add_message_handler(handler, topic)


def test_short_integer_vectors_cross_the_wire_as_text():
    """A streamed partial's token ids take the ``v`` tag: same dtype,
    same values, one axis, through the S-expression and back; what is
    not a short integer vector keeps ``np.save``."""
    from aiko_services_tpu.pipeline import codec
    for array in (np.asarray([5, 131071, 0], np.int32),
                  np.asarray([], np.int32),
                  np.asarray([-7, 2**40], np.int64),
                  np.asarray([2**63 + 1], np.uint64),
                  np.arange(codec.VECTOR_VALUES, dtype=np.int32)):
        encoded = encode_swag({"tokens_out": array})
        assert encoded["tokens_out"].startswith(f"v:{array.dtype.name}:")
        _, params = parse(generate("infer_partial", ["r", encoded]))
        back = decode_swag(params[1])["tokens_out"]
        assert back.dtype == array.dtype and back.shape == array.shape
        assert back.tolist() == array.tolist()
    for array in (np.arange(codec.VECTOR_VALUES + 1, dtype=np.int32),
                  np.asarray([1.5], np.float32),
                  np.zeros((2, 2), np.int32)):
        text = codec.encode_value(array)
        assert text.startswith("n:")
        back = codec.decode_value(text)
        assert back.dtype == array.dtype and (back == array).all()


def test_round_robin_and_failover(engine):
    p0 = make_process(engine, 1)
    Registrar(process=p0)
    engine.advance(4.0)

    replica_procs, replicas = [], []
    for i in range(3):
        p = make_process(engine, 10 + i)
        replica = compose_instance(
            ModelReplica, actor_args(f"replica_{i}"), process=p,
            infer=lambda payload: {"doubled": payload["value"] * 2})
        replica_procs.append(p)
        replicas.append(replica)

    pr = make_process(engine, 99)
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=pr)
    engine.drain()
    assert router.share["replicas"] == 3

    responses = []
    response_topic = "test/h/99/client/response"
    collect_responses(pr, response_topic, responses)

    for i in range(9):
        pr.message.publish(
            f"{router.topic_path}/in",
            generate("infer", [f"req{i}", response_topic,
                               encode_swag({"value": np.int64(i)})]))
    engine.drain()
    assert len(responses) == 9
    assert sorted(int(v["doubled"]) for _, v in responses) == \
        [2 * i for i in range(9)]
    served = [r.share["requests_served"] for r in replicas]
    assert served == [3, 3, 3]        # perfect round-robin

    # Kill one replica process: LWT -> registrar eviction -> router prune.
    replica_procs[0].kill()
    engine.drain()
    assert router.share["replicas"] == 2

    responses.clear()
    for i in range(4):
        pr.message.publish(
            f"{router.topic_path}/in",
            generate("infer", [f"again{i}", response_topic,
                               encode_swag({"value": np.int64(i)})]))
    engine.drain()
    assert len(responses) == 4        # only live replicas were used


def test_router_reports_no_replicas(engine):
    p0 = make_process(engine, 1, broker="empty")
    Registrar(process=p0)
    engine.advance(4.0)
    pr = make_process(engine, 2, broker="empty")
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=pr)
    engine.drain()
    assert router.route("r1", "test/topic", {}) is False


def test_llama_replica_end_to_end(engine):
    p0 = make_process(engine, 1, broker="llm")
    Registrar(process=p0)
    engine.advance(4.0)

    p1 = make_process(engine, 2, broker="llm")
    compose_instance(ModelReplica, actor_args("llm_replica"), process=p1,
                     infer=make_llama_infer("tiny", max_new_tokens=4))
    pr = make_process(engine, 3, broker="llm")
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=pr)
    engine.drain()
    assert router.share["replicas"] == 1

    responses = []
    response_topic = "test/h/3/client/response"
    collect_responses(pr, response_topic, responses)
    prompt = np.arange(1, 9, dtype=np.int32)[None, :]
    pr.message.publish(
        f"{router.topic_path}/in",
        generate("infer", ["chat1", response_topic,
                           encode_swag({"tokens": prompt})]))
    engine.drain()
    assert len(responses) == 1
    request_id, outputs = responses[0]
    assert request_id == "chat1"
    tokens_out = np.asarray(outputs["tokens_out"])
    assert tokens_out.shape == (1, 12)
    assert (tokens_out[:, :8] == prompt).all()


def test_llama_infer_rejects_overlong_prompt():
    """A prompt >= max_seq_len must come back as a clean error payload,
    not an opaque trace error from a too-short cache (ADVICE r1)."""
    from aiko_services_tpu.models import llama
    infer = make_llama_infer("tiny", max_new_tokens=4)
    too_long = llama.CONFIGS["tiny"].max_seq_len
    out = infer({"tokens": np.zeros((1, too_long), np.int32)})
    assert "error" in out and "max_seq_len" in out["error"]


def test_moe_int8_replica_end_to_end(engine):
    """The EP/MoE model family composes with the serving stack: an
    int8-quantized moe_tiny replica serves a chat request through the
    router (VERDICT r1 #10)."""
    p0 = make_process(engine, 1, broker="moellm")
    Registrar(process=p0)
    engine.advance(4.0)

    p1 = make_process(engine, 2, broker="moellm")
    compose_instance(
        ModelReplica, actor_args("moe_replica"), process=p1,
        infer=make_llama_infer("moe_tiny", quantize=True,
                               max_new_tokens=4))
    pr = make_process(engine, 3, broker="moellm")
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=pr)
    engine.drain()
    assert router.share["replicas"] == 1

    responses = []
    response_topic = "test/h/3/client/response"
    collect_responses(pr, response_topic, responses)
    prompt = np.arange(1, 7, dtype=np.int32)[None, :]
    pr.message.publish(
        f"{router.topic_path}/in",
        generate("infer", ["moe1", response_topic,
                           encode_swag({"tokens": prompt})]))
    engine.drain()
    assert len(responses) == 1
    request_id, outputs = responses[0]
    assert request_id == "moe1"
    tokens_out = np.asarray(outputs["tokens_out"])
    assert tokens_out.shape == (1, 10)
    assert (tokens_out[:, :6] == prompt).all()


def test_load_generator_against_continuous_replica(engine):
    """Open-loop load through the wire protocol: all requests complete,
    latencies recorded, error payloads counted separately."""
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer, ContinuousReplica,
    )
    from aiko_services_tpu.tools import LoadGenerator

    process = make_process(engine, 31, broker="load")
    server = ContinuousBatchingServer(config_name="tiny", slots=4,
                                      max_seq=64, chunk_steps=4)
    replica = compose_instance(
        ContinuousReplica, actor_args("cb_load"), process=process,
        server=server)

    clock = engine._clock
    generator = LoadGenerator(
        process, target_topic=replica.topic_in,
        payload_fn=lambda i: {"tokens": np.arange(1, 6 + (i % 3),
                                                  dtype=np.int32),
                              "max_new_tokens": 4},
        rate_hz=100.0, clock=clock.now, sleep=engine.advance)
    report = generator.run(12, drain_timeout_s=60.0,
                           pump=engine.drain)
    assert report.completed == 12, report
    assert report.timeouts == 0 and report.errors == 0
    assert report.p50_ms >= 0.0 and len(report.latencies_ms) == 12

    # Error payload (missing tokens) counts as error, not timeout.
    bad = LoadGenerator(
        process, target_topic=replica.topic_in,
        payload_fn=lambda i: {"max_new_tokens": 4},
        rate_hz=100.0, clock=clock.now, sleep=engine.advance)
    bad_report = bad.run(2, drain_timeout_s=30.0, pump=engine.drain)
    assert bad_report.errors == 2 and bad_report.timeouts == 0


def test_speculative_replica_matches_plain_replica(engine):
    """A speculative replica and a plain greedy replica serve the SAME
    prompt over the wire and return IDENTICAL tokens (greedy
    speculative decoding is exact) — so a router can mix them freely.
    The speculative response also carries acceptance stats."""
    p0 = make_process(engine, 1, broker="spec")
    Registrar(process=p0)
    engine.advance(4.0)

    p1 = make_process(engine, 2, broker="spec")
    plain = compose_instance(
        ModelReplica, actor_args("plain"), process=p1,
        infer=make_llama_infer("tiny", max_new_tokens=10))
    p2 = make_process(engine, 3, broker="spec")
    spec = compose_instance(
        ModelReplica, actor_args("spec"), process=p2,
        infer=make_speculative_infer(
            target_config="tiny", draft_config="tiny",
            max_new_tokens=10, k=3, seed=0, draft_seed=7))

    pr = make_process(engine, 99, broker="spec")
    responses = []
    response_topic = "test/h/99/client/response"
    collect_responses(pr, response_topic, responses)
    prompt = np.asarray([5, 17, 200, 3, 9], np.int32)
    for name, replica in (("plain", plain), ("spec", spec)):
        pr.message.publish(
            f"{replica.topic_path}/in",
            generate("infer", [name, response_topic,
                               encode_swag({"tokens": prompt,
                                            "max_new_tokens":
                                            np.int64(10)})]))
    engine.drain()
    by_id = dict(responses)
    assert set(by_id) == {"plain", "spec"}
    np.testing.assert_array_equal(by_id["plain"]["tokens_out"],
                                  by_id["spec"]["tokens_out"])
    assert 0.0 <= float(by_id["spec"]["acceptance_rate"]) <= 1.0
    assert float(by_id["spec"]["tokens_per_target_pass"]) >= 1.0


def test_constrained_replica_grammatical_over_wire(engine):
    """A constrained replica serves requests whose outputs the grammar
    MUST accept — verified by replaying every returned sequence through
    the automaton, over the actual wire protocol."""
    from aiko_services_tpu.models.constrained import automaton_from_rules
    from aiko_services_tpu.orchestration.serving import (
        make_constrained_infer,
    )
    LP, RP = 1, 2
    automaton = automaton_from_rules(
        vocab=1024,
        rules={0: [((LP,), 1)], 1: [((3, 4, 5), 2)],
               2: [((6, 7, 8, 9), 4), ((RP,), 3)],
               4: [((RP,), 3)], 3: []},
        accepting=[3])

    p1 = make_process(engine, 2, broker="grammar")
    replica = compose_instance(
        ModelReplica, actor_args("grammar_replica"), process=p1,
        infer=make_constrained_infer("tiny", automaton=automaton,
                                     max_new_tokens=8,
                                     temperature=1.0))
    pr = make_process(engine, 3, broker="grammar")
    responses = []
    response_topic = "test/h/3/client/response"
    collect_responses(pr, response_topic, responses)
    prompt = np.asarray([[30, 40, 50, 60]], np.int32)
    pr.message.publish(
        f"{replica.topic_path}/in",
        generate("infer", ["g1", response_topic,
                           encode_swag({"tokens": prompt,
                                        "seed": np.int64(9)})]))
    engine.drain()
    assert len(responses) == 1
    _, outputs = responses[0]
    out = np.asarray(outputs["tokens_out"])[0].tolist()
    assert np.asarray(outputs["accepted"]).all()
    close = out.index(RP)
    assert automaton.accepts(out[:close + 1])
    assert all(t == 0 for t in out[close + 1:])
