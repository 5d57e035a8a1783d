"""Time to first token accounted for inside the scheduler (PR 24).

- **Stamps**: ``prefill_started_ts`` and ``decode_ready_ts`` join the
  four that existed; the six are ordered and the four parts they bound
  (``queue``, ``slice_wait``, ``prefill_run``, ``first_chunk``) sum to
  the request's time to first token, for whole-bucket and for chunked
  admission, on the request and over a counter delta.
- **Slice queue**: two long prompts admitted together wait for one
  another's slices; the slice, backlog and token counters equal the
  counts worked out by hand.
- **Span tree**: a traced request's ``prefill`` span has exactly the
  three children, which tile it; an untraced request builds none.
"""

import numpy as np
import pytest

from aiko_services_tpu.obs import trace
from aiko_services_tpu.orchestration import continuous
from aiko_services_tpu.orchestration.continuous import (
    TTFT_COUNTERS, TTFT_PARTS, ContinuousBatchingServer, DecodeRequest,
    ttft_parts,
)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer

@pytest.fixture(autouse=True)
def _no_tracer():
    yield
    trace.uninstall()


def _request(config, name, prompt_len, new=4, seed=0):
    rng = np.random.default_rng([seed, prompt_len])
    prompt = rng.integers(1, config.vocab_size, prompt_len)
    return DecodeRequest(request_id=name, prompt=prompt.astype(np.int32),
                         max_new_tokens=new)


def _contiguous(**overrides):
    kwargs = dict(config_name="tiny", slots=2, max_seq=128,
                  chunk_steps=2, seed=3)
    kwargs.update(overrides)
    return ContinuousBatchingServer(**kwargs)


def _paged(**overrides):
    kwargs = dict(config_name="tiny", slots=4, max_seq=256,
                  chunk_steps=4, seed=3, block_size=16, total_blocks=64,
                  chunk_prefill_tokens=32)
    kwargs.update(overrides)
    return PagedContinuousServer(**kwargs)


def _stamps(request):
    return (request.submitted_ts, request.activated_ts,
            request.prefill_started_ts, request.decode_ready_ts,
            request.first_token_ts, request.finished_ts)


SERVERS = {
    "contiguous whole-bucket": (_contiguous, {}, 40),
    "contiguous chunked": (_contiguous, {"chunk_prefill_tokens": 16}, 40),
    "paged whole-bucket": (_paged, {"chunk_prefill_tokens": 0}, 40),
    "paged chunked": (_paged, {}, 70),
}


@pytest.mark.parametrize("kind", sorted(SERVERS))
def test_stamps_are_ordered_and_the_parts_sum_to_ttft(kind):
    build, overrides, prompt_len = SERVERS[kind]
    server = build(**overrides)
    # A first request through the same path, so that the counter delta
    # below is taken on counters that already hold something.
    server.submit(_request(server.config, "warm", prompt_len, seed=1))
    server.run_until_drained()
    before = dict(server.counters)
    request = _request(server.config, "r", prompt_len)
    server.submit(request)
    finished = server.run_until_drained()
    assert [r.error for r in finished] == [None]

    stamps = _stamps(request)
    assert None not in stamps
    assert list(stamps) == sorted(stamps)
    parts = ttft_parts(request)
    assert tuple(parts) == TTFT_PARTS
    ttft = request.first_token_ts - request.submitted_ts
    assert sum(parts.values()) == pytest.approx(ttft, abs=1e-9)

    delta = {key: server.counters[key] - before[key]
             for key in TTFT_COUNTERS + ("ttft_ms", "first_tokens")}
    assert delta["first_tokens"] == 1
    assert delta["ttft_ms"] == pytest.approx(ttft * 1e3, rel=1e-6)
    assert sum(delta[key] for key in TTFT_COUNTERS) == \
        pytest.approx(delta["ttft_ms"], rel=1e-9)
    chunked = bool(overrides.get("chunk_prefill_tokens",
                                 build is _paged))
    assert (request.prefill_dispatches > 1) == chunked


def test_parts_are_empty_until_the_first_token():
    server = _contiguous()
    request = _request(server.config, "r", 20)
    server.submit(request)
    assert ttft_parts(request) == {}
    assert server.counters["first_tokens"] == 0
    server.run_until_drained()
    assert set(ttft_parts(request)) == set(TTFT_PARTS)


def test_two_long_prompts_queue_for_slices():
    """Head of line.  ``a`` (20 tokens, whole-bucket) decodes while
    ``b`` (100 tokens: bucket 128, four 32-token slices) and ``c`` (70
    tokens: bucket 128, three slices, the bucket's last slice holds
    padding alone and never runs) are admitted together: one slice
    rides each decode chunk, the oldest admission's first."""
    server = _paged()
    a = _request(server.config, "a", 20, new=48)
    b = _request(server.config, "b", 100, new=4)
    c = _request(server.config, "c", 70, new=4)
    server.submit(a)
    server.step()
    assert server.counters["prefill_backlog"] == 0
    before = dict(server.counters)
    server.submit(b)
    server.submit(c)
    finished = server.run_until_drained()
    assert all(r.error is None for r in finished)

    delta = {key: server.counters[key] - before[key]
             for key in ("prefill_slices", "prefill_slices_mixed",
                         "prefill_backlog", "prompt_tokens",
                         "prefill_tokens")}
    # a's 48 tokens outlast the seven slices: all of them ride chunks.
    assert delta["prefill_slices"] == 4 + 3
    assert delta["prefill_slices_mixed"] == 7
    assert delta["prompt_tokens"] == 100 + 70
    assert delta["prefill_tokens"] == 7 * 32
    # A dispatch counts the queue as its own slice leaves it: both
    # prompts at b's first three slices, c alone at b's last and at
    # its own first two, nothing at its last.
    assert delta["prefill_backlog"] == 2 + 2 + 2 + 1 + 1 + 1 + 0
    assert (b.prefill_dispatches, b.prefill_tokens) == (4, 128)
    assert (c.prefill_dispatches, c.prefill_tokens) == (3, 96)
    assert b.shared_tokens == c.shared_tokens == 0

    wait_b, wait_c = (ttft_parts(r)["slice_wait"] for r in (b, c))
    assert wait_c >= ttft_parts(b)["prefill_run"] > 0
    assert wait_c > wait_b
    # c's first slice went out with the chunk after b's last.
    assert c.prefill_started_ts >= b.decode_ready_ts


def test_the_prefix_cache_shortens_what_is_counted_as_prompt():
    server = _paged(enable_prefix_cache=True)
    first = _request(server.config, "first", 70)
    server.submit(first)
    server.run_until_drained()
    before = dict(server.counters)
    again = DecodeRequest(request_id="again", prompt=first.prompt.copy(),
                          max_new_tokens=4)
    server.submit(again)
    server.run_until_drained()
    assert again.shared_tokens == 64          # four full blocks of 16
    assert server.counters["prompt_tokens"] - before["prompt_tokens"] \
        == 70 - 64
    assert again.prefill_tokens == \
        server.counters["prefill_tokens"] - before["prefill_tokens"]


# ---------------------------------------------------------------- #
# The traced request's tree
# ---------------------------------------------------------------- #

def _served_over_the_wire(engine, broker, traced):
    from .test_infer_client import _pump, _rig
    if traced:
        trace.install(trace.Tracer(service="client", seed=4))
    engine, server, client = _rig(engine, broker, max_seq=128,
                                  chunk_prefill_tokens=16)
    prompt = np.arange(1, 41, dtype=np.int32)
    future = client.submit(prompt, max_new_tokens=5)
    assert _pump(engine, lambda: future.done)
    assert future.error is None
    return server, future


def test_prefill_span_has_the_three_children_that_tile_it(engine):
    server, future = _served_over_the_wire(engine, "ttft1", True)
    spans = {span.name: span for span in future.spans}
    prefill = spans["prefill"]
    children = [span for span in future.spans
                if span.parent_id == prefill.span_id]
    assert [span.name for span in children] == list(TTFT_PARTS[1:])
    assert children[0].start == pytest.approx(prefill.start, abs=1e-6)
    assert children[-1].end == pytest.approx(prefill.end, abs=1e-6)
    for before, after in zip(children, children[1:]):
        assert before.end == pytest.approx(after.start, abs=1e-6)
    request_id = spans["replica"].attrs["request_id"]
    for span in children:
        assert span.trace_id == prefill.trace_id
        assert span.attrs["request_id"] == request_id
    run = spans["prefill_run"].attrs
    # 40 tokens, bucket 64, 16-token slices: three reach token 40.
    assert (run["slices"], run["tokens_dispatched"],
            run["prompt_tokens"], run["shared_tokens"]) == (3, 48, 40, 0)
    # prefill and queue keep their names and extents.
    assert spans["queue"].end == pytest.approx(prefill.start, abs=1e-6)
    assert prefill.end == pytest.approx(spans["decode"].start, abs=1e-6)
    # The parts ride the response and the histograms like any phase.
    for part in TTFT_PARTS:
        assert float(np.asarray(future.outputs[f"{part}_ms"])) >= 0.0
        assert server.latency_hists[part].count == 1


def test_untraced_request_builds_no_span(engine, monkeypatch):
    """Untraced: ``_respond`` tests ``request.trace_ctx`` and that is
    all; no span of the tree is synthesised."""
    built = []
    monkeypatch.setattr(
        continuous.ContinuousReplica, "_request_spans",
        lambda self, request: built.append(request) or "[]")
    _, future = _served_over_the_wire(engine, "ttft0", False)
    assert built == []
    assert "trace_spans" not in future.outputs
