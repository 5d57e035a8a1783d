"""The block-diffusion configuration's benchmark files at a tiny size on
the CPU: the plain reference against the program's float32 forward and
against what the paged server serves (the prompt in slices, then block
passes through the cache, LOGITS compared pass by pass; static and
dynamic schedules, partial first blocks, slots out of phase, a reused
slot, answers that end inside a block), the chip's share of a layer's
experts against the uncut reference layer, the 4-bit and the bfloat16
controls, the chip-size configuration's arithmetic from its own keys,
the traffic mix, what ``BENCHMARK.json`` lists for the cell, and a
rehearsal of the tiny twin of ``sdar30b.fixedlen``
(``data/BENCHMARK_sdar.json``).

Tolerance 1e-4 on float32 logits of standard deviation 1 (measured
5e-6): the reference forwards the whole sequence one expert at a time,
the server appends slices to a paged cache, stacks a block's queries
beside the head group in one decode call and runs every held expert on
every row, so only the order of float32 sums differs.  bfloat16
arithmetic misses by 1e-2 (a test below holds it to that); a reference
that shifted the logits by a position, masked causally inside a block,
rotated before the q/k norm or normalised the gates before the top-k
would miss by 1e-1 or more.
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import cells, check, peaks  # noqa: E402
from benchmark import traffic as traffic_mod  # noqa: E402
from benchmark.builders import sdar_moe as builder  # noqa: E402
from benchmark.reference import block_diffusion_moe as reference  # noqa: E402
from tests.benchmark.listed import (Listed, by_name, held_to,  # noqa: E402
                                    last_json_line)

DATA = "tests/benchmark/data/BENCHMARK_sdar.json"
CELL, CONFIG = "sdar30b.fixedlen", "sdar-30b-a3b-chat-l12e32"
NEW = {"tokens_per_pass", "store_pass_share"}
PUBLISHED = json.loads((ROOT / "benchmark/configs/"
                        f"{CONFIG}.json").read_text())
TWIN = json.loads((ROOT / "tests/benchmark/data/configs/"
                   "sdar-tiny-test.json").read_text())
#: The uncut model the twin is a share of: all 8 experts held.
WHOLE = dict(TWIN, num_experts=8, experts_first=0)
CASES = {"share": TWIN, "whole": WHOLE}
SEED = 2 ** 31 + 12345
TOLERANCE = 1e-4


def _both(cfg, name, bits=8):
    import jax.numpy as jnp
    from aiko_services_tpu.models import sdar
    config = builder.program_config(name, cfg)
    params = builder.build_params(cfg, SEED, bits)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 1023, 150).astype(np.int32)
    masked = np.zeros(150, bool)
    masked[[145, 147, 148, 149]] = True       # a block in progress
    served = np.asarray(sdar.forward(
        params, jnp.asarray(tokens[None]), config,
        masked=jnp.asarray(masked[None])))[0]
    wanted = reference.forward(cfg, builder.ReferenceWeights(cfg, SEED),
                               tokens, masked)
    return config, served, wanted


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_the_program_forward(case):
    from aiko_services_tpu.models import sdar
    config, served, wanted = _both(CASES[case], f"sdar_reftest_{case}")
    preset = sdar.CONFIGS[
        "sdar_tiny_share" if case == "share" else "sdar_tiny"]
    # The twin is the program's preset, but for the cell's schedule.
    import dataclasses
    assert config == dataclasses.replace(
        preset, denoise_steps=2, denoise_dynamic=False)
    assert 0.5 < wanted.std() < 2.0
    np.testing.assert_allclose(served, wanted, atol=TOLERANCE, rtol=0)


def test_four_bit_weights_fail_the_margin():
    _, served, wanted = _both(TWIN, "sdar_reftest_4bit", bits=4)
    gaps = check.gaps_of(wanted, served.argmax(-1))
    assert gaps.mean() > 4 * TWIN["check"]["mean_gap_limit"]


def test_bfloat16_arithmetic_fails_the_stated_tolerance():
    cfg = dict(TWIN, assumed=dict(TWIN["assumed"],
                                  activation_dtype="bfloat16"))
    _, served, wanted = _both(cfg, "sdar_reftest_bf16")
    assert np.abs(served - wanted).max() > 50 * TOLERANCE


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """Layer 0 of the twin on the four chips that hold 2 of its 8
    experts each (the tiny form of ``held=(0, 32) .. (96, 32)``): the
    program's routed parts add up to what the reference gives for the
    whole feed-forward block, and every token-expert choice falls on
    exactly one chip."""
    import jax
    import jax.numpy as jnp
    from aiko_services_tpu.models import moe
    weights = builder.ReferenceWeights(WHOLE, SEED)
    layer = weights.layer(0)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        wanted = np.asarray(reference.experts(WHOLE, weights, 0, layer,
                                              [x], range(8))[0])
        normed = reference.rms_norm(x, layer["ffn_norm"],
                                    WHOLE["rms_norm_eps"])
        total, pairs = np.asarray(x), 0
        for first in range(0, 8, 2):
            cfg = dict(TWIN, experts_first=first)
            config = builder.program_config("sdar_share_test", cfg)
            assert config.experts_held == (first, 2)
            params = builder.build_params(cfg, SEED)["layers"][0]["moe"]
            out, counts = moe.moe_layer(params, normed[None],
                                        config.moe_config)
            total = total + np.asarray(out)[0]
            pairs += int(counts[0])
    np.testing.assert_allclose(total, wanted, atol=TOLERANCE, rtol=0)
    assert pairs == 24 * 2


def test_slices_then_passes_through_the_cache_give_the_references_logits():
    """Two slots out of phase, driven by hand: each prompt appended in
    slices of 32 under the block-causal mask, then ``block_pass`` (the
    served chunk's pass as a program that also returns its logits)
    until both answers are whole.  Before every pass the reference
    forwards each slot's sequence so far with its block as it stands;
    the pass's logits, and the positions its rule then commits, are the
    reference's."""
    import jax.numpy as jnp
    from aiko_services_tpu.models import sdar
    config = builder.program_config("sdar_pass_test", TWIN)
    params = builder.build_params(TWIN, SEED)
    weights = builder.ReferenceWeights(TWIN, SEED)
    B, rng = 4, np.random.default_rng(5)
    prompts = [rng.integers(1, 1023, n).astype(np.int32) for n in (37, 50)]
    asked = [6, 9]
    pool = sdar.init_paged_cache(config, 13, 16)
    tables = np.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]],
                        np.int32)
    for slot, prompt in enumerate(prompts):
        padded = np.zeros(64, np.int32)
        padded[:len(prompt)] = prompt
        for start in (0, 32):
            _, pool = sdar.prefill_append_paged(
                params, jnp.asarray(padded[None, start:start + 32]), pool,
                jnp.asarray(tables[slot:slot + 1]), jnp.int32(start),
                config, kv_limit=4, compute_logits=False)
    state = sdar.block_slot_state(config, 2)
    state.update(token=np.zeros((2, 1), np.int32),
                 positions=np.zeros(2, np.int32), active=np.ones(2, bool),
                 remaining=np.asarray(asked, np.int32),
                 temps=np.zeros(2, np.float32), tops=np.ones(2, np.float32),
                 adapter_ids=np.zeros(2, np.int32), tables=tables)
    state["denoise_steps"][:] = [2, 4]
    state["dynamic"][:] = [False, True]
    state["threshold"][:] = [0.9, 0.015]
    known = []
    for slot, prompt in enumerate(prompts):
        base = len(prompt) // B * B
        given = len(prompt) - base
        state["positions"][slot] = base
        state["window"][slot, :given] = prompt[base:]
        state["masked"][slot] = np.arange(B) >= given
        state["delivered"][slot] = given
        known.append(list(prompt[:base]))
    state = {name: jnp.asarray(leaf) for name, leaf in state.items()}
    compared = stored = 0
    while np.asarray(state["active"]).any():
        before = {name: np.asarray(leaf) for name, leaf in state.items()}
        logits, window, mark, state, pool = sdar.block_pass(
            params, state, pool, config)
        logits, window, mark = map(np.asarray, (logits, window, mark))
        for slot in np.nonzero(before["active"])[0]:
            base = int(before["positions"][slot])
            assert base == len(known[slot])
            masked = before["masked"][slot]
            sequence = np.asarray(known[slot]
                                  + list(before["window"][slot]), np.int32)
            flags = np.zeros(len(sequence), bool)
            flags[base:] = masked
            wanted = reference.forward(TWIN, weights, sequence,
                                       flags)[base:]
            np.testing.assert_allclose(logits[slot], wanted,
                                       atol=TOLERANCE, rtol=0)
            compared += 1
            if mark[slot] >> (B + sdar.MARK_STORE) & 1:
                assert not masked.any()
                known[slot] += list(before["window"][slot])
                stored += 1
                continue
            # The rule on the reference's own confidences picks what
            # the pass committed, each with the reference's best token.
            end = before["delivered"][slot] + before["remaining"][slot]
            opened = masked & (np.arange(B) < end)
            shifted = wanted - wanted.max(-1, keepdims=True)
            cfg = dict(TWIN, serving=dict(
                TWIN["serving"],
                denoise_steps=int(before["denoise_steps"][slot]),
                denoise_rule="dynamic" if before["dynamic"][slot]
                else "static",
                denoise_threshold=float(before["threshold"][slot])))
            chosen = reference.commit(1.0 / np.exp(shifted).sum(-1),
                                      opened, int(before["passes"][slot]),
                                      cfg)
            still = [bool(mark[slot] >> p & 1) for p in range(B)]
            assert still == [bool(masked[p]) and p not in chosen
                             for p in range(B)]
            for p in chosen:
                assert window[slot, p] == wanted[p].argmax()
    # 37 = 36 + 1 given, 6 asked: a block stored, the next cut after
    # its third position; 50 = 48 + 2 given, 9 asked: two stored, the
    # third cut.  A cut block retires its slot and is never stored.
    assert stored == 3 and compared >= 8


#: denoise_steps, rule, threshold of each request (None: the config's).
SCHEDULES = {
    "static_1": [(1, "static", None)] * 5,
    "static_2": [(None, None, None)] * 5,
    "static_4": [(4, "static", None)] * 5,
    "dynamic": [(4, "dynamic", 0.015)] * 5,
    "mixed": [(1, "static", None), (4, "dynamic", 0.015),
              (2, "static", None), (3, "dynamic", 0.9),
              (4, "static", None)],
}


@pytest.mark.parametrize("case", sorted(SCHEDULES))
def test_served_schedules_replay_to_the_references_own_tokens(case):
    """Through ``PagedContinuousServer``: prompts whose length leaves
    0, 1 and 3 tokens in the first generated block, two prompts in
    slices of 32 that ride the passes of others, five requests on two
    slots (out of phase, each slot used again), answers that end inside
    a block.  Every served token is the reference's own best at its
    position in the pass that committed it; ``mixed`` runs five
    schedules in one batch."""
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    builder.program_config("sdar_served_test", TWIN)
    server = PagedContinuousServer(
        config_name="sdar_served_test", slots=2, max_seq=256,
        chunk_steps=3, quantize=True,
        params=builder.build_params(TWIN, SEED), block_size=16,
        total_blocks=40, chunk_prefill_tokens=32)
    rng = np.random.default_rng(7)
    sizes = [(16, 10), (17, 7), (19, 9), (45, 13), (70, 6)]
    requests = [DecodeRequest(
        request_id=f"r{i}", max_new_tokens=answer,
        prompt=rng.integers(1, 1023, prompt).astype(np.int32),
        denoise_steps=steps, denoise_rule=rule, denoise_threshold=level)
        for i, ((prompt, answer), (steps, rule, level))
        in enumerate(zip(sizes, SCHEDULES[case]))]
    for request in requests:
        server.submit(request)
    server.run_until_drained()
    counters = server.counters
    assert counters["prefill_slices_mixed"] > 0
    assert counters["tokens_committed"] == sum(a for _, a in sizes)
    assert server.free_blocks == server.total_blocks
    weights = builder.ReferenceWeights(TWIN, SEED)
    for request, (steps, rule, level) in zip(requests, SCHEDULES[case]):
        assert request.error is None
        assert len(request.tokens) == request.max_new_tokens
        serving = dict(TWIN["serving"])
        serving.update({key: value for key, value in (
            ("denoise_steps", steps), ("denoise_rule", rule),
            ("denoise_threshold", level)) if value is not None})
        sequence = np.concatenate([request.prompt,
                                   np.asarray(request.tokens, np.int32)])
        span = (len(request.prompt) - 1, len(sequence) - 1)
        cfg = dict(TWIN, serving=serving)
        wanted, = reference.run(cfg, weights, [sequence], [span])
        assert check.gaps_of(wanted, request.tokens).max() <= TOLERANCE
        if case == "mixed":
            # One forward a block a pass, of the sequence so far: the
            # plain form of what run() does in one forward a pass.
            plain, = reference.run(cfg, weights, [sequence], [span],
                                   sequential=True)
            np.testing.assert_allclose(wanted, plain, atol=TOLERANCE,
                                       rtol=0)
    denoise = counters["block_pass_rows"] - counters["block_store_rows"]
    per_pass = counters["tokens_committed"] / denoise
    if case == "static_1":
        assert per_pass > 2.5          # whole blocks in one pass
    elif case == "static_4":
        assert per_pass == 1.0
    elif case == "dynamic":
        assert per_pass > 1.0          # the threshold let some through


# --- the chip-size configuration, from its own keys --------------------- #


def test_the_configurations_arithmetic_from_its_own_keys():
    cfg, z = PUBLISHED, builder.sizes(PUBLISHED)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["moe_intermediate_size"]
    # ISSUE 39: 18.9 M a layer in attention, 4.72 M an expert; the
    # query projection is 2048 -> 4096 (head_dim is not d / heads).
    assert heads * hd == 4096 != d
    assert d * heads * hd + 2 * d * kv * hd + heads * hd * d == \
        pytest.approx(18.9e6, rel=2e-3)
    assert 3 * d * f == pytest.approx(4.72e6, rel=1e-3)
    assert (z["layers"], z["experts"], z["experts_total"], z["top_k"],
            z["block"]) == (12, 32, 128, 8, 4)
    import jax
    tree = jax.eval_shape(lambda: builder.build_params(cfg, 1))
    served = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(tree))
    assert served == pytest.approx(4.5e9, rel=0.01)
    serving = cfg["serving"]
    position = 2 * kv * hd * 2
    assert position * z["layers"] == 24_576
    pool = serving["pool_blocks"] * serving["block_size"] * position \
        * z["layers"]
    assert pool == pytest.approx(3.22e9, rel=5e-3)
    assert 0.45 < (served + pool) / peaks.of("TPU v5 lite")["hbm_bytes"] \
        < 0.52
    config = builder.program_config("sdar_published", cfg)
    assert config.experts_held == (0, 32) and config.n_experts == 128
    assert (config.block_length, config.denoise_steps,
            config.denoise_dynamic, config.mask_id) == (4, 2, False,
                                                        151_669)
    assert 0 < config.mask_id < cfg["vocab_size"]
    for key in ("qk_norm", "no_shift", "block_length", "schedule",
                "mask_token_id", "partial_first_block", "answer_cut",
                "weight_precision", "kv_precision", "weights"):
        assert key in cfg["assumed"], key
    # Every number of the catalog row is in the file under its key,
    # but the two reduced ones; nested groups whole.
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(line) for line in open(path)] \
        if os.path.exists(path) else []
    for row in rows:
        if row["source_url"] == cfg["source"]:
            for key, value in row["config"].items():
                if key not in cfg["reduced"]:
                    assert cfg[key] == value, key
            for key in cfg["reduced"]:
                assert cfg["reduced_from"][key] == row["config"][key]


def test_the_traffic_mix_is_the_issues():
    mix = json.loads((ROOT / "benchmark/traffic/fixedlen_closed64.json"
                      ).read_text())
    assert (mix["loop"], mix["clients"], mix["slots"]) == ("closed", 64,
                                                           64)
    assert "rate_per_s" not in mix and "sharing" not in mix
    serving = PUBLISHED["serving"]
    assert mix["max_seq"] % serving["block_size"] == 0
    stream = traffic_mod.Mix(mix, 151_936, seed=5).requests()
    requests = [next(stream) for _ in range(2 * mix["population"])]
    for request in requests:
        assert 32 <= len(request.prompt) <= 1_024
        assert 256 <= request.max_new <= 1_024
        assert request.max_new % 64 == 0 and request.shared == 0
        # The asked length plus the last block's headroom fits.
        assert len(request.prompt) + request.max_new \
            + serving["block_length"] <= mix["max_seq"]
    assert 150 < np.median([len(r.prompt) for r in requests]) < 240
    assert {r.max_new for r in requests} == set(range(256, 1_025, 64))
    # A warm-up scene for every prompt bucket, idle and decoding.
    scenes = mix["warm"]["scenes"]
    for when in ("idle", "decoding"):
        assert {s["prompt"] for s in scenes if s["when"] == when} == {
            32, 64, 128, 256, 257, 513}


# --- what BENCHMARK.json lists for the cell ------------------------------ #


def test_the_root_lists_the_cell_and_its_two_metrics(listed):
    assert cells.check_names(listed.bench) == []
    config = listed.entry("configs", CONFIG)
    workload = listed.entry("workloads", CELL)
    assert (workload["config"], workload["traffic"], workload["chips"]) \
        == (config["name"], "fixedlen_closed64", 1)
    assert sorted(config["reduced"]) == sorted(PUBLISHED["reduced"])
    assert config["source"] == PUBLISHED["source"]
    cell = listed.cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "tpot_p50_ms", "out_tokens_per_s", "setup_s"}
    assert (cell.config["builder"], cell.config["reference"]) == (
        "sdar_moe", "block_diffusion_moe")
    held = by_name(cell)
    for metric in listed.per_layer(sorted(NEW)):
        assert set(metric) == {"name", "unit", "better", "source",
                               "layer", "moves", "workloads"}
        assert metric["workloads"][0] == CELL
        assert metric["source"] == "program_counter"
        _, described, _ = held[metric["name"]]
        for key in ("layer", "unit", "moves", "source"):
            assert described[key] == metric[key]
    # Every per-layer metric with no list of cells is the cell's too,
    # and no other cell loads the two that are this one's.
    unlisted = {m["name"] for m in listed.bench["per_layer"]
                if "workloads" not in m}
    assert unlisted and unlisted <= set(held)
    assert set(held) == unlisted | NEW
    # int8_matmul_roofline finds nothing to read in a pass of 256 rows
    # (ops/quant.py keeps its kernel to 64), so it names the cells that
    # had it and not this one (PERF.md section 3).  By name and not by
    # number: a later PR may append its own cell to that list.
    named = listed.entry("per_layer", "int8_matmul_roofline")["workloads"]
    assert CELL not in named and {
        "mistral7b.chat", "mixtral8x7b.chat", "nemotron3super.reason",
        "mistralsmall4.docs"} <= set(named)
    for other in listed.cells():
        if other.name != CELL:
            assert not NEW & set(by_name(other))


def test_the_twins_benchmark_file_holds_the_roots_entries(listed):
    twin = Listed(DATA)
    assert cells.check_names(twin.bench) == []
    cell = twin.cell("tiny.fixedlen")
    assert NEW <= set(by_name(cell))
    for metric, _, _ in cell.per_layer:
        assert held_to(listed, metric), metric


# --- the tiny twin of the cell ------------------------------------------ #


def _rehearse(tmp_path_factory, *more):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("cache"))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--benchmark", DATA, "--workload", "tiny.fixedlen", "--seed",
         "3000000011", "--seconds", "2", "--rehearsal", *more],
        cwd=ROOT, env=env, text=True, timeout=900,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert done.returncode == 0, done.stdout[-3000:]
    return done.stdout, last_json_line(done.stdout)


def test_the_twin_cell_rehearses_correct_with_its_counters(
        tmp_path_factory):
    output, line = _rehearse(tmp_path_factory, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert "compiles inside the window: 0" in output
    metrics = {name: entry["value"]
               for name, entry in line["metrics"].items()}
    # Two static passes and a store pass a block of four: 4 / 3 tokens
    # a pass and a third of the passes, but for first blocks that the
    # prompt opened and last blocks cut short.
    assert 1.2 < metrics["tokens_per_pass"] < 4 / 3 + 0.01
    assert 30.0 < metrics["store_pass_share"] < 100 / 3 + 0.01
    # A pass is not a token: tokens a pass over ALL slots.
    assert metrics["batch_occupancy"] == pytest.approx(
        metrics["tokens_per_pass"] * 4, rel=0.35)
    # No device plane on the CPU: the trace readers leave theirs out.
    assert not {"decode_attn_roofline", "decode_step_ms",
                "int8_matmul_roofline"} & set(metrics)


def test_the_four_bit_control_rehearses_incorrect(tmp_path_factory):
    output, line = _rehearse(tmp_path_factory, "--control-bits", "4")
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["mean_gap"]["value"] > \
        line["checks"]["mean_gap"]["limit"]
    assert '"correct": false' in output
