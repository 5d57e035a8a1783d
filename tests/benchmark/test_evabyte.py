"""The byte-level configuration's benchmark files at a tiny size on the
CPU: the plain reference against the program's float32 forward on the
builder's seeded weights (all prediction heads), the 4-bit control, the
chip-size configuration's arithmetic from its own keys, the traffic
mix, what ``BENCHMARK.json`` lists for the cell, the three new readers
on counters and on a synthetic trace, and a rehearsal of the tiny twin
of ``evabyte.files`` (``data/BENCHMARK_evabyte.json``).  The served
path against the reference at the program's own tiny widths is
``tests/test_evabyte.py``.
"""

import json
import os
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import cells, check, peaks  # noqa: E402
from benchmark import traffic as traffic_mod  # noqa: E402
from benchmark.builders import evabyte as builder  # noqa: E402
from benchmark.reference import eva_window_chunks as reference  # noqa: E402
from tests.benchmark.listed import (Listed, by_name, held_to,  # noqa: E402
                                    last_json_line)

DATA = "tests/benchmark/data/BENCHMARK_evabyte.json"
CELL, CONFIG = "evabyte.files", "evabyte-6.5b"
NEW = {"eva_summary_block_share", "eva_rows_per_position",
       "eva_summarise_roofline"}
PUBLISHED = json.loads((ROOT / "benchmark/configs/"
                        f"{CONFIG}.json").read_text())
TWIN = json.loads((ROOT / "tests/benchmark/data/configs/"
                   "evabyte-tiny-test.json").read_text())
SEED = 2 ** 31 + 4141
TOLERANCE = 2e-4


def _both(bits=8, length=600):
    import jax.numpy as jnp
    from aiko_services_tpu.models import evabyte
    config = builder.program_config(f"evabyte_reftest_{bits}", TWIN)
    params = builder.build_params(TWIN, SEED, bits)
    tokens = np.random.default_rng(0).integers(0, 320, length).astype(
        np.int32)
    served = np.asarray(evabyte.forward(params, jnp.asarray(tokens[None]),
                                        config))[0]
    wanted, = reference.run(TWIN, builder.ReferenceWeights(TWIN, SEED),
                            [tokens], [(0, length)], all_heads=True)
    return config, served, wanted


def test_reference_matches_the_program_forward_on_every_head():
    """600 bytes, window 256: two windows behind the last queries."""
    config, served, wanted = _both()
    assert (config.window_size, config.chunk_size, config.n_pred_heads,
            config.n_kv_heads) == (256, 16, 8, config.n_heads)
    assert served.shape == wanted.shape == (600, 8, 320)
    assert 0.3 < wanted.std() < 3.0
    np.testing.assert_allclose(served, wanted, atol=TOLERANCE, rtol=0)


def test_four_bit_weights_fail_the_margin():
    _, served, wanted = _both(bits=4)
    gaps = check.gaps_of(wanted[:, 0], served[:, 0].argmax(-1))
    assert gaps.mean() > 4 * TWIN["check"]["mean_gap_limit"]


def test_the_reference_imports_nothing_of_the_program():
    source = (ROOT / "benchmark/reference/eva_window_chunks.py"
              ).read_text()
    assert "aiko_services_tpu" not in source
    assert 'default_matmul_precision(HIGHEST)' in source
    assert 'HIGHEST = "highest"' in source
    for step in ("1. ", "2. ", "3. ", "4. ", "5. ", "6. ", "Departures"):
        assert step in reference.__doc__


# --- the chip-size configuration, from its own keys --------------------- #


def test_the_configurations_arithmetic_from_its_own_keys():
    cfg, z = PUBLISHED, builder.sizes(PUBLISHED)
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    assert (z["heads"], z["kv"], z["hd"], z["window"], z["chunk"],
            z["pred_heads"], z["vocab"], z["layers"]) == (
        32, 32, 128, 2048, 16, 8, 320, 32)
    # ISSUE 41: 202.38 M a layer, 1.31 M embedding, 10.49 M head.
    layer = 4 * d * d + 3 * d * f + 2 * 32 * 128 + 2 * d
    assert layer == pytest.approx(202.38e6, rel=1e-3)
    assert 320 * d == pytest.approx(1.31e6, rel=1e-2)
    assert d * 8 * 320 == pytest.approx(10.49e6, rel=1e-3)
    import jax
    tree = jax.eval_shape(lambda: builder.build_params(cfg, 1))
    served = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(tree))
    assert served == pytest.approx(6.49e9, rel=0.01)
    serving = cfg["serving"]
    assert serving["block_size"] == cfg["chunk_size"]
    row = 2 * 32 * 128 + 2 * 32 * 4
    assert row == 8448
    block = z["layers"] * serving["block_size"] * row
    assert block == 4_325_376
    pool = serving["pool_blocks"] * block
    assert pool == pytest.approx(6.64e9, rel=2e-3)
    hbm = peaks.of("TPU v5 lite")["hbm_bytes"]
    assert 0.75 < (served + pool) / hbm < 0.85
    mix = json.loads((ROOT / "benchmark/traffic/files_closed8.json"
                      ).read_text())
    from aiko_services_tpu.models import evabyte
    config = builder.program_config("evabyte_published", cfg)
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    assert evabyte.slot_blocks(config, longest, 16) == 128 + 8 * 7 == 184
    assert evabyte.table_blocks(config, mix["max_seq"], 16) == 184
    assert mix["slots"] * 184 + 64 == serving["pool_blocks"]
    # Kept exactly the longest request would be 800 blocks a slot.
    assert -(-longest // 16) == 800
    assert cfg["reduced"] == []
    for key in ("summary", "summary_scale", "rope", "head_layout",
                "qk_norm", "next_byte", "weight_precision",
                "kv_precision", "weights"):
        assert key in cfg["assumed"], key
    # Every key of the catalog row is in the file, unchanged.
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(line) for line in open(path)] \
        if os.path.exists(path) else []
    for row in rows:
        if row["source_url"] == cfg["source"]:
            for key, value in row["config"].items():
                assert cfg[key] == value, key


def test_the_traffic_mix_is_the_issues():
    mix = json.loads((ROOT / "benchmark/traffic/files_closed8.json"
                      ).read_text())
    assert (mix["loop"], mix["clients"], mix["slots"]) == ("closed", 8, 8)
    assert "rate_per_s" not in mix and "sharing" not in mix
    serving = PUBLISHED["serving"]
    stream = traffic_mod.Mix(mix, 320, seed=5).requests()
    requests = [next(stream) for _ in range(2 * mix["population"])]
    window = PUBLISHED["window_size"]
    for request in requests:
        assert 1_024 <= len(request.prompt) <= 12_288
        assert len(request.prompt) % 256 == 0
        assert 128 <= request.max_new <= 512
        assert request.max_new % serving["chunk_steps"] == 0
        assert 0 < request.prompt.min() and request.prompt.max() < 320
        assert len(request.prompt) + request.max_new <= mix["max_seq"]
    lengths = np.asarray([len(r.prompt) for r in requests])
    assert 3_800 <= np.median(lengths) <= 4_400
    # Half the prompts close 2 or more windows in prefill; some answers
    # close one in decode (the longest prompts end on a window's end).
    assert 0.4 < (lengths >= 2 * window).mean() < 0.6
    crossing = [(len(r.prompt) - 1) // window
                != (len(r.prompt) + r.max_new - 2) // window
                for r in requests]
    assert 0.15 < np.mean(crossing) < 0.5
    assert lengths.max() == 12_288
    scenes = mix["warm"]["scenes"]
    assert {s["when"] for s in scenes} == {"idle", "decoding"}
    assert any(0 < window - s["prompt"] <= 16 and s["output"] > 16
               for s in scenes)


# --- what BENCHMARK.json lists for the cell ------------------------------ #


def test_the_root_lists_the_cell_and_its_three_metrics(listed):
    assert cells.check_names(listed.bench) == []
    config = listed.entry("configs", CONFIG)
    workload = listed.entry("workloads", CELL)
    assert (workload["config"], workload["traffic"], workload["chips"]) \
        == (config["name"], "files_closed8", 1)
    assert config["reduced"] == PUBLISHED["reduced"] == []
    assert config["source"] == PUBLISHED["source"]
    cell = listed.cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "tpot_p50_ms", "out_tokens_per_s", "setup_s"}
    assert (cell.config["builder"], cell.config["reference"]) == (
        "evabyte", "eva_window_chunks")
    held = by_name(cell)
    for metric in listed.per_layer(sorted(NEW)):
        assert set(metric) == {"name", "unit", "better", "source",
                               "layer", "moves", "workloads"}
        assert metric["workloads"] == [CELL]
        _, described, _ = held[metric["name"]]
        for key in ("layer", "unit", "moves", "source"):
            assert described[key] == metric[key]
    # Every per-layer metric with no list of cells is the cell's too,
    # and no other cell loads the three that are this one's.
    unlisted = {m["name"] for m in listed.bench["per_layer"]
                if "workloads" not in m}
    assert unlisted and unlisted <= set(held)
    assert NEW <= set(held)
    for other in listed.cells():
        if other.name != CELL:
            assert not NEW & set(by_name(other))


def test_the_twins_benchmark_file_holds_the_roots_entries(listed):
    twin = Listed(DATA)
    assert cells.check_names(twin.bench) == []
    cell = twin.cell("tiny.files")
    assert NEW <= set(by_name(cell))
    for metric, _, _ in cell.per_layer:
        assert held_to(listed, metric), metric


# --- the three readers --------------------------------------------------- #


def _readers():
    cell = Listed("BENCHMARK.json").cell(CELL)
    return {name: read for name, (_, _, read) in by_name(cell).items()
            if name in NEW}


def test_the_counter_readers_read_ratios_and_nothing_without_counters():
    readers = _readers()
    run = types.SimpleNamespace(counters=dict(
        decode_blocks_read=1000, decode_summary_blocks_read=120,
        eva_rows_held=2500, eva_positions_held=10000))
    assert readers["eva_summary_block_share"](run) == pytest.approx(12.0)
    assert readers["eva_rows_per_position"](run) == pytest.approx(25.0)
    older = types.SimpleNamespace(counters=dict(decode_blocks_read=1000))
    assert readers["eva_summary_block_share"](older) is None
    assert readers["eva_rows_per_position"](older) is None
    idle = types.SimpleNamespace(counters=dict(
        decode_blocks_read=0, decode_summary_blocks_read=0,
        eva_rows_held=0, eva_positions_held=0))
    assert readers["eva_summary_block_share"](idle) is None
    assert readers["eva_rows_per_position"](idle) is None


def test_the_kernels_share_is_its_needed_time_over_its_device_time():
    """A synthetic trace: two calls of the chunk-summary kernel and a
    call of another kernel, over a span in which decode wrote 1,600
    rows (100 chunks of 16 a layer)."""
    from benchmark import eva_shapes, shapes, xplane
    read = _readers()["eva_summarise_roofline"]
    z = builder.sizes(PUBLISHED)
    name = ("%eva_summarise.{} = (f32[8,32,1,128]{{3,2,1,0}}, "
            "f32[8,32,1,128]{{3,2,1,0}}) custom-call(s32[8]{{0}} %a)")
    ops = [(name.format(3), 0, 4_000_000),
           (name.format(41), 5_000_000, 6_000_000),
           ("%closed_call.7 = bf16[8,32,128]{2,1,0} custom-call(s32[8,184]"
            "{1,0} %t)", 12_000_000, 900_000)]

    def run(**more):
        base = dict(
            traced=dict(tokens_committed=1_600), sizes=z,
            trace=dict(ops=ops), xplane=xplane, shapes=shapes,
            peaks=peaks.of("TPU v5 lite"),
            cell=types.SimpleNamespace(config=PUBLISHED))
        base.update(more)
        return types.SimpleNamespace(**base)

    # A chunk a layer: 2 x 16 x 32 x 128 int8 + 2 x 16 x 32 f32 scales
    # read, 2 x 32 x 128 f32 written; memory-bound.
    assert eva_shapes.chunk_summaries(z, 1)[1] == 131_072 + 4_096 + 32_768
    needed = 100 * 32 * 167_936 / peaks.of("TPU v5 lite")[
        "hbm_bytes_per_s"]
    assert read(run()) == pytest.approx(100 * needed / 10e-3)
    assert 0 < read(run()) < 100
    assert read(run(trace=None)) is None
    assert read(run(traced={})) is None
    assert read(run(sizes=dict(z, chunk=None))) is None
    assert read(run(trace=dict(ops=ops[2:]))) is None


# --- the tiny twin of the cell ------------------------------------------ #


def _rehearse(tmp_path_factory, *more):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("cache"))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--benchmark", DATA, "--workload", "tiny.files", "--seed",
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
    # Prompts of 64-640 bytes over windows of 256: some of what a step
    # reads is summaries, and a slot holds well under a row a position
    # (never under a sixteenth).
    assert 1.0 < metrics["eva_summary_block_share"] < 50.0
    assert 100 / 16 < metrics["eva_rows_per_position"] < 100.0
    assert metrics["prefix_hit_tokens"] == 0.0
    # No device plane on the CPU: the trace readers leave theirs out.
    assert not {"decode_attn_roofline", "decode_step_ms",
                "eva_summarise_roofline"} & set(metrics)


def test_the_four_bit_control_rehearses_incorrect(tmp_path_factory):
    output, line = _rehearse(tmp_path_factory, "--control-bits", "4")
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["mean_gap"]["value"] > \
        line["checks"]["mean_gap"]["limit"]
    assert '"correct": false' in output
