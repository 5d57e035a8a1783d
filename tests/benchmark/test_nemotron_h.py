"""The hybrid configuration's benchmark files at a tiny size on the
CPU: the plain reference against the program's float32 forward and
against what the paged server serves, the chip's share of an expert
layer against the uncut reference layer, the 4-bit control, the new
per-layer readers on hand-built traces, a rehearsal of the tiny
twin of ``nemotron3super.reason`` (``data/BENCHMARK_nemotron.json``).
What ``BENCHMARK.json`` lists for the cell is ``test_root_cells.py``.

Tolerance 1e-4 on float32 logits of standard deviation 1, as in
``test_reference.py``: both sides compute float32 arithmetic from the
same int8 draws and differ by summation order (measured 1e-5).  A
reference that rotated q and k, normalised the gates without the
scaling factor, left the selection bias out of the choice, or ran the
recurrence from the wrong convolution tap would miss by 1e-1 or more.
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

from benchmark import cells, check, hybrid_shapes, peaks, shapes  # noqa: E402
from benchmark import xplane  # noqa: E402
from benchmark.builders import nemotron_h as builder  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402
from tests.benchmark.listed import last_json_line  # noqa: E402

DATA = "tests/benchmark/data/BENCHMARK_nemotron.json"
TWIN = json.loads((ROOT / "tests/benchmark/data/configs/"
                   "nemotron-tiny-test.json").read_text())
#: The uncut model the twin is a share of: all 16 experts held.
WHOLE = dict(TWIN, n_routed_experts=16, experts_first=0,
             reduced_from=dict(TWIN["reduced_from"], n_routed_experts=16))
CASES = {"share": TWIN, "whole": WHOLE}
SEED = 2 ** 31 + 12345


def _both(case, bits):
    import jax.numpy as jnp
    from aiko_services_tpu.models import nemotron_h
    cfg = CASES[case]
    config = builder.program_config(f"reftest_{case}", cfg)
    preset = nemotron_h.CONFIGS[
        "nemotron_tiny_share" if case == "share" else "nemotron_tiny"]
    assert (config.pattern, config.d_model, config.mamba_heads,
            config.n_experts, config.experts_held, config.d_latent) == (
        preset.pattern, preset.d_model, preset.mamba_heads,
        preset.n_experts, preset.experts_held, preset.d_latent)
    params = builder.build_params(cfg, SEED, bits)
    tokens = np.random.default_rng(0).integers(1, 1024, 100).astype(
        np.int32)
    served = np.asarray(nemotron_h.forward(
        params, jnp.asarray(tokens[None]), config))[0]
    wanted = reference.run(cfg, builder.ReferenceWeights(cfg, SEED),
                           [tokens], [(0, len(tokens))])[0]
    return served, wanted


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_the_program_forward(case):
    served, wanted = _both(case, bits=8)
    assert 0.5 < wanted.std() < 2.0
    np.testing.assert_allclose(served, wanted, atol=1e-4, rtol=0)
    assert check.gaps_of(wanted, served.argmax(-1)).max() <= 1e-4


@pytest.mark.parametrize("case", sorted(CASES))
def test_four_bit_weights_fail_the_margin(case):
    served, wanted = _both(case, bits=4)
    gaps = check.gaps_of(wanted, served.argmax(-1))
    # The twin's limit (tests/benchmark/data/configs).
    assert gaps.mean() > 4 * TWIN["check"]["mean_gap_limit"]


def test_the_share_is_a_part_of_the_uncut_reference_layer():
    """The first E layer of the twin, on the four chips that hold 4 of
    its 16 experts each: the program's routed parts, with the shared
    expert that every chip computes alike counted once, add up to what
    the reference gives for the whole layer."""
    import jax
    import jax.numpy as jnp
    from aiko_services_tpu.models import moe
    weights = builder.ReferenceWeights(WHOLE, SEED)
    index = weights.kept.index("E")
    layer = weights.layer(index)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        wanted = np.asarray(reference.experts(WHOLE, weights, index,
                                              layer, [x], range(16))[0])
        normed = reference.rms_norm(x, layer["norm"], WHOLE["norm_eps"])
        shared = np.asarray(reference.relu2(
            normed @ layer["shared_up"]) @ layer["shared_down"])
    total = np.asarray(x) + shared
    for first in range(0, 16, 4):
        cfg = dict(TWIN, experts_first=first)
        config = builder.program_config("share_test", cfg)
        params = builder.build_params(cfg, SEED)["layers"][index]["moe"]
        out, counts = moe.moe_layer(params, normed[None],
                                    config.moe_config)
        assert int(counts[2]) == 24
        total += np.asarray(out)[0] - shared
    np.testing.assert_allclose(total, wanted, atol=1e-4, rtol=0)


def test_slices_of_a_padded_bucket_then_decode_serve_the_reference():
    """Through ``PagedContinuousServer``: 45 prompt tokens in a bucket
    of 64 as two 32-token slices (the second mostly padding), and a
    300-token prompt in slices riding the decode chunks of the first;
    every served token is the reference's own best at its position."""
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    builder.program_config("served_test", TWIN)
    server = PagedContinuousServer(
        config_name="served_test", slots=2, max_seq=512, chunk_steps=8,
        quantize=True, params=builder.build_params(TWIN, SEED),
        block_size=16, total_blocks=64, chunk_prefill_tokens=32)
    rng = np.random.default_rng(7)
    requests = [DecodeRequest(request_id=f"r{n}", max_new_tokens=24,
                              prompt=rng.integers(1, 1024, n).astype(
                                  np.int32)) for n in (45, 300)]
    for request in requests:
        server.submit(request)
    server.run_until_drained()
    assert server.counters["prefill_slices_mixed"] > 0
    sequences = [np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
                 for r in requests]
    spans = [(len(r.prompt) - 1, len(s) - 1)
             for r, s in zip(requests, sequences)]
    logits = reference.run(TWIN, builder.ReferenceWeights(TWIN, SEED),
                           sequences, spans)
    for request, wanted in zip(requests, logits):
        assert len(request.tokens) == 24
        assert check.gaps_of(wanted, request.tokens).max() <= 1e-4


def test_sizes_count_only_the_layers_that_own_a_kv_pool():
    published = json.loads((ROOT / "benchmark/configs/"
                            "nemotron-3-super-120b-l11e128.json"
                            ).read_text())
    z = builder.sizes(published)
    assert builder.pattern(published) == "*EMEMEMEMEM"
    assert (z["layers"], z["mamba_layers"], z["expert_layers"]) == (
        1, 5, 5)
    assert (z["experts"], z["experts_total"], z["top_k"]) == (128, 512,
                                                              22)
    # Every number of the catalog row is in the file under its key,
    # but the two reduced ones.
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else []
    for row in rows:
        if row["source_url"] == published["source"]:
            for key, value in row["config"].items():
                if key not in published["reduced"]:
                    assert published[key] == value, key
            for key in published["reduced"]:
                assert published["reduced_from"][key] == \
                    row["config"][key]


# --- the readers on hand-built traces ----------------------------------- #

Z = dict(experts=4, latent=8, f=16, expert_layers=2, mamba_layers=2,
         mamba_heads=8, mamba_hd=32, state=16, groups=2, chunk=128)


def _run(ops, traced=None, counters=None, slots=4):
    cell = types.SimpleNamespace(traffic={"slots": slots})
    return types.SimpleNamespace(
        sizes=Z, cell=cell, trace={"ops": ops}, traced=traced or {},
        counters=counters or {}, xplane=xplane, shapes=shapes,
        peaks=peaks.of("TPU v5 lite"))


def _reader(name):
    return cells._import(ROOT / "benchmark" / "layer_metrics"
                         / f"{name}.py").read


def test_moe_expert_roofline_reads_the_two_matmuls_by_their_weights():
    """Needed work is the ROUTED work: inside the decode scan the
    pairs the program counted, at a slice the routes' expectation;
    never the dispatch's every-expert-on-every-row."""
    up = ("%fusion.7 = bf16[4,4,16]{2,1,0} fusion(bf16[4,8,16]{2,1,0} "
          "%p.1, f32[4,4]{1,0} %g, bf16[4,8]{1,0} %x), kind=kOutput")
    down = ("%fusion.8 = bf16[4,8]{1,0} fusion(bf16[4,4,16]{2,1,0} %h, "
            "bf16[4,16,8]{2,1,0} %p.2), kind=kOutput")
    wide = ("%fusion.17 = bf16[32,4,16]{2,1,0} fusion(bf16[4,8,16]"
            "{2,1,0} %p.1, f32[32,4]{1,0} %g, bf16[32,8]{1,0} %x)")
    loop = "%while.4 = (s32[], bf16[4,8,16]{2,1,0}) while(...)"
    other = "%fusion.9 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %y)"

    def least(needs, rows, pairs):
        operations, moved = needs(rows, pairs, 4, 8, 16)
        return shapes.roofline_seconds(operations, 197e12, moved,
                                       819e9)[0]

    # A slice of 32 rows before the scan; two decode steps inside it.
    ops = [(wide, 0, 3_000), (loop, 10_000, 10_000),
           (up, 11_000, 2_000), (down, 13_000, 2_000),
           (other, 15_000, 5_000)]
    read = _reader("moe_expert_roofline")
    # 2 E layers x 10 steps of the span routed 60 pairs here: 3 a call;
    # the twin of Z holds all 4 of 4 experts, top-k 2: 64 at the slice.
    z = dict(Z, top_k=2, experts_total=4)
    run = _run(ops, traced={"decode_steps": 10, "moe_pairs_here": 60})
    run.sizes = z
    wanted = (least(hybrid_shapes.expert_up, 32, 64)
              + least(hybrid_shapes.expert_up, 4, 3)
              + least(hybrid_shapes.expert_down, 4, 3))
    assert read(run) == pytest.approx(100.0 * wanted / 7e-6)
    # Without the program's count a decode call takes the expectation.
    run = _run(ops)
    run.sizes = z
    wanted = (least(hybrid_shapes.expert_up, 32, 64)
              + least(hybrid_shapes.expert_up, 4, 8)
              + least(hybrid_shapes.expert_down, 4, 8))
    assert read(run) == pytest.approx(100.0 * wanted / 7e-6)
    # The dense dispatch's own arithmetic is not what is needed: at a
    # wide slice of a quarter-held top-2-of-16 layer it is 8 x more.
    routed, _ = hybrid_shapes.expert_up(256, 256 * 2 * 4 / 16, 4, 8, 16)
    assert 2.0 * 256 * 4 * 8 * 16 == 8 * routed
    run = _run([(other, 0, 5)])
    run.sizes = z
    assert read(run) is None


def test_ssm_update_roofline_reads_the_fusion_by_its_result():
    update = ("%fusion.3 = (f32[4,8,32,16]{3,2,1,0:T(8,128)}, "
              "f32[4,8,32]{2,1,0:T(8,128)S(1)}) fusion(f32[4,8,32,16]"
              "{3,2,1,0} %state, f32[4,8,16]{2,1,0} %b), kind=kLoop")
    patch = ("%fusion.5 = f32[4,8,32,16]{3,2,1,0} fusion(f32[4,8,32,16]"
             "{3,2,1,0} %state, f32[8,32,16]{2,1,0} %row), kind=kLoop")
    ops = [(update, 0, 1_000), (patch, 1_000, 500), (update, 2_000, 3_000)]
    _, moved = hybrid_shapes.ssm_update(4, 8, 32, 16, 2)
    share = _reader("ssm_update_roofline")(_run(ops))
    assert share == pytest.approx(100.0 * 2 * moved / 819e9 / 4e-6)
    assert moved > 2 * 4 * 4 * 8 * 32 * 16
    assert _reader("ssm_update_roofline")(_run([(patch, 0, 5)])) is None


def test_ssm_prefill_ms_per_ktok_leaves_the_decode_scan_out():
    loop = "%while.4 = (s32[], f32[4,8,32,16]{3,2,1,0}) while(...)"
    inside = ("%fusion.3 = (f32[4,8,32,16]{3,2,1,0}, f32[4,8,32]{2,1,0})"
              " fusion(f32[4,8,32,16]{3,2,1,0} %state)")
    decay = "%fusion.11 = f32[8,128,128]{2,1,0} fusion(f32[128,8]{1,0} %a)"
    grown = ("%fusion.12 = f32[2,4,32,16]{3,2,1,0} fusion(f32[128,2,4,32]"
             "{3,2,1,0} %x, f32[128,2,16]{2,1,0} %b)")
    matmul = "%fusion.13 = f32[128,1320]{1,0} fusion(f32[128,128]{1,0} %u)"
    ops = [(decay, 0, 3_000), (grown, 3_000, 1_000), (matmul, 4_000, 9_000),
           (loop, 20_000, 10_000), (inside, 21_000, 2_000)]
    read = _reader("ssm_prefill_ms_per_ktok")
    assert read(_run(ops, traced={"ssm_prefill_tokens": 500})) == \
        pytest.approx(4_000 / 1e6 / 0.5)
    assert read(_run(ops)) is None


def test_moe_pairs_per_expert_is_rows_an_expert_sees_a_step():
    read = _reader("moe_pairs_per_expert")
    run = _run([], counters={"moe_pairs_here": 1_600, "decode_steps": 100})
    assert read(run) == pytest.approx(1_600 / 100 / (4 * 2))
    assert read(_run([], counters={"decode_steps": 100})) is None


# --- the tiny twin of the cell ------------------------------------------ #


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("cache"))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--benchmark", DATA, "--workload", "tiny.reason", "--seed",
         "3000000011", "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, text=True, timeout=600,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert done.returncode == 0, done.stdout[-3000:]
    return done.stdout, last_json_line(done.stdout)


def test_the_twin_cell_rehearses_correct_with_its_counters(rehearsal):
    output, line = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert "compiles inside the window: 0" in output
    metrics = {name: entry["value"]
               for name, entry in line["metrics"].items()}
    # Rows an expert sees a step: occupancy x top-4 / 16 experts.
    assert metrics["moe_pairs_per_expert"] == pytest.approx(
        metrics["batch_occupancy"] * 4 / 16, rel=0.05)
    assert line["metrics"]["moe_pairs_per_expert"]["unit"] == "tokens"
    # No device plane on the CPU: the trace readers leave theirs out.
    assert "moe_expert_roofline" not in metrics
