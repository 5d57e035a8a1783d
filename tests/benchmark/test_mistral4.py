"""The latent-attention configuration's benchmark files at a tiny size
on the CPU: the plain reference against the program's float32 forward
and against what the paged server serves (slices, a prefix hit on
shared latent blocks, decode through the cache), the chip's share of a
layer's experts against the uncut reference layer, the 4-bit control,
the chip-size configuration's arithmetic from its own keys, the traffic
mix's multiset, the new per-layer readers on hand-built traces, a
rehearsal of the tiny twin of ``mistralsmall4.docs``
(``data/BENCHMARK_mistral4.json``).  What ``BENCHMARK.json`` lists for
the cell is ``test_root_cells.py``.

Tolerance 1e-4 on float32 logits of standard deviation 1, as in
``test_reference.py`` (measured 2e-5): the reference is the EXPANDED
attention written on its own, the server computes the absorbed form
over its cache.  A reference that rotated halves instead of interleaved
pairs, left the query scale or YaRN's factor out of the softmax scale,
or normalised the gates before the top-k would miss by 1e-1 or more.
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

from benchmark import cells, check, latent_shapes, peaks, shapes  # noqa: E402
from benchmark import traffic as traffic_mod  # noqa: E402
from benchmark import xplane  # noqa: E402
from benchmark.builders import mistral4 as builder  # noqa: E402
from benchmark.reference import mla_moe as reference  # noqa: E402
from tests.benchmark.listed import last_json_line  # noqa: E402

DATA = "tests/benchmark/data/BENCHMARK_mistral4.json"
PUBLISHED = json.loads((ROOT / "benchmark/configs/"
                        "mistral-small-4-119b-l4e32.json").read_text())
TWIN = json.loads((ROOT / "tests/benchmark/data/configs/"
                   "mistral4-tiny-test.json").read_text())
#: The uncut model the twin is a share of: all 16 experts held.
WHOLE = dict(TWIN, n_routed_experts=16, experts_first=0)
CASES = {"share": TWIN, "whole": WHOLE}
SEED = 2 ** 31 + 12345


def _both(case, bits):
    import jax.numpy as jnp
    from aiko_services_tpu.models import mistral4
    cfg = CASES[case]
    config = builder.program_config(f"mla_reftest_{case}", cfg)
    preset = mistral4.CONFIGS[
        "mistral4_tiny_share" if case == "share" else "mistral4_tiny"]
    assert (config.d_model, config.n_heads, config.kv_lora_rank,
            config.n_experts, config.experts_held, config.sm_scale) == (
        preset.d_model, preset.n_heads, preset.kv_lora_rank,
        preset.n_experts, preset.experts_held, preset.sm_scale)
    params = builder.build_params(cfg, SEED, bits)
    # Past the twin's original context of 64, so both scalings act.
    tokens = np.random.default_rng(0).integers(1, 1024, 200).astype(
        np.int32)
    served = np.asarray(mistral4.forward(
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
    assert gaps.mean() > 4 * TWIN["check"]["mean_gap_limit"]


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """Layer 0 of the twin, on the four chips that hold 4 of its 16
    experts each: the program's routed parts, with the shared expert
    that every chip computes alike counted once, add up to what the
    reference gives for the whole feed-forward block."""
    import jax
    import jax.numpy as jnp
    from aiko_services_tpu.models import moe
    weights = builder.ReferenceWeights(WHOLE, SEED)
    layer = weights.layer(0)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):
        wanted = np.asarray(reference.experts(WHOLE, weights, 0, layer,
                                              [x], range(16))[0])
        normed = reference.rms_norm(x, layer["ffn_norm"],
                                    WHOLE["rms_norm_eps"])
        shared = np.asarray(reference.shared_term(layer, normed))
        total, pairs = np.asarray(x) + shared, 0
        for first in range(0, 16, 4):
            cfg = dict(TWIN, experts_first=first)
            config = builder.program_config("mla_share_test", cfg)
            assert config.experts_held == (first, 4)
            params = builder.build_params(cfg, SEED)["layers"][0]["moe"]
            out, counts = moe.moe_layer(params, normed[None],
                                        config.moe_config)
            total += np.asarray(out)[0] - shared
            pairs += int(counts[0])
    np.testing.assert_allclose(total, wanted, atol=1e-4, rtol=0)
    assert pairs == 24 * 4


def test_slices_a_prefix_hit_and_decode_serve_the_reference():
    """Through ``PagedContinuousServer`` with the prefix cache on: a
    260-token prompt in slices of 32 riding the decode chunks of a
    short one, then two more askings of its first 192 tokens, which
    hit the 12 latent blocks it left; every served token is the
    reference's own best at its position."""
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    builder.program_config("mla_served_test", TWIN)
    server = PagedContinuousServer(
        config_name="mla_served_test", slots=2, max_seq=512,
        chunk_steps=2, quantize=True,
        params=builder.build_params(TWIN, SEED), block_size=16,
        total_blocks=96, chunk_prefill_tokens=32,
        enable_prefix_cache=True)
    rng = np.random.default_rng(7)
    document = rng.integers(1, 1024, 192)
    prompts = [rng.integers(1, 1024, 45)] + [
        np.concatenate([document, rng.integers(1, 1024, n)])
        for n in (68, 30, 51)]
    requests = [DecodeRequest(request_id=f"r{i}", max_new_tokens=16,
                              prompt=prompt.astype(np.int32))
                for i, prompt in enumerate(prompts)]
    for request in requests[:2]:
        server.submit(request)
    server.run_until_drained()
    for request in requests[2:]:
        server.submit(request)
    server.run_until_drained()
    assert server.counters["prefill_slices_mixed"] > 0
    assert server.prefix_blocks_reused == 2 * 12
    assert [r.shared_tokens for r in requests] == [0, 0, 192, 192]
    sequences = [np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
                 for r in requests]
    spans = [(len(r.prompt) - 1, len(s) - 1)
             for r, s in zip(requests, sequences)]
    logits = reference.run(TWIN, builder.ReferenceWeights(TWIN, SEED),
                           sequences, spans)
    for request, wanted in zip(requests, logits):
        assert len(request.tokens) == 16
        assert check.gaps_of(wanted, request.tokens).max() <= 1e-4


# --- the chip-size configuration, from its own keys --------------------- #


def test_the_configurations_arithmetic_from_its_own_keys():
    cfg, z = PUBLISHED, builder.sizes(PUBLISHED)
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    f = cfg["moe_intermediate_size"]
    attention = (d * cfg["q_lora_rank"]
                 + cfg["q_lora_rank"] * heads * cfg["qk_head_dim"]
                 + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                 + cfg["kv_lora_rank"] * heads
                 * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                 + heads * cfg["v_head_dim"] * d)
    shared = 3 * d * f * cfg["n_shared_experts"]
    router = d * cfg["reduced_from"]["n_routed_experts"]
    # ISSUE 31: 53.7 M a layer outside its routed experts, 25.17 M an
    # expert.
    assert attention + shared + router == pytest.approx(53.7e6, rel=2e-3)
    assert 3 * d * f == pytest.approx(25.17e6, rel=1e-3)
    assert (z["layers"], z["experts"], z["experts_total"], z["top_k"]) \
        == (4, 32, 128, 4)
    # As served: int8 2-D matrices (f32 scales a column), bf16 experts,
    # router and per-head W_uk / W_uv; the whole vocabulary.
    import jax
    tree = jax.eval_shape(lambda: builder.build_params(cfg, 1))
    served = sum(leaf.size * leaf.dtype.itemsize
                 for leaf in jax.tree.leaves(tree))
    experts = z["layers"] * z["experts"] * 3 * d * f * 2
    assert experts == pytest.approx(6.44e9, rel=1e-3)
    assert served == pytest.approx(7.73e9, rel=3e-3)
    assert 0.45 < served / peaks.of("TPU v5 lite")["hbm_bytes"] < 0.52
    # The cache: 640 B a position a layer NEEDED; the reader of the
    # accepted decode_attn_roofline counts exactly that from sizes().
    needed = latent_shapes.latent_row_bytes(z["rank"], z["rope"])
    assert needed == 640 == 2 * z["kv"] * z["hd"] * 2
    assert z["layers"] * needed == 2560
    config = builder.program_config("mla_published", cfg)
    assert config.sm_scale == pytest.approx(
        128 ** -0.5 * (0.1 * np.log(128.0) + 1.0) ** 2)
    assert config.experts_held == (0, 32) and config.n_experts == 128
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
    # The pool: blocks for 32 live documents and 64 rows' tails.
    serving = cfg["serving"]
    mix = json.loads((ROOT / "benchmark/traffic/docs_closed64.json"
                      ).read_text())
    table = mix["max_seq"] // serving["block_size"]
    documents = mix["sharing"]["documents"]
    mean = (documents["min"] + documents["max"]) // 2 \
        // serving["block_size"]
    assert 32 * (2 * (table - 8) - mean) <= serving["pool_blocks"]
    block_bytes = z["layers"] * serving["block_size"] * 384 * 2
    assert serving["pool_blocks"] * block_bytes == pytest.approx(
        2.42e9, rel=5e-3)


def test_the_decode_readers_needed_bytes_are_the_exact_count():
    """The accepted ``decode_attn_roofline`` counts, through
    ``sizes()``'s ``kv`` and ``hd``, what the exact count of
    ``latent_shapes`` gives, within 2 %, at the contexts the cell has."""
    z = builder.sizes(PUBLISHED)
    for rows, context in ((50, 12_288), (64, 8_192), (20, 16_384)):
        positions = rows * context
        _, accepted = shapes.decode_attention(
            dict(z, layers=1), context_tokens=positions, rows=rows,
            kv_bytes=2)
        _, exact = latent_shapes.latent_decode(
            positions, rows, z["heads"], z["rank"], z["rope"])
        assert accepted == pytest.approx(exact, rel=0.02)
        assert exact == pytest.approx(positions * 640, rel=0.01)
    ops, _ = latent_shapes.latent_decode(1000, 1, 32, 256, 64)
    assert ops == 1000 * 2 * 32 * 576


def test_the_traffic_mix_is_the_issues_multiset():
    mix = json.loads((ROOT / "benchmark/traffic/docs_closed64.json"
                      ).read_text())
    assert (mix["loop"], mix["clients"], mix["slots"], mix["max_seq"]) \
        == ("closed", 64, 64, 16_896)
    assert "rate_per_s" not in mix
    sharing = mix["sharing"]
    assert (sharing["askings"], sharing["lag"]) == (32, 1)
    stream = traffic_mod.Mix(mix, 131_072, seed=5).requests()
    requests = [next(stream) for _ in range(528 + 10 * 32)]
    documents = {}
    for request in requests:
        assert request.shared % 256 == 0
        assert 8_192 <= request.shared <= 16_384
        question = len(request.prompt) - request.shared
        assert 32 <= question <= 128
        assert 128 <= request.max_new <= 384 and request.max_new % 8 == 0
        assert len(request.prompt) + request.max_new <= mix["max_seq"] - 1
        key = request.prompt[:request.shared].tobytes()
        documents.setdefault(key, []).append(request.index)
    # Past the first 32 steps (1 + 2 + ... + 32 requests) every step
    # is one fresh document and the next asking of the 31 before it.
    steady = requests[528:]
    fresh = [r for r in steady
             if documents[r.prompt[:r.shared].tobytes()][0] == r.index]
    assert len(fresh) == 10 and len(steady) == 320
    asked = [indices for indices in documents.values()
             if indices[0] >= 528 and len(indices) > 1]
    assert len(asked) == 9
    for indices in asked:
        # A document's next asking is one place later in the next
        # step: 33 requests after the last.
        assert set(np.diff(indices)) == {33}
    # Every width a slice can have is 256: documents and both padded
    # buckets are whole slices.
    assert mix["max_seq"] % 256 == 0
    scenes = mix["warm"]["scenes"]
    assert {s["when"] for s in scenes} == {"idle", "decoding"}


# --- the readers on hand-built traces ----------------------------------- #

Z = dict(builder.sizes(TWIN))


def _run(ops, traced=None, counters=None, slots=4, sizes=None):
    cell = types.SimpleNamespace(
        traffic={"slots": slots},
        config={"serving": {"block_size": 16, "kv_dtype": "bfloat16"}})
    return types.SimpleNamespace(
        sizes=sizes or Z, cell=cell, trace={"ops": ops},
        traced=traced or {}, counters=counters or {}, xplane=xplane,
        shapes=shapes, peaks=peaks.of("TPU v5 lite"))


def _reader(name):
    return cells._import(ROOT / "benchmark" / "layer_metrics"
                         / f"{name}.py").read


DECODE = ("%closed_call.6 = bf16[4,4,32]{2,1,0:T(8,128)(2,1)} custom-call("
          "s32[4,32]{1,0} %tables, s32[4]{0} %add.3, f32[4,4,128]{2,1,0} "
          "%q, bf16[97,16,128]{2,1,0} %pool), custom_call_target="
          "\"tpu_custom_call\"")
APPEND = ("%latent_append.3 = bf16[97,16,128]{2,1,0} custom-call(s32[4]{0}"
          " %blocks, s32[4,1]{1,0} %offsets, bf16[4,128]{1,0} %rows, "
          "bf16[97,16,128]{2,1,0} %pool)")
PREFILL = ("%latent_prefill_call.1 = bf16[1,128,32]{2,1,0} custom-call("
           "s32[1,32]{1,0} %table, s32[1]{0} %start, bf16[1,128,128]"
           "{2,1,0} %q, bf16[97,16,128]{2,1,0} %pool, bf16[32,128]{1,0} "
           "%own)")


def test_both_decode_readers_find_the_latent_kernel_by_its_call():
    ops = [(DECODE, 0, 4_000), (APPEND, 4_000, 500), (DECODE, 5_000, 6_000),
           (PREFILL, 12_000, 9_000)]
    traced = {"decode_blocks_read": 120, "decode_steps": 5}
    z = Z
    # 120 blocks of 16 positions a layer, 2 layers, 10 us of kernel.
    _, moved = latent_shapes.latent_decode(120 * 16, 5 * 4, z["heads"],
                                           z["rank"], z["rope"])
    share = _reader("latent_decode_roofline")(_run(ops, traced))
    assert share == pytest.approx(100.0 * 2 * moved / 819e9 / 10e-6)
    _, counted = shapes.decode_attention(
        dict(z, layers=1), context_tokens=120 * 16, rows=20, kv_bytes=2)
    accepted = _reader("decode_attn_roofline")(_run(ops, traced))
    assert accepted == pytest.approx(100.0 * 2 * counted / 819e9 / 10e-6)
    assert moved > 120 * 16 * 2 * (32 + 16)
    # Nothing to read: no kernel in the trace, or a program that
    # counted no blocks (the parent's).
    assert _reader("latent_decode_roofline")(
        _run([(APPEND, 0, 5)], traced)) is None
    assert _reader("latent_decode_roofline")(_run(ops)) is None
    # A K/V configuration's sizes have no rank: not this reader's.
    other = {k: v for k, v in z.items() if k != "rank"}
    assert _reader("latent_decode_roofline")(
        _run(ops, traced, sizes=other)) is None


def test_latent_prefill_roofline_is_the_swept_blocks_over_the_kernel():
    from aiko_services_tpu.ops import latent_attention
    spec = json.loads((ROOT / "benchmark/layer_metrics/"
                       "latent_prefill_roofline.json").read_text())
    assert spec["q_tile"] == latent_attention.PREFILL_Q_TILE
    ops = [(PREFILL, 0, 30_000), (DECODE, 30_000, 4_000),
           (PREFILL, 40_000, 50_000)]
    visits = latent_attention.latent_slice_key_blocks(1024, 256, 16)
    needed, moved = latent_shapes.latent_prefill(
        visits, 16, 64, Z["heads"], Z["rank"], Z["rope"])
    assert needed == visits * 16 * 64 * 2 * Z["heads"] * (2 * 32 + 16)
    # In every layer but the last, whose attention feeds nothing.
    least = max(needed / 197e12, moved / 819e9) * (Z["layers"] - 1)
    read = _reader("latent_prefill_roofline")
    assert read(_run(ops, {"prefill_key_blocks": visits})) == \
        pytest.approx(100.0 * least / 80e-6)
    assert read(_run(ops)) is None
    assert read(_run([(DECODE, 0, 5)], {"prefill_key_blocks": 7})) is None


def test_swiglu_expert_roofline_counts_three_matrices_an_expert():
    z = dict(Z, experts=4, experts_total=16, d=128, f=64, top_k=4)
    both = ("%fusion.7 = f32[4,4,64]{2,1,0} fusion(bf16[4,128,64]{2,1,0} "
            "%w_gate, bf16[4,128,64]{2,1,0} %w_up, f32[4,4]{1,0} %g, "
            "bf16[4,128]{1,0} %x), kind=kOutput")
    one = ("%fusion.9 = f32[32,4,64]{2,1,0} fusion(bf16[4,128,64]{2,1,0} "
           "%w_up, bf16[32,128]{1,0} %x), kind=kOutput")
    down = ("%fusion.8 = bf16[4,128]{1,0} fusion(bf16[4,256]{1,0} %h, "
            "bf16[256,128]{1,0} %w_down), kind=kOutput")
    down3 = ("%fusion.18 = bf16[32,128]{1,0} fusion(bf16[32,4,64]{2,1,0} "
             "%h, bf16[4,64,128]{2,1,0} %w_down), kind=kOutput")
    loop = "%while.4 = (s32[], bf16[4,128,64]{2,1,0}) while(...)"
    other = "%fusion.5 = bf16[4,128]{1,0} fusion(bf16[4,128]{1,0} %y)"

    def least(needs, *args, **kwargs):
        operations, moved = needs(*args, 4, 128, 64, **kwargs)
        return shapes.roofline_seconds(operations, 197e12, moved,
                                       819e9)[0]

    ops = [(one, 0, 3_000), (one, 3_000, 3_000), (down3, 6_000, 2_000),
           (loop, 10_000, 10_000), (both, 11_000, 2_000),
           (down, 13_000, 2_000), (other, 15_000, 5_000)]
    read = _reader("swiglu_expert_roofline")
    # 2 layers x 10 steps routed 60 pairs here: 3 a call in the scan;
    # at the slice of 32 rows the expectation, 32 x 4 x 4 / 16.
    run = _run(ops, {"decode_steps": 10, "moe_pairs_here": 60}, sizes=z)
    wanted = (2 * least(latent_shapes.swiglu_up, 32, 32, matrices=1)
              + least(latent_shapes.swiglu_down, 32, 32)
              + least(latent_shapes.swiglu_up, 4, 3, matrices=2)
              + least(latent_shapes.swiglu_down, 4, 3))
    assert read(run) == pytest.approx(100.0 * wanted / 12e-6)
    # The gate matrix is counted: a fusion reading both costs twice one.
    two, _ = latent_shapes.swiglu_up(4, 3, 4, 128, 64, matrices=2)
    single, _ = latent_shapes.swiglu_up(4, 3, 4, 128, 64, matrices=1)
    assert two == 2 * single == 2 * 2.0 * 3 * 128 * 64
    assert read(_run([(other, 0, 5)], sizes=z)) is None
    # A configuration without a latent cache is the accepted reader's.
    assert read(_run(ops, sizes={k: v for k, v in z.items()
                                 if k != "rank"})) is None


def test_the_accepted_expert_reader_leaves_this_cell_to_the_new_one():
    # moe_expert_roofline needs the latent EXPERT width of a hybrid
    # stack; here experts work on the full width.
    assert "latent" not in Z
    assert _reader("moe_expert_roofline")(_run([])) is None
    read = _reader("moe_pairs_per_expert")
    run = _run([], counters={"moe_pairs_here": 1_600, "decode_steps": 100})
    assert read(run) == pytest.approx(
        1_600 / 100 / (Z["experts"] * Z["expert_layers"]))


# --- the tiny twin of the cell ------------------------------------------ #


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("cache"))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--benchmark", DATA, "--workload", "tiny.docs", "--seed",
         "3000000011", "--seconds", "3", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, text=True, timeout=900,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert done.returncode == 0, done.stdout[-3000:]
    return done.stdout, last_json_line(done.stdout)


def test_the_twin_cell_rehearses_correct_with_its_counters(rehearsal):
    output, line = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert "compiles inside the window: 0" in output
    metrics = {name: entry["value"]
               for name, entry in line["metrics"].items()}
    # Three of four askings hit their document's blocks.
    assert 40.0 < metrics["prefix_hit_tokens"] < 100.0
    # Rows an expert sees a step: occupancy x top-4 / 16 experts, were
    # the routes even (over the 4 held of 16 they are not, quite).
    assert metrics["moe_pairs_per_expert"] == pytest.approx(
        metrics["batch_occupancy"] * 4 / 16, rel=0.4)
    # No device plane on the CPU: the trace readers leave theirs out.
    assert not {"latent_decode_roofline", "latent_prefill_roofline",
                "swiglu_expert_roofline", "decode_attn_roofline"} \
        & set(metrics)
