"""Each plain reference against the program's own full-sequence
forward at a tiny size, and the 4-bit control failing the margin.

Tolerance 1e-4 on float32 logits of standard deviation 1: both sides
then compute the same float32 arithmetic from the same int8 draws, and
differ by summation order alone (measured 8e-6).  A reference that
left out the window, the rotary convention, the renormalised top-2
gates or a scale would miss by 1e-1 or more.
"""

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import check  # noqa: E402
from benchmark.builders import llama_family  # noqa: E402
from benchmark.reference import dense_gqa, moe_top2  # noqa: E402

BASE = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=352, vocab_size=1024, num_hidden_layers=2,
            rope_theta=10000.0, rms_norm_eps=1e-5,
            max_position_embeddings=512, sliding_window=None,
            assumed=dict(activation_dtype="float32"))
MOE = dict(num_local_experts=4, num_experts_per_tok=2,
           assumed=dict(activation_dtype="float32",
                        moe_capacity_factor=2.0))
CASES = {"tiny": ({}, dense_gqa),
         "mistral_tiny": (dict(sliding_window=16), dense_gqa),
         "moe_tiny": (MOE, moe_top2)}
SEED = 2 ** 31 + 12345


def _both(case, bits):
    import jax.numpy as jnp
    from aiko_services_tpu.models import llama
    extra, reference = CASES[case]
    cfg = dict(BASE, **extra)
    config = llama_family.program_config(f"reftest_{case}", cfg)
    # The same widths as the program's own preset of that name.
    preset = llama.CONFIGS[case]
    assert (config.d_model, config.n_heads, config.n_kv_heads,
            config.d_ff, config.sliding_window, config.n_experts) == (
        preset.d_model, preset.n_heads, preset.n_kv_heads, preset.d_ff,
        preset.sliding_window, preset.n_experts)
    params = llama_family.build_params(cfg, SEED, bits)
    tokens = np.random.default_rng(0).integers(1, 1024, 100).astype(
        np.int32)
    served = np.asarray(llama.forward(params, jnp.asarray(tokens[None]),
                                      config, use_flash=False))[0]
    wanted = reference.run(
        cfg, llama_family.ReferenceWeights(cfg, SEED), [tokens],
        [(0, len(tokens))])[0]
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
    # The tiny cell's limits (tests/benchmark/data/configs).
    assert gaps.max() > 4 * 0.25 and gaps.mean() > 4 * 0.02


def test_seed_wider_than_32_bits_changes_the_weights():
    cfg = dict(BASE)
    low = llama_family.build_params(cfg, 7, 8)
    high = llama_family.build_params(cfg, 7 + 2 ** 32, 8)
    assert not np.array_equal(np.asarray(low["lm_head"]["q"]),
                              np.asarray(high["lm_head"]["q"]))
    again = llama_family.build_params(cfg, 7, 8)
    assert np.array_equal(np.asarray(low["lm_head"]["q"]),
                          np.asarray(again["lm_head"]["q"]))
    q = np.asarray(low["layers"][0]["wq"]["q"]).astype(np.int32)
    assert q.min() >= -127 and abs(q.mean()) < 2.0 and 60 < q.std() < 85
