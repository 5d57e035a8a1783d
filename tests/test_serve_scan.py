"""``llama._serve_scan``, the decode scan every serving module shares:
the first-step hook alone (a scan handed its own ``step_core`` as the
first step's core is the plain scan, bit for bit), and what the callers
that do not pass the hook trace: one scan of ``num_steps`` trips with
the whole step inside it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import llama, mistral4, nemotron_h

SLOTS, VOCAB = 6, 40


def _toy_core():
    """A step with a cache to carry and logits that depend on the
    token, the position and what the cache has seen."""
    table = jax.random.normal(jax.random.PRNGKey(3), (VOCAB, VOCAB))

    def step_core(token, cache, positions, active):
        cache = cache + jnp.where(active, token[:, 0], 0)
        logits = (table[token[:, 0]]
                  + jnp.sin(positions + cache)[:, None].astype(jnp.float32)
                  * table[(cache + positions) % VOCAB])
        return logits[:, None], cache

    return step_core


def _toy_state():
    return dict(token=jnp.asarray([[1], [7], [9], [30], [2], [11]]),
                positions=jnp.asarray([4, 0, 17, 9, 3, 80], jnp.int32),
                active=jnp.asarray([True, False, True, True, True, True]),
                remaining=jnp.asarray([9, 0, 1, 3, 9, 9], jnp.int32),
                temps=jnp.asarray([0.0, 0.0, 0.9, 0.0, 0.6, 1.3]),
                tops=jnp.asarray([1.0, 1.0, 0.8, 1.0, 1.0, 0.5]))


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("num_steps", [1, 2, 6])
def test_a_first_core_that_is_the_step_core_changes_nothing(num_steps,
                                                            sampled):
    step_core, state = _toy_core(), _toy_state()
    cache = jnp.arange(SLOTS, dtype=jnp.int32)
    key = jax.random.PRNGKey(5)

    def run(first_core, eos_id):
        return jax.jit(lambda: llama._serve_scan(
            step_core, state, cache, num_steps, eos_id, sampled, key,
            first_core=first_core))()

    # A token slot 0 emits mid-chunk retires it by EOS in both.
    eos_id = int(np.asarray(run(None, -1)[0])[0, min(1, num_steps - 1)])
    plain, hooked = run(None, eos_id), run(step_core, eos_id)
    for mine, theirs in zip(jax.tree.leaves(hooked),
                            jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    tokens, counts = np.asarray(plain[0]), np.asarray(plain[1])
    assert tokens.shape == (SLOTS, num_steps)
    assert counts[1] == 0 and counts[2] == 1 and counts[5] == num_steps
    assert counts[0] == min(2, num_steps)


# --- what the callers without the hook trace ---------------------------- #


def _equations(jaxpr, scans=()):
    """``(equation, the scans it lies in)`` over a jaxpr and every
    jaxpr its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn, scans
        inner = scans + (eqn,) if eqn.primitive.name == "scan" else scans
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inner)


def _makes_logits(eqn, slots, vocab):
    return eqn.primitive.name == "dot_general" and any(
        out.aval.shape in ((slots, vocab), (slots, 1, vocab))
        for out in eqn.outvars)


def _state(slots, table):
    return dict(token=jnp.zeros((slots, 1), jnp.int32),
                positions=jnp.zeros((slots,), jnp.int32),
                active=jnp.ones((slots,), bool),
                remaining=jnp.ones((slots,), jnp.int32),
                temps=jnp.zeros((slots,)), tops=jnp.ones((slots,)),
                adapter_ids=jnp.zeros((slots,), jnp.int32),
                tables=jnp.zeros((slots, table), jnp.int32))


def _llama_mixed(steps):
    config = llama.CONFIGS["tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    pool = llama.init_paged_cache(config, 20, 16)
    return jax.make_jaxpr(lambda: llama.serve_chunk_mixed(
        params, _state(3, 8), pool, jnp.zeros((1, 32), jnp.int32),
        jnp.int32(1), jnp.int32(0), steps, config)), 3, config.vocab_size


def _llama_paged(steps):
    config = llama.CONFIGS["tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    pool = llama.init_paged_cache(config, 20, 16)
    return jax.make_jaxpr(lambda: llama.serve_chunk_paged(
        params, _state(3, 8), pool, steps, config)), 3, config.vocab_size


def _nemotron_mixed(steps):
    config = nemotron_h.CONFIGS["nemotron_tiny"]
    params = nemotron_h.init_params(config, jax.random.PRNGKey(0))
    pool = nemotron_h.init_paged_cache(config, 20, 16, slots=3)
    return jax.make_jaxpr(lambda: nemotron_h.serve_chunk_mixed(
        params, _state(3, 8), pool, jnp.zeros((1, 32), jnp.int32),
        jnp.int32(1), jnp.int32(0), steps, config)), 3, config.vocab_size


def _mistral4_mixed(steps, layers=2):
    config = dataclasses.replace(mistral4.CONFIGS["mistral4_tiny"],
                                 n_layers=layers)
    params = mistral4.init_params(config, jax.random.PRNGKey(0))
    pool = mistral4.init_paged_cache(config, 20, 16)
    return jax.make_jaxpr(lambda: mistral4.serve_chunk_mixed(
        params, _state(3, 8), pool, jnp.zeros((1, 32), jnp.int32),
        jnp.int32(1), jnp.int32(0), steps, config)), 3, config.vocab_size


def _serving_scans(trace, slots, vocab):
    """``(lengths of the scans that hold a step's head, heads outside
    every scan)`` of a traced serving program."""
    scans, outside = {}, 0
    for eqn, inside in _equations(trace().jaxpr):
        if not _makes_logits(eqn, slots, vocab):
            continue
        if inside:
            scans[id(inside[0])] = inside[0].params["length"]
        else:
            outside += 1
    return sorted(scans.values()), outside


@pytest.mark.parametrize("program", [_llama_mixed, _llama_paged,
                                     _nemotron_mixed],
                         ids=lambda f: f.__name__.strip("_"))
def test_without_the_hook_the_whole_chunk_is_one_scan(program):
    """Five steps: one scan of five trips holds the step's head, and
    no head is computed outside it (no slice asks for logits)."""
    assert _serving_scans(*program(5)) == ([5], 0)


def test_with_the_hook_the_first_step_stands_before_a_shorter_scan():
    assert _serving_scans(*_mistral4_mixed(5)) == ([4], 1)
    assert _serving_scans(*_mistral4_mixed(1)) == ([], 1)


def test_the_experts_see_a_mixed_slice_only_beside_the_first_steps_rows():
    """Three layers, a slice of 32 tokens, 3 slots: outside the scan the
    held experts (``moe_experts``, a jit of its own) are called with
    ``T + S`` rows in every layer but the last and with the ``S`` rows
    of the slots in the last, never with a slice's ``T`` alone; the
    scan's body calls them with ``S`` rows a layer."""
    trace, slots, _ = _mistral4_mixed(4, layers=3)
    rows = {False: [], True: []}
    for eqn, inside in _equations(trace().jaxpr):
        if eqn.params.get("name") == "moe_experts":
            rows[bool(inside)].append(eqn.invars[0].aval.shape[0])
    assert rows[False] == [32 + slots, 32 + slots, slots]
    assert rows[True] == [slots] * 3
