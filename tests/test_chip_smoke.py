"""``chip_smoke.py`` wiring, the compile-cache placement rule, and the
attention path tags — everything about the chip bring-up that a CPU
can check.  The script's real run needs a TPU; here its rehearsal mode
exercises the same code at a tiny size with the kernels interpreted."""

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from aiko_services_tpu.obs import compiles

REPO = pathlib.Path(__file__).resolve().parent.parent
SMOKE = str(REPO / "chip_smoke.py")


def _run(args, env_overrides, timeout):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    return subprocess.run([sys.executable, SMOKE, *args], cwd=str(REPO),
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_rehearsal_exits_zero_on_cpu(tmp_path):
    """The whole script — kernel checks, the wire path, the checks on
    what was served — at a tiny size, labelled as a rehearsal; its
    cache goes where the environment says, not into the checkout."""
    cache = tmp_path / "cache"
    proc = _run(["--rehearsal"],
                {"JAX_COMPILATION_CACHE_DIR": str(cache)}, 600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    assert "REHEARSAL" in proc.stdout
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result == {"ok": True, "rehearsal": True,
                      "device": {"platform": "cpu", "kind": "cpu",
                                 "count": 1}}
    assert "decode=kernel prefill=kernel" in proc.stdout
    assert f"compile cache {cache}" in proc.stdout
    assert any(cache.iterdir()), "nothing was cached where the "\
        "environment placed the cache"


def test_no_arguments_without_a_chip_fails_fast():
    """No TPU: non-zero within seconds, the platform it found named,
    and no result line."""
    proc = _run([], {"JAX_PLATFORMS": "cpu"}, 120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stdout and "'cpu'" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_environment_placement_beats_engine_argument(tmp_path,
                                                     monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, an engine constructed with
    ``compilation_cache_dir="/elsewhere"`` leaves the cache where the
    environment put it."""
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    placed = str(tmp_path / "placed")
    monkeypatch.setenv(compiles.CACHE_DIR_ENV, placed)
    with compiles.persistent_cache(str(tmp_path / "rig")) as in_use:
        assert in_use == placed
        server = PagedContinuousServer(
            config_name="tiny", slots=2, max_seq=64,
            compilation_cache_dir=str(tmp_path / "elsewhere"))
        assert jax.config.jax_compilation_cache_dir == placed
        assert server.compilation_cache_dir == placed
    assert not (tmp_path / "elsewhere").exists()


def test_entry_point_default_is_the_fixed_in_checkout_path():
    """Unset, the entry points use one path resolved from the package's
    location — and importing the package enabled nothing."""
    assert compiles.default_cache_dir() == str(REPO / ".jax_cache")
    probe = ("import os, sys; "
             "from aiko_services_tpu.obs import compiles; "
             "import aiko_services_tpu; "
             "assert 'JAX_COMPILATION_CACHE_DIR' not in os.environ; "
             "print(compiles.entry_point_cache()); "
             "print(os.environ['JAX_COMPILATION_CACHE_DIR']); "
             "print('jax' in sys.modules)")
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", probe], cwd="/",
                          env=dict(env, PYTHONPATH=str(REPO)),
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(REPO / ".jax_cache")] * 2 \
        + ["False"]


# --------------------------------------------------------------------------- #
# The path tags follow the dispatch predicate


def _wide_head_config(monkeypatch):
    """A config whose heads are wider than the kernels' lane axis."""
    import dataclasses

    from aiko_services_tpu.models import llama
    config = dataclasses.replace(llama.CONFIGS["tiny"], d_model=512,
                                 n_heads=2, n_kv_heads=1)
    assert config.head_dim == 256
    monkeypatch.setitem(llama.CONFIGS, "tiny_wide_heads", config)
    return "tiny_wide_heads"


@pytest.mark.parametrize("mode", ["kernel", "interpret"])
def test_path_tags_follow_the_dispatch_predicate(monkeypatch, mode):
    """On the kernel mode a ``head_dim > 128`` config is served by the
    reference — and says so: the tag is the dispatch's own answer at
    the server's geometry, not the mode's."""
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    monkeypatch.setenv("AIKO_DECODE_ATTENTION", mode)
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", mode)
    narrow = PagedContinuousServer(config_name="tiny", slots=2,
                                   max_seq=64).stats()
    assert narrow["decode_attention_path"] == "kernel"
    assert narrow["prefill_attention_path"] == "kernel"
    # ... and how a step's int8 scales are appended: by the kernel
    # where blocks fill lane rows of scales (8 kv heads), by the
    # scatter where they do not (2), not at all in a float pool.
    assert narrow["decode_scale_append_path"] == "none"
    for name, path in (("tiny_tp", "kernel"), ("tiny", "scatter")):
        assert PagedContinuousServer(
            config_name=name, slots=2, max_seq=64,
            quantize_kv=True).stats()["decode_scale_append_path"] == path
    wide = PagedContinuousServer(
        config_name=_wide_head_config(monkeypatch), slots=2,
        max_seq=64).stats()
    assert wide["decode_attention_path"] == "reference"
    assert wide["prefill_attention_path"] == "reference"


def test_wide_heads_dispatch_runs_the_reference(monkeypatch):
    """The same predicate drives the dispatch: with the kernel forced,
    a wide-head decode step traces no pallas_call."""
    import jax.numpy as jnp

    from aiko_services_tpu.models import llama
    monkeypatch.setenv("AIKO_DECODE_ATTENTION", "kernel")
    name = _wide_head_config(monkeypatch)
    config = llama.CONFIGS[name]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    pool = llama.init_paged_cache(config, 5, 16)
    tables = jnp.arange(1, 5, dtype=jnp.int32).reshape(1, 4)
    jaxpr = jax.make_jaxpr(
        lambda t, p: llama._decode_core_paged(
            params, t, p, tables, jnp.asarray([3], jnp.int32), config))(
        jnp.zeros((1, 1), jnp.int32), pool)
    assert "pallas_call" not in str(jaxpr)
