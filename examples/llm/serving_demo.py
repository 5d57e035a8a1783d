"""LLM serving demo: continuous batching + speculative decoding.

Run (CPU or TPU):

    python examples/llm/serving_demo.py

Shows the two serving modes the framework adds over the reference's
shell-out-to-Ollama design (reference examples/llm/elements_llm.py):

1. **Continuous batching** — requests of different lengths admitted into
   one resident decode batch; outputs exactly equal per-request greedy.
2. **Speculative decoding** — a small draft accelerates a larger target
   with identical greedy output.
"""

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def main():
    if os.environ.get("SERVING_DEMO_CPU"):
        # Force the CPU backend on a machine whose accelerator another
        # process holds (a chip belongs to one process at a time).
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax  # noqa: E402
    from aiko_services_tpu.models import llama  # noqa: E402
    from aiko_services_tpu.models.speculative import (  # noqa: E402
        speculative_generate,
    )
    from aiko_services_tpu.orchestration.continuous import (  # noqa: E402
        ContinuousBatchingServer, DecodeRequest,
    )

    rng = np.random.default_rng(0)

    print("== continuous batching ==")
    server = ContinuousBatchingServer(config_name="tiny", slots=4,
                                      max_seq=128, chunk_steps=8)
    requests = [
        DecodeRequest(f"req{i}",
                      rng.integers(1, 900, n).astype(np.int32), new)
        for i, (n, new) in enumerate(
            [(8, 12), (21, 6), (5, 16), (13, 8), (30, 10), (11, 4)])]
    for request in requests:
        server.submit(request)
    started = time.perf_counter()
    finished = server.run_until_drained()
    elapsed = time.perf_counter() - started
    total = sum(len(r.tokens) for r in finished)
    print(f"  {len(finished)} requests, {total} tokens through 4 slots "
          f"in {elapsed:.2f}s")
    for request in finished:
        print(f"  {request.request_id}: {request.tokens}")

    print("== speculative decoding ==")
    import dataclasses
    config = llama.CONFIGS["small"]
    draft_config = dataclasses.replace(llama.CONFIGS["tiny"],
                                       vocab_size=config.vocab_size)
    target = llama.init_params(config, jax.random.PRNGKey(1))
    draft = llama.init_params(draft_config, jax.random.PRNGKey(2))
    prompt = rng.integers(1, config.vocab_size, 16).astype(np.int32)
    tokens, stats = speculative_generate(
        target, draft, prompt, 24, config, draft_config, k=4)
    print(f"  random draft (acceptance floor): {len(tokens)} tokens; "
          f"{stats}")
    # Self-draft = acceptance ceiling (trained draft models land
    # between the two; output is exact either way).
    tokens2, stats2 = speculative_generate(
        target, target, prompt, 24, config, config, k=4)
    assert list(tokens2) == list(tokens)   # exactness: same greedy seq
    print(f"  self draft (acceptance ceiling): {stats2}")


if __name__ == "__main__":
    main()
