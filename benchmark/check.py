"""The comparison that decides ``correct``.

The served path returns tokens, not logits, so the test is made on the
plain reference's own logits: the reference runs once over each
sampled request's prompt plus the tokens that were SERVED for it
(teacher-forced), and at every generated position the served token's
reference logit must lie within a margin of the reference's largest.
Greedy decoding on sound arithmetic picks the reference's best token
or one that near-ties it; arithmetic in a lower precision picks tokens
the reference ranks well below its best.

The sample is drawn from the seed among the requests the window
finished, with the longest of them in it.  The margin
(``check.gap_limit`` of the configuration file) is set from measured
readings, given in PERF.md: above the largest gap sound runs showed,
below the smallest the 4-bit control showed.
"""

from __future__ import annotations

import numpy as np


def sample(good, seed: int, count: int):
    """``count`` finished requests: the longest, then a seeded draw."""
    if not good:
        return []
    ordered = sorted(good, key=lambda r: -(len(r.request.prompt)
                                           + len(r.future.tokens)))
    rest = ordered[1:]
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 41])
    picks = rng.permutation(len(rest))[:max(0, count - 1)]
    return [ordered[0]] + [rest[i] for i in sorted(picks)]


def gaps_of(logits: np.ndarray, served) -> np.ndarray:
    """How far below the reference's best each served token lies."""
    served = np.asarray(served)
    return logits.max(-1) - logits[np.arange(len(served)), served]


def served_against_reference(cell, good, seed: int):
    """``[(short name, what it is, value, limit)]``: the numbers
    compared, each beside its limit."""
    spec = cell.config["check"]
    chosen = sample(good, seed, spec["sample"])
    if not chosen:
        return [("compared", "requests available to compare", 1, 0)]
    sequences, spans = [], []
    for record in chosen:
        prompt = np.asarray(record.request.prompt, np.int32)
        tokens = np.asarray(record.future.tokens, np.int32)
        sequences.append(np.concatenate([prompt, tokens]))
        # Position p's logits predict token p + 1.
        spans.append((len(prompt) - 1, len(prompt) + len(tokens) - 1))
    weights = cell.builder.ReferenceWeights(cell.config, seed)
    logits = cell.reference.run(cell.config, weights, sequences, spans)
    gaps = np.concatenate([gaps_of(l, r.future.tokens)
                           for l, r in zip(logits, chosen)])
    lengths = [len(s) for s in sequences]
    print(f"check: reference over {len(chosen)} requests "
          f"(lengths {lengths}), {len(gaps)} served tokens: "
          f"{100.0 * float((gaps == 0).mean()):.2f} % are the "
          f"reference's own argmax; gap mean {gaps.mean():.5f} "
          f"p99 {np.quantile(gaps, 0.99):.5f} max {gaps.max():.5f}",
          flush=True)
    verdicts = [("mean_gap", "mean gap of the served tokens below the "
                 "reference's best logit", float(gaps.mean()),
                 spec["mean_gap_limit"])]
    if "gap_limit" in spec:
        # Not every configuration can hold the widest gap to a limit:
        # where experts are routed, a near-tie between the second and
        # third expert flips under rounding and moves one token's
        # logits by whole units in sound runs too (PERF.md section 2).
        verdicts.append(("widest_gap", "widest gap of a served token "
                         "below the reference's best logit",
                         float(gaps.max()), spec["gap_limit"]))
    return verdicts
