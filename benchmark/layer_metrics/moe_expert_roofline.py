import json
import pathlib
import re

from benchmark import hybrid_shapes

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    z = run.sizes
    if run.trace is None or not z.get("experts") or "latent" not in z:
        return None
    x, ops = run.xplane, run.trace["ops"]
    skip = re.compile(SPEC["skip_pattern"])
    rows_of = re.compile(SPEC["result_rows"])
    kinds = ((SPEC["up_operand"].format(**z), hybrid_shapes.expert_up),
             (SPEC["down_operand"].format(**z),
              hybrid_shapes.expert_down))
    loops = [(start, start + duration) for _, start, duration
             in x.matching(ops, SPEC["loop_pattern"])]
    decoding = {id(event) for event in x.inside(ops, loops)}
    # Choices that fell on held experts, of a layer's rows: counted by
    # the program for the decode steps of the traced span; for a
    # prefill slice the routes' expectation (rows x top-k x the share
    # of the experts that is held).
    steps = run.traced.get("decode_steps", 0) * z["expert_layers"]
    counted = run.traced.get("moe_pairs_here", 0) / steps if steps \
        else None
    expected = z["top_k"] * z["experts"] / z["experts_total"]
    least = spent = 0.0
    for event in ops:
        name, _, duration = event
        rows = rows_of.match(name)
        if skip.match(name) or not rows:
            continue
        rows = int(rows.group(1))
        for operand, needs in kinds:
            if operand not in name:
                continue
            pairs = rows * expected
            if id(event) in decoding and counted is not None:
                pairs = counted
            needed, moved = needs(rows, pairs, z["experts"], z["latent"],
                                  z["f"])
            seconds, _ = run.shapes.roofline_seconds(
                needed, run.peaks["bf16_flops"], moved,
                run.peaks["hbm_bytes_per_s"])
            least += seconds
            spent += duration / 1e9
            break
    return 100.0 * least / spent if spent else None
