import json
import pathlib
import re

from benchmark import latent_shapes

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    z = run.sizes
    if run.trace is None or not z.get("experts") or "rank" not in z:
        return None
    ops = run.trace["ops"]
    skip = re.compile(SPEC["skip_pattern"])
    rows_of = re.compile(SPEC["result_rows"])
    names = dict(z, rows_down=z["experts"] * z["f"])
    up = SPEC["up_operand"].format(**names)
    downs = [operand.format(**names) for operand in SPEC["down_operands"]]
    # Choices that fell on held experts, of a layer's rows: counted by
    # the program for the decode steps of the traced span (a call on
    # exactly the slots' rows, in a loop or in line); for a prefill
    # slice, alone or with a step's rows behind it, the routes'
    # expectation.
    decode_rows = run.cell.traffic["slots"]
    steps = run.traced.get("decode_steps", 0) * z["expert_layers"]
    counted = run.traced.get("moe_pairs_here", 0) / steps if steps \
        else None
    expected = z["top_k"] * z["experts"] / z["experts_total"]
    least = spent = 0.0
    for name, _, duration in ops:
        rows = rows_of.match(name)
        if skip.match(name) or not rows:
            continue
        rows = int(rows.group(1))
        pairs = rows * expected
        if rows == decode_rows and counted is not None:
            pairs = counted
        matrices = name.count(up)
        if matrices:
            needed, moved = latent_shapes.swiglu_up(
                rows, pairs, z["experts"], z["d"], z["f"],
                matrices=min(matrices, 2))
        elif any(operand in name for operand in downs):
            needed, moved = latent_shapes.swiglu_down(
                rows, pairs, z["experts"], z["d"], z["f"])
        else:
            continue
        seconds, _ = run.shapes.roofline_seconds(
            needed, run.peaks["bf16_flops"], moved,
            run.peaks["hbm_bytes_per_s"])
        least += seconds
        spent += duration / 1e9
    return 100.0 * least / spent if spent else None
