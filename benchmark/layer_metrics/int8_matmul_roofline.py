import json
import pathlib
import re

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    if run.trace is None:
        return None
    pattern = re.compile(SPEC["op_pattern"])
    least = spent = 0.0
    for name, _, duration in run.trace["ops"]:
        match = pattern.match(name)
        if not match or "S(1)" in match.group(4):
            continue
        m, n, k = (int(match.group(i)) for i in (1, 2, 3))
        ops, moved = run.shapes.int8_matmul(m, k, n)
        # bf16 operations: the kernel widens the int8 tile on the chip.
        seconds, _ = run.shapes.roofline_seconds(
            ops, run.peaks["bf16_flops"], moved,
            run.peaks["hbm_bytes_per_s"])
        least += seconds
        spent += duration / 1e9
    return 100.0 * least / spent if spent else None
