import json
import pathlib

from benchmark import hybrid_shapes

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    z = run.sizes
    if run.trace is None or not z.get("mamba_layers"):
        return None
    shape = dict(z, slots=run.cell.traffic["slots"])
    calls = run.xplane.durations_of(run.trace["ops"],
                                    SPEC["op_pattern"].format(**shape))
    if not calls:
        return None
    ops, moved = hybrid_shapes.ssm_update(
        shape["slots"], z["mamba_heads"], z["mamba_hd"], z["state"],
        z["groups"])
    # float32 on the vector unit: no published peak, and the bytes
    # bound it by far; the roofline is the memory one.
    least = moved / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least * len(calls) / (sum(calls) / 1e9)
