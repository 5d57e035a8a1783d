import json
import pathlib

from benchmark import latent_shapes

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    z = run.sizes
    visits = run.traced.get("prefill_key_blocks", 0)
    if run.trace is None or not visits or "rank" not in z:
        return None
    kernel_ns = sum(run.xplane.durations_of(run.trace["ops"],
                                            SPEC["op_pattern"]))
    if not kernel_ns:
        return None
    ops, moved = latent_shapes.latent_prefill(
        visits, run.cell.config["serving"]["block_size"], SPEC["q_tile"],
        z["heads"], z["rank"], z["rope"])
    # A slice's logits are not asked for, so its last layer's attention
    # feeds nothing and is not computed (only its rows are cached).
    layers = z["layers"] - 1
    least, _ = run.shapes.roofline_seconds(
        ops * layers, run.peaks["bf16_flops"], moved * layers,
        run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns / 1e9)
