import json
import pathlib

from benchmark import latent_shapes

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    z = run.sizes
    blocks = run.traced.get("decode_blocks_read", 0)
    if run.trace is None or not blocks or "rank" not in z:
        return None
    kernel_ns = sum(run.xplane.durations_of(run.trace["ops"],
                                            SPEC["op_pattern"]))
    if not kernel_ns:
        return None
    block = run.cell.config["serving"]["block_size"]
    rows = run.traced.get("decode_steps", 0) * run.cell.traffic["slots"]
    ops, moved = latent_shapes.latent_decode(
        blocks * block, rows, z["heads"], z["rank"], z["rope"])
    least, _ = run.shapes.roofline_seconds(
        ops * z["layers"], run.peaks["bf16_flops"], moved * z["layers"],
        run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns / 1e9)
