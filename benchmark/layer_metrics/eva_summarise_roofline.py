import json
import pathlib

from benchmark import eva_shapes

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    rows = run.traced.get("tokens_committed", 0)
    z = run.sizes
    if run.trace is None or not rows or not z.get("chunk"):
        return None
    kernel_ns = sum(run.xplane.durations_of(run.trace["ops"],
                                            SPEC["op_pattern"]))
    if not kernel_ns:
        return None
    int8 = run.cell.config["serving"]["kv_dtype"] == "int8"
    ops, moved = eva_shapes.chunk_summaries(
        z, chunks=rows / z["chunk"], kv_bytes=1 if int8 else 2)
    least, _ = run.shapes.roofline_seconds(
        ops * z["layers"], run.peaks["bf16_flops"], moved * z["layers"],
        run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns / 1e9)
