import json
import pathlib

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    blocks = run.traced.get("decode_blocks_read", 0)
    if run.trace is None or not blocks:
        return None
    kernel_ns = sum(run.xplane.durations_of(run.trace["ops"],
                                            SPEC["op_pattern"]))
    if not kernel_ns:
        return None
    serving = run.cell.config["serving"]
    steps = run.traced.get("decode_steps", 0)
    ops, moved = run.shapes.decode_attention(
        dict(run.sizes, layers=1),
        context_tokens=blocks * serving["block_size"],
        rows=steps * run.cell.traffic["slots"],
        kv_bytes=1 if serving["kv_dtype"] == "int8" else 2)
    layers = run.sizes["layers"]
    least, _ = run.shapes.roofline_seconds(
        ops * layers, run.peaks["bf16_flops"], moved * layers,
        run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns / 1e9)
