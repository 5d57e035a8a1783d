import json
import pathlib

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    if run.trace is None:
        return None
    x = run.xplane
    loops = x.matching(run.trace["ops"], SPEC["loop_pattern"])
    programs = x.matching(run.trace["modules"], SPEC["serving_programs"])
    # A serving program that holds no decode loop is its steps in
    # line: the program itself is what its steps cost.
    unrolled = [duration for _, start, duration in programs
                if not x.inside(loops, [(start, start + duration)])]
    # Whichever kind most of the span's serving programs are: a stray
    # run of the other kind (a chunk cut by the span's edge, a chunk
    # that carried no slice) does not move the reading.
    runs = unrolled if len(unrolled) > len(programs) - len(unrolled) \
        else [duration for _, _, duration in loops]
    if not runs:
        return None
    steps = run.cell.config["serving"]["chunk_steps"]
    return run.stats.quantile(runs, 0.5) / 1e6 / steps
