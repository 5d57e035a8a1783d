"""The trace reduction on a hand-built event list with known answers
and on a small recorded trace of a real run on the chip; quantile,
due-time and lateness arithmetic on fixed inputs."""

import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import cells, stats, traffic, xplane  # noqa: E402

#: (name, start_ns, duration_ns): two overlapping ops, a gap of 30, a
#: lone op, a gap of 100, an op that runs past the window.
EVENTS = [("fusion.1", 0, 50), ("custom-call.2", 40, 30),
          ("fusion.1", 100, 20), ("copy.3", 220, 100)]


def test_busy_is_the_union_of_intervals():
    assert xplane.busy_intervals(EVENTS) == [(0, 70), (100, 120),
                                             (220, 320)]
    assert xplane.busy_ns(EVENTS) == 190
    assert xplane.busy_ns(xplane.clip(EVENTS, (0, 300))) == 170


def test_gaps_longest_first_inside_the_window():
    assert xplane.idle_gaps(EVENTS, (0, 300)) == [(120, 220), (70, 100)]
    assert xplane.idle_gaps(EVENTS, (-10, 330)) == [
        (120, 220), (70, 100), (-10, 0), (320, 330)]


def test_time_by_name_and_pattern():
    totals = xplane.total_by_name(EVENTS)
    assert list(totals)[0] == "copy.3"
    assert totals["fusion.1"] == (2, 70)
    assert xplane.durations_of(EVENTS, r"^fusion") == [50, 20]
    assert xplane.extent(EVENTS) == (0, 320)


def test_gaps_are_charged_to_the_host_span_that_covers_them():
    host = {"engine steps": [("sync", 60, 45), ("admission", 105, 120)]}
    gaps = xplane.idle_gaps(EVENTS, (0, 300))
    ranked = xplane.attribute_gaps(gaps, host)
    assert ranked[0] == ["admission", 100 / 1e9]
    assert ranked[1] == ["sync", 30 / 1e9]
    assert xplane.attribute_gaps(gaps, {}) == [["unattributed",
                                                130 / 1e9]]


def test_quantiles_due_times_and_lateness():
    assert stats.quantile([], 0.9) is None
    assert stats.quantile([5.0], 0.9) == 5.0
    assert stats.quantile(range(11), 0.5) == 5
    assert stats.quantile(range(11), 0.9) == 9
    assert stats.quantile([1, 2, 3, 4], 0.5) == 2.5
    assert stats.due_times([0.5, 0.25, 1.0], start=10.0) == [
        10.5, 10.75, 11.75]
    assert stats.lateness_ms([1.0, 2.0], [1.002, 1.999]) == [
        pytest.approx(2.0), 0.0]


def test_every_seed_offers_the_same_work_with_other_contents():
    mix = dict(loop="open", rate_per_s=4.0, population=32,
               prompt=dict(dist="lognormal", median=512, sigma=0.8,
                           min=32, max=2048),
               output=dict(dist="lognormal", median=96, sigma=0.7,
                           min=16, max=384, quantum=8))
    streams = []
    for seed in (1, 2 ** 31 + 9):
        source = traffic.Mix(mix, 1000, seed).requests()
        streams.append([next(source) for _ in range(32)])
    sizes = [sorted(len(r.prompt) for r in s) for s in streams]
    outs = [sorted(r.max_new for r in s) for s in streams]
    assert sizes[0] == sizes[1] and outs[0] == outs[1]
    # The order is the mix's, the token values are the seed's.
    assert [len(r.prompt) for r in streams[0]] == \
        [len(r.prompt) for r in streams[1]]
    assert [r.due for r in streams[0]] == [r.due for r in streams[1]]
    assert not (streams[0][0].prompt[:20]
                == streams[1][0].prompt[:20]).all()
    other = traffic.Mix(dict(mix, order_seed=5), 1000, 1).requests()
    assert [len(next(other).prompt) for _ in range(32)] != \
        [len(r.prompt) for r in streams[0]]
    assert all(r.max_new % 8 == 0 for r in streams[0])
    # A block of the population lasts exactly population / rate.
    assert streams[0][-1].due == pytest.approx(8.0)
    assert streams[1][-1].due == pytest.approx(8.0)


def test_documents_are_asked_again_after_the_lag():
    mix = dict(loop="closed", clients=2, population=8,
               prompt=dict(dist="uniform", min=4, max=8),
               output=dict(dist="uniform", min=8, max=8, quantum=8),
               sharing=dict(documents=dict(dist="uniform", min=100,
                                           max=200),
                            askings=3, lag=2))
    source = traffic.Mix(mix, 1000, 3).requests()
    requests = [next(source) for _ in range(30)]
    seen = {}
    for request in requests:
        key = request.prompt[:request.shared].tobytes()
        seen.setdefault(key, []).append(request.index)
    full = [indexes for indexes in seen.values() if len(indexes) == 3]
    assert len(full) >= 6
    for first, second, third in full[1:]:
        assert second - first >= 3 and third - second >= 3


RECORDED = ROOT / "benchmark" / "testdata" / \
    "mistral7b_chat_60ms.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    """60 ms of the traced run of mistral7b.chat on one TPU v5e (my
    chip run, PR 23), cut down by a scratch tool to the first chip's
    operation and program lines and the benchmark's clock marker."""
    return xplane.load(str(RECORDED))


def test_recorded_trace_has_one_chip_with_ops_and_programs(recorded):
    assert list(recorded["devices"]) == [0]
    device = recorded["devices"][0]
    assert len(device["ops"]) > 500 and device["modules"]
    assert all(name.startswith("jit_") for name, _, _ in device["modules"])
    low, high = xplane.extent(device["ops"])
    busy = xplane.busy_ns(xplane.clip(device["modules"], (low, high)))
    assert 0 < busy <= high - low


def test_recorded_trace_names_the_kernels_the_readers_look_for(recorded):
    import json
    ops = recorded["devices"][0]["ops"]
    specs = {name: json.loads((ROOT / "benchmark" / "layer_metrics"
                               / f"{name}.json").read_text())
             for name in ("decode_attn_roofline", "int8_matmul_roofline",
                          "decode_step_ms")}
    attention = xplane.durations_of(
        ops, specs["decode_attn_roofline"]["op_pattern"])
    matmuls = xplane.matching(
        ops, specs["int8_matmul_roofline"]["op_pattern"])
    assert len(attention) >= 16 and len(matmuls) >= 100
    # One decode attention call costs about 2.4 ms whatever it reads.
    assert 1.5e6 < sorted(attention)[len(attention) // 2] < 3.5e6
    shares = xplane.self_times(ops)
    top = next(iter(shares))
    assert top.startswith("closed_call custom-call bf16[32,32,128]")
    assert sum(shares.values()) <= xplane.extent(ops)[1] \
        - xplane.extent(ops)[0]


def test_int8_matmul_roofline_of_the_recorded_trace_is_a_share(recorded):
    from benchmark import peaks, shapes

    class Run:
        trace = {"ops": recorded["devices"][0]["ops"]}
    Run.shapes, Run.peaks = shapes, peaks.of("TPU v5 lite")
    reader = ROOT / "benchmark" / "layer_metrics" / "int8_matmul_roofline.py"
    namespace = {"__file__": str(reader)}
    exec(compile(reader.read_text(), str(reader), "exec"), namespace)
    share = namespace["read"](Run)
    assert 40.0 < share < 100.0


def test_label_shortens_an_instruction_to_kind_and_shape():
    text = ("%closed_call.407 = bf16[32,32,128]{2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[32,160]{1,0} %x), custom_call_target=\"t\"")
    assert xplane.label(text) == "closed_call custom-call bf16[32,32,128]"
    assert xplane.label("%while.4 = (s32[]{:T(128)}, s32[32,1]) while(") \
        == "while (s32[]"
    assert xplane.label("jit_serve_chunk_mixed(123)") == \
        "jit_serve_chunk_mixed(123)"


# --- decode_step_ms: loops if there are loops, else the program --------- #

MS = 1_000_000
LOOP = "%while.4 = (s32[], bf16[64,4096]{1,0}) while(...)"
PAGED = "jit_serve_chunk_paged(1234)"
MIXED = "jit_serve_chunk_mixed(5678)"
PREFILL = "jit_prefill_append_paged(9)"
#: Three chunks of a program that scans (a loop inside each program
#: run), and one standalone prefill program, which is no serving step.
LOOPED = {"ops": [(LOOP, 1 * MS, 30 * MS), (LOOP, 41 * MS, 34 * MS),
                  (LOOP, 81 * MS, 32 * MS)],
          "modules": [(PAGED, 0, 38 * MS), (MIXED, 40 * MS, 40 * MS),
                      (PREFILL, 80 * MS, 1 * MS), (PAGED, 80 * MS, 35 * MS)]}
#: A mixed program whose steps stand in line: no loop anywhere.
UNROLLED = {"ops": [("%fusion.3 = bf16[320,4096]{1,0} fusion(...)", 0,
                     2 * MS)],
            "modules": [(MIXED, 0, 33 * MS), (MIXED, 34 * MS, 35 * MS),
                        (PREFILL, 70 * MS, 50 * MS),
                        (MIXED, 120 * MS, 34 * MS)]}
#: Mostly unrolled, one chunk that scanned (a chunk without a slice):
#: the span is read as what most of its programs are, in line.
BOTH = {"ops": [(LOOP, 71 * MS, 28 * MS)],
        "modules": UNROLLED["modules"][:2] + [(PAGED, 70 * MS, 29 * MS),
                                              (MIXED, 120 * MS, 34 * MS)]}
#: Loops in all but a run cut short by the span's edge: read as before.
MOSTLY_LOOPED = {"ops": LOOPED["ops"],
                 "modules": LOOPED["modules"] + [(MIXED, 120 * MS, 3 * MS)]}
NEITHER = {"ops": UNROLLED["ops"], "modules": [(PREFILL, 0, 50 * MS)]}


@pytest.mark.parametrize("trace, steps, wanted", [
    (LOOPED, 8, 32 / 8), (LOOPED, 2, 32 / 2), (UNROLLED, 2, 34 / 2),
    (BOTH, 2, 34 / 2), (MOSTLY_LOOPED, 8, 32 / 8), (NEITHER, 2, None),
    (None, 2, None)])
def test_decode_step_ms_reads_loops_or_the_loopless_program(trace, steps,
                                                            wanted):
    read = cells._import(ROOT / "benchmark" / "layer_metrics"
                         / "decode_step_ms.py").read
    cell = types.SimpleNamespace(
        config={"serving": {"chunk_steps": steps}})
    run = types.SimpleNamespace(trace=trace, xplane=xplane, stats=stats,
                                cell=cell)
    got = read(run)
    assert got == (pytest.approx(wanted) if wanted else None)
    if trace in (LOOPED, MOSTLY_LOOPED):
        # With loops in every serving program the reading is what it
        # was before the second case existed: the median loop.
        loops = xplane.durations_of(trace["ops"], r"^%while\.\d+ = ")
        assert got == stats.quantile(loops, 0.5) / 1e6 / steps
