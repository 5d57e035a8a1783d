"""The benchmark's command, end to end on the CPU at a tiny size.

The cell driven here (``tiny.chat``) lives wholly under
``tests/benchmark/data/``: a benchmark file, a configuration, a traffic
mix, found by name like any other.  That is the proof that a cell is
added as files.  Three runs are started together (each a process of its
own, as the driver starts them) and every test reads their results.

What ``BENCHMARK.json`` itself must satisfy is ``test_root_file.py``.
"""

import copy
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = "tests/benchmark/data/BENCHMARK.json"
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}

sys.path.insert(0, str(ROOT))
from benchmark import cells  # noqa: E402
from tests.benchmark.listed import last_json_line  # noqa: E402

#: Breaks the timed path underneath the harness: every decode chunk's
#: tokens are altered where they are produced.
BROKEN = """
import sys
sys.path.insert(0, {root!r})
from aiko_services_tpu.orchestration import paged
sound = paged.PagedContinuousServer._serve_chunk
def altered(self, *args, **kwargs):
    tokens, counts, state = sound(self, *args, **kwargs)
    return (tokens + 1) % self.config.vocab_size, counts, state
paged.PagedContinuousServer._serve_chunk = altered
sys.argv = ["benchmark/run.py"] + {argv!r}
import runpy
runpy.run_path({run!r}, run_name="__main__")
"""


def _argv(trace, seed):
    return ["--benchmark", DATA, "--workload", "tiny.chat", "--seed",
            str(seed), "--seconds", "2", "--trace", str(trace),
            "--rehearsal"]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    run_py = str(ROOT / "benchmark" / "run.py")
    commands = {
        "plain": [sys.executable, run_py] + _argv(0, 2 ** 31 + 77),
        "traced": [sys.executable, run_py] + _argv(1, 5),
        "broken": [sys.executable, "-c", BROKEN.format(
            root=str(ROOT), argv=_argv(0, 6), run=run_py)],
    }
    started = {}
    for name, command in commands.items():
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_COMPILATION_CACHE_DIR"] = str(
            tmp_path_factory.mktemp(f"cache_{name}"))
        started[name] = subprocess.Popen(
            command, cwd=ROOT, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    results = {}
    for name, process in started.items():
        output, _ = process.communicate(timeout=300)
        results[name] = (process.returncode, output)
    return results


def _last_line(runs, name):
    code, output = runs[name]
    assert code == 0, output[-3000:]
    return last_json_line(output)


def test_rehearsal_prints_the_contract_line(runs):
    line = _last_line(runs, "plain")
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    bench = json.loads((ROOT / DATA).read_text())
    assert set(line["metrics"]) == {m["name"]
                                    for m in bench["end_to_end"]}
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    # A CPU rehearsal never prints under a device's name.
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_every_number_compared_is_printed_beside_its_limit(runs):
    _, output = runs["plain"]
    checks = [row for row in output.splitlines()
              if row.startswith("check: ") and "(limit " in row]
    assert len(checks) >= 5 and all(row.endswith(" ok")
                                    for row in checks), checks
    # Each again under a short name: the last key of the result line,
    # and the last lines of standard error.
    line = _last_line(runs, "plain")
    assert next(reversed(line)) == "checks"
    assert len(line["checks"]) == len(checks)
    last = output.strip().splitlines()[-len(checks):]
    for row, (name, compared) in zip(last, line["checks"].items()):
        assert row == (f"check {name}: {compared['value']} "
                       f"(limit {compared['limit']})")
        assert compared["value"] <= compared["limit"]


def test_traced_run_reports_the_per_layer_metrics(runs):
    line = _last_line(runs, "traced")
    bench = json.loads((ROOT / DATA).read_text())
    assert set(line) - {"breakdown"} == KEYS
    assert set(line["metrics"]) <= {m["name"]
                                    for m in bench["per_layer"]}
    assert {"batch_occupancy", "window_compiles",
            "gen_late_p90_ms"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0


def test_a_median_recorded_per_layer_is_in_the_traced_line(runs):
    # The whole-request median where it is recorded and not judged
    # (BENCHMARK.json: mistral7b.chat; here: tiny.chat).
    line = _last_line(runs, "traced")
    assert line["metrics"]["open_req_p50_ms"]["value"] > 0
    assert line["metrics"]["open_req_p50_ms"]["unit"] == "ms"


def test_an_end_to_end_metric_is_kept_to_the_cells_it_names(listed):
    chat = listed.cell("mistral7b.chat")
    moe = listed.cell("mixtral8x7b.chat")
    judged = lambda cell: {m["name"] for m in cell.end_to_end}
    recorded = lambda cell: {m["name"] for m, _, _ in cell.per_layer}
    assert "req_p50_ms" in judged(moe) - judged(chat)
    assert "open_req_p50_ms" in recorded(chat) - recorded(moe)
    assert {"setup_s", "ttft_p50_ms", "tpot_p50_ms",
            "out_tokens_per_s"} <= judged(chat) & judged(moe)


def test_broken_timed_path_is_not_correct(runs):
    line = _last_line(runs, "broken")
    assert line["correct"] is False
    assert any(compared["value"] > compared["limit"]
               for compared in line["checks"].values())
    _, output = runs["broken"]
    assert "FAIL" in output


def test_without_a_chip_nothing_is_printed():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py")]
        + _argv(0, 1)[:-1], cwd=ROOT, env=env, text=True,
        capture_output=True, timeout=120)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout


@pytest.mark.parametrize("field, value, needle", [
    ("traffic", "no_such_mix", "traffic mix"),
    ("config", "no-such-config", "configuration"),
])
def test_a_name_without_a_file_fails_loudly(tmp_path, capsys, field,
                                            value, needle):
    bench = json.loads((ROOT / DATA).read_text())
    bench["workloads"][0][field] = value
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    with pytest.raises(SystemExit) as raised:
        cells.Cell(ROOT, str(path), "tiny.chat")
    assert raised.value.code == 2
    assert needle in capsys.readouterr().out


def test_a_metric_without_a_file_fails_loudly(tmp_path, capsys):
    bench = json.loads((ROOT / DATA).read_text())
    metric = copy.deepcopy(bench["per_layer"][0])
    metric["name"] = "no_such_metric"
    bench["per_layer"].append(metric)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    with pytest.raises(SystemExit):
        cells.Cell(ROOT, str(path), "tiny.chat")
    assert "no_such_metric" in capsys.readouterr().out
