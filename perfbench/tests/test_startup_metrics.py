"""The eight ``setup_*`` metrics (``perfbench/lib/startup_rows.py`` and its
readers): on hand-made rows — the cut at ``setup_s``, the union rule,
``None`` without a recorder — and in one rehearsal run of the train and of
the serve toy at ``--trace 1``.  (``test_rehearsal.py`` takes a metric's
kind from its FIRST listed cell, a train cell for these: the serve toy is
covered here.)"""
import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REH = os.path.join(HERE, "rehearsal")
sys.path.insert(0, ROOT)

from perfbench.lib import startup_rows                 # noqa: E402

METRICS = ("setup_before_program_s", "setup_program_init_s",
           "setup_program_trace_lower_s", "setup_program_compile_s",
           "setup_program_cache_load_s", "setup_programs_built",
           "setup_engine_traffic_s", "setup_outside_program_s")


def build(program, start, trace, lower, backend, source="compiled", own=1,
          **args):
    return dict(kind="program_build", program=program, start_s=start,
                end_s=start + trace + lower + backend, trace_s=trace,
                lower_s=lower, backend_s=backend, source=source, own=own,
                **args)


def span(kind, start, end, **args):
    return dict(kind=kind, start_s=start, end_s=end, **args)


ROWS = [
    span("before_program", 0.0, 9.0),
    span("package_import", 9.0, 9.5),
    build("ref", 10.0, 0.5, 0.5, 2.0, own=0),          # the benchmark's
    span("package_import", 13.0, 14.0, part="inference"),
    span("place_params", 14.1, 14.2, parent="engine_init"),
    span("engine_init", 14.0, 15.0, mode="serving"),
    # a serve() call: the widths' build, decode_step's, then traffic
    build("prefill_step", 15.5, 1.0, 1.0, 4.0, width=512),
    build("prefill_step", 21.5, 0.0, 0.0, 0.5, "kept_executable",
          width=256, bytes=1),
    span("warm_prefill_widths", 15.4, 22.0),
    build("_threefry_fold_in", 22.0, 0.01, 0.02, 0.07, own=0),
    build("decode_step", 23.0, 1.0, 0.5, 0.5, "compile_cache"),
    span("engine_traffic", 15.0, 30.0, mode="serving"),
    # after the cut: the window's serve() call and a reference built late
    span("engine_traffic", 40.5, 91.0, mode="serving"),
    build("ref_late", 92.0, 1.0, 1.0, 1.0, own=0),
]


def test_the_eight_parts_of_hand_made_rows():
    got = startup_rows.split(ROWS, 40.0)
    assert set(got) == set(METRICS)
    assert got["setup_before_program_s"] == 9.0
    assert got["setup_program_init_s"] == pytest.approx(0.5 + 1.0 + 1.0)
    assert got["setup_program_trace_lower_s"] == pytest.approx(3.5)
    assert got["setup_program_compile_s"] == pytest.approx(4.0)
    assert got["setup_program_cache_load_s"] == pytest.approx(1.0)
    assert got["setup_programs_built"] == 3
    # the call's 15 s less the builds inside it, own or not: 6 + 0.5 +
    # 0.1 + 2 (the span round the widths is no build)
    assert got["setup_engine_traffic_s"] == pytest.approx(15.0 - 8.6)
    # union: 0-9.5, 13-30 (the builds and the widths lie inside the
    # call: counted once); the benchmark's ``ref`` is outside
    assert got["setup_outside_program_s"] == pytest.approx(40.0 - 26.5)


def test_a_row_counts_if_it_began_before_the_cut():
    early = startup_rows.split(ROWS, 23.5)      # decode_step had begun
    assert early["setup_programs_built"] == 3
    assert early["setup_program_cache_load_s"] == pytest.approx(1.0)
    before = startup_rows.split(ROWS, 22.9)     # ... and had not
    assert before["setup_programs_built"] == 2
    assert before["setup_program_cache_load_s"] == pytest.approx(0.5)
    late = startup_rows.split(ROWS, 200.0)
    assert late["setup_engine_traffic_s"] == pytest.approx(6.4 + 50.5)


def test_measure_is_the_union():
    assert startup_rows.measure([]) == 0.0
    assert startup_rows.measure([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4.0
    assert startup_rows.measure([(1, 3), (0, 10)]) == 10.0


def test_by_program_keeps_a_width_apart():
    got = startup_rows.by_program([r for r in ROWS
                                   if r["kind"] == "program_build"])
    assert got["prefill_step@512"]["source"] == {"compiled": 1}
    assert got["prefill_step@256"]["source"] == {"kept_executable": 1}
    assert got["ref"]["own"] == 0 and got["decode_step"]["n"] == 1


def test_none_without_a_recorder(monkeypatch, capsys):
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.monitor.startup", None)
    record = {"end_to_end": {"setup_s": 40.0}}
    for name in METRICS:
        reader = importlib.import_module(
            f"perfbench.layer_metrics.{name}")
        assert reader.read(record) is None
    assert capsys.readouterr().out == ""


def test_one_line_a_record(monkeypatch, capsys):
    import types
    fake = types.ModuleType("deepspeed_tpu.monitor.startup")
    fake.snapshot = lambda: {
        "rows": ROWS, "clock": "process_age_s", "first_useful_s": 24.5,
        "dropped": 0, "by_kind": {"engine_init": {"n": 1, "seconds": 1.0},
                                  "program_build": {"n": 6, "seconds": 0}}}
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.monitor.startup", fake)
    record = {"end_to_end": {"setup_s": 40.0}}
    values = [startup_rows.read(record, name) for name in METRICS]
    assert values[5] == 3 and values[0] == 9.0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    line = json.loads(lines[0])
    assert line["phase"] == "startup" and line["cut_s"] == 40.0
    assert line["parts"]["setup_programs_built"] == 3
    assert line["all_builds"] == {
        "n": 5, "trace_lower_s": pytest.approx(4.53),
        "compile_s": pytest.approx(6.07), "cache_load_s": pytest.approx(1.0)}
    assert [b["program"] for b in line["builds_after_cut"]] == ["ref_late"]
    assert line["spans"] == {"engine_init": {"n": 1, "seconds": 1.0}}


# --------------------------------------------------------------------- #
# one rehearsal run a kind of runner
# --------------------------------------------------------------------- #
CELLS = [("train.tiny", "pretrain-s128"), ("serve.tiny.over",
                                           "chat-tiny-over")]


@pytest.fixture(scope="module")
def bench_json(tmp_path_factory):
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    ours = [m for m in b["per_layer"] if m["name"] in METRICS]
    assert len(ours) == 8
    for m in ours:
        assert (m["layer"], m["moves"], m["source"], m["better"]) == \
            ("Start-up", "setup_s", "host_clock", "lower")
        assert m["unit"] == ("count" if m["name"] == "setup_programs_built"
                             else "s")
        assert m["workloads"] == [w["name"] for w in b["workloads"]]
        m["workloads"] = [n for n, _ in CELLS]
    b["per_layer"] = ours
    b["paths"] = [REH]
    b["configs"] = [{"name": "gpt2-tiny", "source": "none", "reduced": [],
                     "file": os.path.join(REH, "configs", "gpt2-tiny.json"),
                     "why": "toy"}]
    b["workloads"] = [{"name": n, "config": "gpt2-tiny", "traffic": t,
                       "chips": 1, "why": "rehearsal"} for n, t in CELLS]
    for m in b["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [n for n, _ in CELLS
                              if n.split(".")[0] == m["name"].split("_")[0]]
    path = tmp_path_factory.mktemp("reh") / "BENCHMARK.json"
    path.write_text(json.dumps(b))
    return str(path)


@pytest.mark.parametrize("cell", [n for n, _ in CELLS])
def test_a_traced_rehearsal_carries_all_eight(bench_json, cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark-json", bench_json, "--workload", cell,
         "--seed", str(2 ** 31 + 7), "--seconds", "2", "--trace", "1",
         "--rehearse-on-cpu"], cwd=ROOT, env=env, text=True,
        capture_output=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()
             if l.startswith("{")]          # (the program logs to stdout)
    last = lines[-1]
    assert last["correct"] is True
    assert set(last["metrics"]) == set(METRICS)
    got = {k: v["value"] for k, v in last["metrics"].items()}
    starts = [l for l in lines if l.get("phase") == "startup"]
    assert len(starts) == 1 and lines.index(starts[0]) == len(lines) - 2
    start = starts[0]
    assert start["parts"] == pytest.approx(got)
    phase = next(l for l in lines if "setup_marks_s" in l)
    setup_s = phase["setup_s"]
    assert start["cut_s"] == setup_s
    # a NEW cache directory: everything compiled, nothing loaded
    assert got["setup_program_compile_s"] > 0
    assert got["setup_program_cache_load_s"] == 0
    assert got["setup_program_trace_lower_s"] > 0
    own = {p for p, v in start["by_program"].items() if v["own"]}
    if cell.startswith("train"):
        assert own == {"init_state", "train_step"}
        assert got["setup_programs_built"] == 2
    else:
        assert {"decode_step", "prefill_step"} < own
        assert got["setup_programs_built"] == len(own) == 3
    # the parts account for the start: none negative, their intervals'
    # sum the set-up's to the slack of the stages' own gaps
    assert all(v >= 0 for v in got.values())
    whole = sum(v for k, v in got.items() if k != "setup_programs_built")
    assert whole == pytest.approx(setup_s, rel=0.1)
    # ``imports_and_device`` is run.py's mark just after the package's
    # import (run.py's clock starts a few tens of ms into the process)
    marks = dict(phase["setup_marks_s"])
    assert got["setup_before_program_s"] == pytest.approx(
        marks["imports_and_device"], abs=0.5)
    # no build of the engines' own inside the window
    assert not [b for b in start["builds_after_cut"] if b["own"]]
    assert start["first_useful_s"] < setup_s
