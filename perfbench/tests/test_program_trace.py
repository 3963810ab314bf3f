"""perfbench/lib/program_trace.py: the scope, span and gap arithmetic on
hand-made tables, the wire reader against ``jax.profiler.ProfileData`` on
the recorded traces, and the whole reduction on one small recorded chip
trace (``data/toy_train_scoped.xplane.pb``: two optimizer steps of the
rehearsal's two-layer toy on one v5e chip, recorded by this benchmark's
own train runner on the program as PR 24 leaves it; the HLO protos of
its ``/host:metadata`` plane taken out to keep it small)."""
import os

import pytest

from perfbench.lib import program_trace as pt
from perfbench.lib import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "data", "toy_train_scoped.xplane.pb")
UNSCOPED = os.path.join(HERE, "data", "tiny_train.xplane.pb")
MS = 1e6        # ns


# ------------------------------------------------------------------ #
# Scope paths
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("tf_op,want", [
    ("jit(train_step)/fwd_bwd/jvp()/while/body/closed_call/attn/dot_general:",
     (("fwd_bwd", "attn"), False, False)),
    ("jit(train_step)/fwd_bwd/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp/_gelu_fwd_kernel/pallas_call:",
     (("fwd_bwd", "mlp"), True, True)),
    # a scope wrapped in the transforms applied under it
    ("jit(train_step)/fwd_bwd/transpose(jvp(embed))/scatter-add:",
     (("fwd_bwd", "embed"), True, False)),
    # a repeat of the scope before it is one scope
    ("jit(train_step)/fwd_bwd/transpose(fwd_bwd)/jvp(lm_head)/mul:",
     (("fwd_bwd", "lm_head"), True, False)),
    ("jit(train_step)/optimizer/flatten/concatenate:",
     (("optimizer", "flatten"), False, False)),
    ("jit(decode_step)/while/body/attn/kv_write/dynamic_update_slice:",
     (("attn", "kv_write"), False, False)),
    # only the first of several op_names counts; no scope, no path
    ("jit(train_step)/reshape;jit(train_step)/optimizer/flatten/x:",
     ((), False, False)),
    ("jit(train_step)/jit(_threefry_fold_in)/slice:", ((), False, False)),
    # a name that merely contains a scope's name is not that scope
    ("jit(f)/attention_fn/flatten_dims/dot:", ((), False, False)),
    ("", ((), False, False)), (None, ((), False, False)),
])
def test_scope_of(tf_op, want):
    assert pt.scope_of(tf_op) == want


def _plane(ops, modules, tf_ops):
    """A device plane from (metadata id, start, dur) ops, (name, start,
    dur) program executions and {metadata id: (HLO line, tf_op)}."""
    meta = {mid: (name, {"tf_op": tf}) for mid, (name, tf) in tf_ops.items()}
    mods = []
    for i, (name, s, d) in enumerate(modules):
        meta[1000 + i] = (name, {})
        mods.append((1000 + i, s, d, None))
    return {"lines": {xplane.OPS_LINE: [(m, s, d, None) for m, s, d in ops],
                      xplane.MODULES_LINE: mods},
            "metadata": meta, "stat_names": {}}


def test_device_self_seconds_by_program_scope_and_phase():
    # one train step of 100 ms: a while of 60 ms holding a forward fusion
    # (20), a backward one (25) and a recomputed kernel (5); then the
    # optimizer: flatten 10, kernel 8, unflatten 6; then an unscoped copy 4
    tf = {1: ("%while.1 = ...", "jit(train_step)/fwd_bwd/jvp()/while:"),
          2: ("%fusion.1 = ...", "jit(train_step)/fwd_bwd/jvp()/while/body/attn/dot:"),
          3: ("%fusion.2 = ...", "jit(train_step)/fwd_bwd/transpose(jvp())/while/body/mlp/dot:"),
          4: ("%_ln_fwd_kernel.1 = ...", "jit(train_step)/fwd_bwd/transpose(jvp())/while/body/"
              "checkpoint/rematted_computation/attn/pallas_call:"),
          5: ("%concatenate.1 = ...", "jit(train_step)/optimizer/flatten/concatenate:"),
          6: ("%_fused_adam_kernel.1 = ...", "jit(train_step)/optimizer/kernel/pallas_call:"),
          7: ("%slice.9 = ...", "jit(train_step)/optimizer/unflatten/slice:"),
          8: ("%copy.3 = ...", "")}
    ops = [(1, 0, 60 * MS), (2, 5 * MS, 20 * MS), (3, 26 * MS, 25 * MS),
           (4, 52 * MS, 5 * MS), (5, 60 * MS, 10 * MS), (6, 70 * MS, 8 * MS),
           (7, 78 * MS, 6 * MS), (8, 90 * MS, 4 * MS)]
    plane = _plane(ops, [("jit_train_step(77)", 0, 100 * MS)], tf)
    got = pt.device_self_seconds(plane)
    ms = {k: round(v * 1e3, 6) for k, v in got.items()}
    assert ms == {
        ("jit_train_step", ("fwd_bwd",), False, False): 10.0,   # the while's own
        ("jit_train_step", ("fwd_bwd", "attn"), False, False): 20.0,
        ("jit_train_step", ("fwd_bwd", "mlp"), True, False): 25.0,
        ("jit_train_step", ("fwd_bwd", "attn"), True, True): 5.0,
        ("jit_train_step", ("optimizer", "flatten"), False, False): 10.0,
        ("jit_train_step", ("optimizer", "kernel"), False, False): 8.0,
        ("jit_train_step", ("optimizer", "unflatten"), False, False): 6.0,
        ("jit_train_step", (), False, False): 4.0}
    tr = {"scoped": got}
    split = pt.train_split_ms(tr, 1)
    assert {k: round(v, 6) for k, v in split.items()} == {
        "fwd": 30.0, "bwd": 30.0, "recompute": 5.0, "optimizer": 24.0,
        "assembly": 16.0, "kernel": 8.0, "norm": 0.0,
        "coverage": round(100 * 84 / 88, 6)}
    # per step
    assert pt.train_split_ms(tr, 2)["optimizer"] == pytest.approx(12.0)
    assert pt.unscoped_ops(plane) == [("copy", pytest.approx(0.004))]
    # nothing scoped, or no step: nothing to report
    assert pt.train_split_ms({"scoped": {("p", (), False, False): 1.0}}, 1) == {}
    assert pt.train_split_ms(tr, 0) == {}


def test_serve_split_reads_kv_write_per_decode_execution():
    tf = {1: ("%dus.1 = ...", "jit(decode_step)/while/body/attn/kv_write/dynamic_update_slice:"),
          2: ("%_pattn_kernel.1 = ...", "jit(decode_step)/while/body/attn/attend/pallas_call:"),
          3: ("%copy.1 = ...", ""),
          4: ("%dus.7 = ...", "jit(prefill_step)/while/body/attn/kv_write/dynamic_update_slice:")}
    ops = [(1, 0, 10 * MS), (2, 10 * MS, 30 * MS), (3, 40 * MS, 20 * MS),
           (4, 100 * MS, 8 * MS),
           (1, 200 * MS, 12 * MS), (2, 212 * MS, 30 * MS), (3, 242 * MS, 18 * MS)]
    mods = [("jit_decode_step(5)", 0, 60 * MS), ("jit_prefill_step(6)", 100 * MS, 8 * MS),
            ("jit_decode_step(5)", 200 * MS, 60 * MS)]
    plane = _plane(ops, mods, tf)
    tr = {"scoped": pt.device_self_seconds(plane), "spans": {}, "window_s": 0.26,
          "whole_executions": {"jit_decode_step": 2.0, "jit_prefill_step": 1.0}}
    got = pt.serve_split(tr)
    assert got["kv_write_ms_per_iter"] == pytest.approx(11.0)   # prefill's not counted
    # an execution the window's edge cut to a tenth counts as a tenth
    tr["whole_executions"]["jit_decode_step"] = 1.1
    assert pt.serve_split(tr)["kv_write_ms_per_iter"] == pytest.approx(20.0)
    assert got["coverage"] == pytest.approx(100 * 90 / 128)
    assert set(got) == {"kv_write_ms_per_iter", "coverage"}


# ------------------------------------------------------------------ #
# Host spans
# ------------------------------------------------------------------ #
def _spans(**rows):
    return {k: sorted(v, key=lambda r: r[0]) for k, v in rows.items()}


def test_serve_split_from_host_spans():
    # two decode spans of 100 ms: tables 1 + dispatch 2 + fetch 90 +
    # advance 3; after the first an emit of 4 and two admits (5, 1; the
    # first 250 ms late) and a prefill of 150; after the second an emit of 2
    def decode(t):
        return {"decode": (t, 100 * MS, {"iteration": 1}),
                "decode_tables": (t + 1 * MS, 1 * MS, {}),
                "decode_dispatch": (t + 2 * MS, 2 * MS, {}),
                "decode_fetch": (t + 4 * MS, 90 * MS, {}),
                "decode_advance": (t + 95 * MS, 3 * MS, {})}
    a, b = decode(0), decode(300 * MS)
    spans = _spans(
        **{k: [a[k], b[k]] for k in a},
        emit=[(100 * MS, 4 * MS, {}), (400 * MS, 2 * MS, {"finished": "7"})],
        admit=[(105 * MS, 5 * MS, {"late_ms": 250.0, "admitted": 1}),
               (262 * MS, 1 * MS, {"late_ms": 3.5, "admitted": 0})],
        prefill=[(110 * MS, 150 * MS, {"slots": 1})])
    got = pt.serve_split({"scoped": {}, "whole_executions": {},
                          "spans": spans, "window_s": 0.5})
    assert got == {
        "prefill_stall_share": pytest.approx(30.0),
        "host_ms_per_iter": pytest.approx((6 + 4 + 5 + 1 + 6 + 2) / 2),
        "arrival_late_max_ms": 250.0}
    # a trace without the program's spans (the parent's) reports nothing
    assert pt.serve_split({"scoped": {}, "whole_executions": {},
                           "spans": {}, "window_s": 0.5}) == {}


def test_host_spans_keep_args_and_skip_device_planes():
    host = {"lines": {"python3": [(1, 5.0, 10.0, None), (2, 1.0, 2.0, None),
                                  (3, 20.0, 1.0, None)]},
            "metadata": {1: ("decode", {}), 2: ("admit", {}),
                         3: ("decode_once", {})}, "stat_names": {}}
    dev = {"lines": {"XLA Ops": [(1, 0.0, 1.0, None)]},
           "metadata": {1: ("decode", {})}, "stat_names": {}}
    got = pt.host_spans({"/host:CPU": host, "/device:TPU:0": dev})
    assert got == {"decode": [(5.0, 10.0, {})], "admit": [(1.0, 2.0, {})]}


def test_gaps_are_named_by_the_innermost_program_span():
    busy = [[0, 10 * MS], [20 * MS, 30 * MS], [100 * MS, 110 * MS]]
    spans = [("decode", 5 * MS, 20 * MS), ("decode_fetch", 12 * MS, 6 * MS),
             ("prefill", 40 * MS, 50 * MS)]
    gaps = xplane.label_gaps(busy, spans, "no_program_span")
    assert gaps == [("prefill", 70 * MS), ("decode_fetch", 10 * MS)]
    assert pt.gap_totals({"gaps": [("a", 1.0), ("b", 3.0), ("a", 0.5)]}) == \
        {"b": 3.0, "a": 1.5}


# ------------------------------------------------------------------ #
# The wire reader and the recorded traces
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("path", [SCOPED, UNSCOPED])
def test_wire_reader_agrees_with_profile_data(path):
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    mine = pt.read_xspace(path)
    theirs = xplane.read_planes(path)
    for pname, lines in theirs.items():
        for lname, events in lines.items():
            got = mine[pname]["lines"][lname]
            assert len(got) == len(events), (pname, lname)
            meta = mine[pname]["metadata"]
            for (mid, s, d, _), (name, s2, d2) in zip(got[:200], events[:200]):
                assert meta[mid][0] == name
                assert s == pytest.approx(s2, abs=1.0)
                assert d == pytest.approx(d2, abs=1.0)


def test_unscoped_recorded_trace_reports_nothing():
    """PR 23's trace: the program had no scopes and only the runner's
    spans.  Every new metric must read ``None`` there, as on the parent."""
    if not os.path.exists(UNSCOPED):
        pytest.skip("no recorded trace in this checkout")
    tr = pt.reduce(UNSCOPED)
    assert tr["programs"] == {"jit_train_step": 2}
    assert pt.seconds(tr["scoped"], scope="*") == 0.0
    assert pt.seconds(tr["scoped"]) == pytest.approx(
        xplane.union_ns(xplane.read_planes(UNSCOPED)["/device:TPU:0"]
                        ["XLA Ops"]) / 1e9, rel=2e-3)
    assert pt.train_split_ms(tr, 2) == {}
    assert set(pt.serve_split(tr)) == set()
    assert set(tr["spans"]) == {"train_batch"}      # the runner's own


def test_scoped_recorded_trace_reduces():
    if not os.path.exists(SCOPED):
        pytest.skip("no recorded trace in this checkout")
    assert os.path.getsize(SCOPED) < 1 << 20
    tr = pt.reduce(SCOPED)
    assert tr["programs"] == {"jit_train_step": 2}
    assert tr["whole_executions"]["jit_train_step"] == pytest.approx(2.0, rel=0.01)
    split = pt.train_split_ms(tr, 2)
    assert set(split) == {"fwd", "bwd", "recompute", "optimizer", "assembly",
                          "kernel", "norm", "coverage"}
    assert all(v > 0 for v in split.values()), split
    assert split["recompute"] < split["bwd"]
    assert split["assembly"] + split["kernel"] + split["norm"] <= \
        split["optimizer"] * (1 + 1e-9)
    assert 50.0 < split["coverage"] <= 100.0
    # the three phases are the scoped time: nothing is counted twice
    sc = tr["scoped"]
    phases = split["fwd"] + split["bwd"] + split["optimizer"]
    others = sum(pt.seconds(sc, scope=s) for s in ("health_tap", "grad_sync"))
    assert (phases / 1e3 * 2 + others) == pytest.approx(
        pt.seconds(sc, scope="*"), rel=1e-6)
    # and all of it is the device's busy time
    busy = xplane.union_ns(xplane.read_planes(SCOPED)["/device:TPU:0"]
                           ["XLA Ops"]) / 1e9
    assert pt.seconds(sc) == pytest.approx(busy, rel=2e-3)
    # the optimizer kernel the existing metrics find by name sits under
    # the kernel scope, all of it
    adam = xplane.self_time_by_name(
        xplane.read_planes(SCOPED)["/device:TPU:0"]["XLA Ops"]
    )["_fused_adam_kernel"] / 1e9
    assert pt.seconds(sc, scope="kernel") >= adam > 0
    # the program's spans, once per traced step, on the profiler's clock
    for name in ("data_prep", "step_dispatch", "step_log"):
        assert len(tr["spans"][name]) == 2, name
        assert [a["step"] for _, _, a in tr["spans"][name]] == \
            sorted(a["step"] for _, _, a in tr["spans"][name])
    assert len(tr["spans"]["train_batch"]) == 4     # the runner's and ours
    assert tr["window_s"] > 0 and tr["gaps"]
    assert {label for label, _ in tr["gaps"]} <= \
        set(pt.SPANS) | {"no_program_span"}
