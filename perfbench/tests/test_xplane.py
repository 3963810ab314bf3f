"""perfbench/lib/xplane.py: the arithmetic on hand-made events, and the
reader on one small recorded trace (``data/tiny_train.xplane.pb``: two
optimizer steps of a two-layer toy on one v5e chip, recorded by this
benchmark's own train runner)."""
import glob
import os
import shutil

import pytest

from perfbench.lib import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000.0


def test_union_counts_overlap_once():
    ev = [("a", 0, 10 * US), ("b", 5 * US, 10 * US), ("c", 30 * US, 5 * US)]
    assert xplane.union_ns(ev) == 20 * US
    assert xplane.busy_intervals(ev) == [[0, 15 * US], [30 * US, 35 * US]]


def test_self_time_takes_children_out_of_their_container():
    # a while of 100 us holding two fusions (30 + 20) and a kernel (10),
    # then a free-standing kernel of 5
    ev = [("while.3", 0, 100 * US), ("fusion.1", 10 * US, 30 * US),
          ("fusion.2", 50 * US, 20 * US), ("_fused_adam_kernel", 80 * US, 10 * US),
          ("_fused_adam_kernel.2", 200 * US, 5 * US)]
    got = xplane.self_time_by_name(ev)
    assert got == {"while": 40 * US, "fusion": 50 * US,
                   "_fused_adam_kernel": 15 * US}
    assert sum(got.values()) == xplane.union_ns(ev)


def test_ops_are_split_by_the_program_they_ran_in():
    mods = [("jit_decode_step(123)", 0, 50 * US), ("jit_prefill_step(9)", 60 * US, 30 * US),
            ("jit_decode_step(123)", 100 * US, 50 * US)]
    ops = [("_pattn_kernel", 10 * US, 5 * US), ("_pattn_kernel.1", 65 * US, 20 * US),
           ("_pattn_kernel", 110 * US, 5 * US), ("copy", 55 * US, 1 * US)]
    got = xplane.split_by_module(ops, mods)
    assert sorted(got) == ["", "jit_decode_step", "jit_prefill_step"]
    assert len(got["jit_decode_step"]) == 2 and len(got["jit_prefill_step"]) == 1
    assert got[""] == [("copy", 55 * US, 1 * US)]


def test_base_name_and_collective_pattern():
    assert xplane.base_name("%fusion.12.3") == "fusion"
    assert xplane.base_name("_pattn_kernel") == "_pattn_kernel"
    assert xplane.base_name(
        "_fused_adam_kernel.1 = (bf16[8,128]{1,0}) custom-call(f32[1,8] %x)"
    ) == "_fused_adam_kernel"
    for name in ("all-reduce", "reduce-scatter.4", "all-gather-start.1",
                 "collective-permute-done"):
        assert xplane.COLLECTIVE.match(xplane.base_name(name)), name
    assert not xplane.COLLECTIVE.match("fusion")


def test_gaps_are_named_by_the_innermost_host_span():
    busy = [[0, 10 * US], [20 * US, 30 * US], [100 * US, 110 * US]]
    spans = [("serve", 0, 200 * US), ("decode_once", 8 * US, 14 * US)]
    gaps = xplane.label_gaps(busy, spans, "host")
    assert gaps == [("serve", 70 * US), ("decode_once", 10 * US)]
    assert xplane.label_gaps(busy, [], "host")[0] == ("host", 70 * US)


def test_recorded_trace_reduces(tmp_path):
    recorded = os.path.join(HERE, "data", "tiny_train.xplane.pb")
    if not os.path.exists(recorded):
        pytest.skip("no recorded trace in this checkout")
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    shutil.copy(recorded, d / "tiny.xplane.pb")
    r = xplane.reduce_trace(str(tmp_path), ("data", "train_batch"), "host", 1)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert abs(sum(r["op_seconds"].values()) - r["busy_s"]) < 1e-6
    assert r["op_seconds"].get("_fused_adam_kernel", 0) > 0
    assert any("train_step" in m for m in r["modules"])
    in_step = r["op_seconds_by_module"]["jit_train_step"]
    assert in_step["_fused_adam_kernel"] == r["op_seconds"]["_fused_adam_kernel"]
    b = xplane.breakdown(r)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
