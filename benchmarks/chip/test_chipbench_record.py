"""CPU checks of what a traced run hands the per-layer metrics: device time by
scope and by collective of a constructed trace and step text, each metric's
reading of a constructed record (and nothing without its input), the cells
each metric is given, and the step's own outputs of a tiny cell."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import program_trace as pt  # noqa: E402
from chipbench import runner  # noqa: E402

MS = 1_000_000

# instruction, opcode written in the step's text, op_name (None: no metadata).
# A TPU names an instruction after its op_name, so only the opcode tells a
# collective; a tuple shape and a tiled layout stand before some opcodes.
STEP = [
    ("while.1", "f32[4]{0} while", "jit(step)/train/while"),
    ("fusion.2", "f32[4]{0} fusion", "jit(step)/train/jvp(net)/dot_general"),
    ("psum_invariant.3", "f32[512]{0:T(512)} all-reduce",
     "jit(step)/train/transpose(jvp())/while"),
    ("psum.7", "(f32[4]{0:T(512)S(1)}, s32[]{:T(128)}) all-reduce-start",
     "jit(step)/grad_allreduce/psum"),
    ("all-reduce-done.8", "f32[4]{0} all-reduce-done", None),
    ("all_to_all.9", "s32[4,1,2048]{2,1,0:T(1,128)S(1)} all-to-all",
     "jit(step)/shard_map/exchange/all_to_all"),
    ("copy.5", "f32[4]{0} copy", None),
]
HLO = "HloModule jit_step, entry_computation_layout={()->()}\n\n" + "\n".join(
    f"  %{i} = {op}(f32[4]{{0}} %p)" + (f", metadata={{op_name=\"{n}\"}}" if n else "")
    for i, op, n in STEP)
# (instruction, start ms, end ms) on each of two devices; the all-reduce runs
# inside the while, and the second device's all-to-all takes twice as long
DEVICE_OPS = {
    "/device:TPU:0": [("while.1", 0, 4), ("fusion.2", 0.5, 1), ("psum_invariant.3", 1, 2),
                      ("psum.7", 4, 4.5), ("all-reduce-done.8", 5, 6),
                      ("all_to_all.9", 6, 7), ("copy.5", 7, 8)],
    "/device:TPU:1": [("while.1", 0, 4), ("fusion.2", 0.5, 1), ("psum_invariant.3", 1, 2),
                      ("psum.7", 4, 4.5), ("all-reduce-done.8", 5, 6),
                      ("all_to_all.9", 6, 8), ("copy.5", 8, 9)],
}
HOST = [("fetch", 0, 0.2 * MS), ("dispatch", 0.2 * MS, 0.5 * MS),
        ("wait", 0.5 * MS, 5 * MS), ("fetch", 5 * MS, 5.2 * MS),
        ("dispatch", 5.2 * MS, 5.5 * MS), ("wait", 5.5 * MS, 10 * MS)]


def _reduced():
    devices = {k: [(a * MS, b * MS, i, "jit_step", i) for i, a, b in v]
               for k, v in DEVICE_OPS.items()}
    return pt.reduce(devices, HOST, steps=2, hlo_text=HLO)


def test_collectives_are_found_by_opcode():
    assert pt.hlo_collectives(HLO) == {
        "psum_invariant.3": "all-reduce", "psum.7": "all-reduce",
        "all-reduce-done.8": "all-reduce", "all_to_all.9": "all-to-all"}
    assert pt.hlo_collectives(None) == {}


def test_constructed_trace_reduces_to_hand_counted_scopes_and_collectives():
    red = _reduced()
    # per device and 2 steps: the all-reduce inside the while (1 ms), its
    # start (0.5) and done (1); the all-to-all 1 ms and 2 ms on the two devices
    assert dict(red["device_collectives"]) == pytest.approx(
        {"all-reduce": 2.5 / 2, "all-to-all": 1.5 / 2})
    scopes = dict(red["device_scopes"])
    # the while's own 2.5 ms, the fusion's 0.5 and the all-reduce's 1 in it
    assert scopes["train"] == pytest.approx(4.0 / 2)
    assert scopes["grad_allreduce"] == pytest.approx(0.5 / 2)
    assert scopes["exchange"] == pytest.approx(1.5 / 2)
    assert scopes["unscoped"] == pytest.approx(2.0 / 2)
    assert red["scopes_busy_s"] == pytest.approx(red["busy_s"])


def test_ops_that_are_no_collectives_give_no_collective_time():
    devices = {"/device:TPU:0": [(0, MS, "copy.5", "jit_step", "copy.5")]}
    red = pt.reduce(devices, HOST, steps=2, hlo_text=HLO)
    assert red["device_collectives"] == []


RECORD = {
    "scopes": {"train": 40.0, "unscoped": 8.0, "buffer_update": 2.0,
               "buffer_sample": 1.5, "augment": 0.5},
    "collectives": {"all-reduce": 3.0, "all-to-all": 0.5},
    "counters": {"input": {"batches": 20, "not_ready": 5, "convert_s": 0.04,
                           "wait_s": 0.1},
                 "step": {"loss": 2.5}},
}
EMPTY = {"scopes": {}, "collectives": {}, "counters": {"input": {}, "step": {}}}
READINGS = {
    "buffer_update_ms": 2.0,
    "buffer_sample_ms": 2.0,
    "unscoped_device_pct": 100.0 * 8.0 / 52.0,
    "input_not_ready_pct": 25.0,
    "input_convert_ms": 2.0,
    "collective_ms": 3.5,
}


def _metric(name):
    return runner.load_module(os.path.join(HERE, "metrics", name + ".py"))


@pytest.mark.parametrize("name", sorted(READINGS))
def test_metric_reads_a_constructed_record(name):
    assert _metric(name).read(RECORD) == pytest.approx(READINGS[name])
    assert _metric(name).read(EMPTY) is None


def test_unscoped_share_of_a_step_without_scopes_reads_none():
    assert _metric("unscoped_device_pct").read(dict(EMPTY, scopes={"unscoped": 5.0})) is None


def test_find_cell_gives_a_cell_only_its_listed_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        got = [m["name"] for m in runner.find_cell(ROOT, cell["name"])["per_layer"]]
        want = [m["name"] for m in bench["per_layer"] if cell["name"] in m["workloads"]]
        assert got == want and got
    incremental = runner.find_cell(ROOT, "resnet50_incremental")["per_layer"]
    assert "buffer_update_ms" not in {m["name"] for m in incremental}
    listed = [{"name": "a", "workloads": ["x"]}, {"name": "b", "workloads": ["y"]},
              {"name": "c"}]
    assert [m["name"] for m in runner.cell_metrics(listed, "x")] == ["a", "c"]


def test_step_outputs_of_a_tiny_cell_are_read_after_the_window():
    import test_chipbench_runs as runs

    seen = []
    res = runs._run("cnn", hook=seen.append)
    assert res["correct"], res["checks"]
    out = seen[0].counters()
    # the buffer is full all through the window
    tr = runs.TINY_IMAGES
    assert out["buffer_fill"] == tr["buckets"] * tr["slots_per_bucket"]
    assert out["loss"] > 0 and all(isinstance(v, float) for v in out.values())
