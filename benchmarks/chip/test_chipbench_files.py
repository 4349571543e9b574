"""CPU checks of the on-chip benchmark's files and arithmetic: every cell's
files are found by name, a run without a TPU fails, the FLOP functions agree
with hand counts, and the trace reduction reads a constructed trace."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import runner, stats, trace  # noqa: E402


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in _bench()["workloads"]])
def test_cell_files_are_found_by_name(workload):
    spec = runner.find_cell(ROOT, workload)
    cfg, tr = spec["config"], spec["traffic"]
    assert cfg["name"] == spec["cell"]["config"]
    for part in ("families", "entries"):
        key = "family" if part == "families" else "entry"
        assert os.path.exists(os.path.join(HERE, "chipbench", part, cfg[key] + ".py"))
    assert set(spec["limits"]) >= {"loss_gap", "grad_gap", "update_gap"}
    if tr["mode"] != "off":
        assert spec["limits"]["buffer_mismatches"] == 0
    names = {m["name"] for m in spec["end_to_end"]}
    assert {"samples_per_s", "step_ms_p90", "setup_s"} <= names
    for m in spec["per_layer"]:
        mod = runner.load_module(os.path.join(HERE, "metrics", m["name"] + ".py"))
        assert callable(mod.read)


def test_config_files_are_named_by_benchmark():
    bench = _bench()
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_run_without_a_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "resnet50_async_flat", "--seed", str(2 ** 31 + 7), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_resnet50_flops_match_a_hand_count():
    from chipbench.families import resnet

    with open(os.path.join(HERE, "configs", "resnet50-cl.json")) as f:
        cfg = json.load(f)
    # hand count at 224 px: stem 7x7x3x64 at 112x112; per stage (hw, mid, cout)
    mac = 112 * 112 * 7 * 7 * 3 * 64
    cin = 64
    for hw_in, hw, mid, cout, n in ((56, 56, 64, 256, 3), (56, 28, 128, 512, 4),
                                    (28, 14, 256, 1024, 6), (14, 7, 512, 2048, 3)):
        for b in range(n):
            h0 = hw_in if b == 0 else hw
            mac += h0 * h0 * cin * mid + hw * hw * 9 * mid * mid + hw * hw * mid * cout
            if b == 0:
                mac += hw * hw * cin * cout
            cin = cout
    mac += 2048 * 1000
    assert resnet.forward_flops(cfg) == 2 * mac
    # the published figure: about 4.1 G multiply-adds a 224 px image
    assert abs(resnet.forward_flops(cfg) / 2 - 4.1e9) < 0.05e9


def test_smollm_flops_match_a_hand_count():
    from chipbench.families import llama

    with open(os.path.join(HERE, "configs", "smollm-135m.json")) as f:
        cfg = json.load(f)
    per_layer = 576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536
    n = 30 * per_layer + 576 * 49152
    assert n == 134_479_872
    tr = {"seq_len": 2048}
    want = 2048 * (6 * n + 12 * 30 * 9 * 64 * 2048)
    assert llama.train_flops_per_row(cfg, tr) == want


def test_trace_reduction_of_a_constructed_trace():
    ms = 1_000_000
    devices = {"/device:TPU:0": [("fusion.1", 0, 4 * ms), ("conv.2", 3 * ms, 6 * ms),
                                 ("fusion.1", 8 * ms, 10 * ms)],
               "/device:TPU:1": [("fusion.1", 0, 10 * ms)]}
    host = [("fetch", 0, 1 * ms), ("dispatch", 1 * ms, 2 * ms),
            ("wait", 2 * ms, 6 * ms), ("fetch", 6 * ms, 7.5 * ms),
            ("dispatch", 7.5 * ms, 8 * ms), ("wait", 8 * ms, 10 * ms)]
    out = trace.reduce_events(devices, host)
    assert out["window_s"] == pytest.approx(0.010)
    # device 0 busy 0-6 and 8-10 ms, device 1 busy throughout: mean 9 ms
    assert out["busy_s"] == pytest.approx(0.009)
    assert out["idle_pct"] == pytest.approx(10.0)
    assert out["idle_gaps"] == [["fetch", pytest.approx(0.002)]]
    ops = dict((k, v) for k, v in out["device_ops"])
    assert ops["fusion.1"] == pytest.approx((6 + 10) * 1e-3 / 2)
    assert trace.reduce_events({}, host) is None


def test_step_time_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2], 50) == 2
