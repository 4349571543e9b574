"""CPU runs of the LM cell's mesh step on four virtual devices (a 4x1 data
mesh, exchange ``full``), with the tiny LM of test_chipbench_runs.py held to
the limits of ``smollm135m_async_flat``: a sound run is correct, and a
program built with the exchange between chips left out (a local sample),
checked against the reference's global sample, is not. The device count is
fixed when JAX starts, so each run is a fresh process."""
import json
import os
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

_RUN = textwrap.dedent("""
    import contextlib, io, json, sys, time
    import jax
    import calibrate
    import test_chipbench_runs as runs
    from chipbench import runner

    assert len(jax.devices()) == 4, jax.devices()
    fault, trace = sys.argv[1], sys.argv[2] == "1"
    spec = runs._spec("lm")
    spec["cell"] = {"name": "lm_4chips", "chips": 4}
    ctx = calibrate.exchange_left_out() if fault == "exchange_local" else contextlib.nullcontext()
    with ctx:
        res = runner.run("lm_4chips", runs.SEED, 0.3, trace, root=runs.ROOT,
                         t_start=time.time(), require_chip=False, spec=spec,
                         log=io.StringIO())
    print(json.dumps(res))
""")


def _run(fault, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([HERE, os.path.join(ROOT, "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _RUN, fault, "1" if trace else "0"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_on_four_devices_is_correct():
    res = _run("none", trace=True)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["checks"]["buffer_mismatches"]["value"] == 0
    # no device plane on the CPU: the scopes read nothing
    assert set(res["metrics"]) == {"input_wait_pct", "dispatch_ms",
                                   "input_not_ready_pct", "input_convert_ms"}


def test_exchange_left_out_is_not_correct():
    res = _run("exchange_local", trace=False)
    assert not res["correct"]
    assert res["checks"]["buffer_mismatches"]["value"] > 0

