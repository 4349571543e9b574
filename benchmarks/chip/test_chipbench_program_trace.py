"""CPU checks of the reduction by the program's own marks
(``chipbench/program_trace.py``) and of ``trace_program.py``: device time per
named scope of a constructed trace, the unscoped share, idle gaps labelled by
the program's spans with ``trace.py``'s labels unchanged, the join of op
events to scopes through a compiled step's HLO text, and the input counters
of a tiny cell's window, and the refusal of a step text without its scopes,
as a compile cache made before them hands it out."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import program_trace as pt  # noqa: E402
from chipbench import trace  # noqa: E402
from repro.obs.scopes import scope_of  # noqa: E402

MS = 1_000_000


OPS = [  # (instruction, start ms, end ms, op_name in the step's text or None)
    ("while.1", 0, 4, "jit(step)/train/while"),
    ("fusion.2", 0.5, 1.5, "jit(step)/train/jvp(net)/dot_general"),
    ("fusion.3", 2, 3, "jit(step)/transpose(jvp(train))/mul"),
    ("scatter.4", 4, 5, "jit(step)/buffer_update/scatter"),
    ("copy.5", 5, 6, None),
    ("fusion.6", 7, 9, "jit(step)/buffer_sample/gather"),
    ("fusion.7", 9, 9.5, "jit(step)/exchange/all_to_all"),
]
HLO = "HloModule jit_step, entry_computation_layout={()->()}\n\n" + "\n".join(
    f"  %{i} = f32[4]{{0}} op(), metadata={{op_name=\"{n}\"}}" if n
    else f"  %{i} = f32[4]{{0}} copy(f32[4]{{0}} %p)" for i, _, _, n in OPS)


def _constructed():
    """Two steps on one device, all inside the step's execution. A while loop
    (train) holds two body ops; a buffer_update scatter; a copy no scope owns;
    a sample and an exchange."""
    ops = [(a * MS, b * MS, i, "jit_step", i) for i, a, b, _ in OPS]
    host = [("fetch", 0, 0.2 * MS), ("dispatch", 0.2 * MS, 0.5 * MS),
            ("wait", 0.5 * MS, 6.5 * MS), ("fetch", 6.5 * MS, 7 * MS),
            ("dispatch", 7 * MS, 7.2 * MS), ("wait", 7.2 * MS, 10 * MS),
            ("input.convert", 6 * MS, 6.9 * MS), ("input.wait", 6.5 * MS, 6.6 * MS),
            ("input.fetch", 9.6 * MS, 10 * MS)]
    return {"/device:TPU:0": ops}, host


def test_scopes_add_up_to_the_busy_time():
    devices, host = _constructed()
    red = pt.reduce(devices, host, steps=2, hlo_text=HLO)
    scopes = dict(red["device_scopes"])
    # the while's own time is what its body ops leave of it: 4 - 1 - 1
    assert scopes["train"] == pytest.approx(4.0 / 2)
    assert scopes["buffer_update"] == pytest.approx(1.0 / 2)
    assert scopes["unscoped"] == pytest.approx(1.0 / 2)
    assert scopes["buffer_sample"] == pytest.approx(2.0 / 2)
    assert scopes["exchange"] == pytest.approx(0.5 / 2)
    assert red["scopes_busy_s"] == pytest.approx(red["busy_s"])
    assert pt.scope_ms(red, ("buffer_update",)) == pytest.approx(0.5)
    assert pt.scope_ms(red, pt.SAMPLE_SCOPES) == pytest.approx(1.25)
    assert pt.scope_ms(red, ("augment",)) is None
    assert pt.unscoped_pct(red) == pytest.approx(100.0 * 1.0 / 8.5)
    scoped = {name: s for name, s, _ in red["device_ops_scoped"]}
    assert scoped["copy.5"] == "unscoped" and scoped["fusion.3"] == "train"


def test_idle_gaps_by_program_span_leave_the_benchmark_labels_unchanged():
    devices, host = _constructed()
    red = pt.reduce(devices, host, steps=2, hlo_text=HLO)
    bench = [h for h in host if h[0] in trace.HOST_SPANS]
    alone = trace.reduce_events({k: [(o[4], o[0], o[1]) for o in v]
                                 for k, v in devices.items()}, bench)
    assert red["idle_gaps"] == alone["idle_gaps"]
    assert red["idle_pct"] == pytest.approx(alone["idle_pct"])
    # gaps 6-7 ms (input.convert covers 0.9 of it) and 9.5-10 ms (input.fetch)
    assert red["idle_gaps_program"] == [["input.convert", pytest.approx(0.001)],
                                        ["input.fetch", pytest.approx(0.0005)]]
    assert red["idle_gaps"] == [["wait", pytest.approx(0.001)],
                                ["wait", pytest.approx(0.0005)]]
    assert red["program_span_s"]["input.convert"] == pytest.approx(0.0009)


def test_exclusive_times_of_overlapping_intervals_are_their_union():
    iv = [(0, 10), (2, 4), (3, 12), (20, 21)]
    out = pt.exclusive_times(iv, ["a", "b", "c", "d"], 0, 100)
    # a: 0-2; b: 2-3; c: 3-12; d: 20-21 (latest started wins)
    assert out == pytest.approx({"a": 2e-9, "b": 1e-9, "c": 9e-9, "d": 1e-9})
    assert sum(out.values()) == pytest.approx(13e-9)
    assert pt.exclusive_times(iv, ["a", "b", "c", "d"], 5, 8) == pytest.approx(
        {"c": 3e-9})


def _compiled_step_text():
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    def step(w, x):
        with jax.named_scope("train"):
            g = jax.grad(loss)(w, x)
        with jax.named_scope("optimizer"):
            return w - 0.1 * g

    return jax.jit(step).lower(jnp.ones((8, 8)), jnp.ones((4, 8))).compile().as_text()


def test_ops_join_their_scope_through_the_compiled_text():
    hlo = _compiled_step_text()
    module, names = pt.hlo_op_names(hlo)
    assert module == "jit_step" and names
    instr = {scope_of(v): k for k, v in names.items()}
    train, opt = instr["train"], instr["optimizer"]
    ops = [(0, 1, train, None, train),             # no module line in the trace
           (1, 2, opt, "jit_step", opt),
           (2, 3, train, "jit_other", train),      # same name, another program
           (3, 4, "fusion.999", "jit_step", "fusion.999")]  # not in the text
    assert pt.op_scopes(ops, hlo) == ["train", "optimizer", "unscoped", "unscoped"]
    assert pt.op_scopes(ops, None) == ["unscoped"] * 4


def test_op_events_take_the_module_whose_execution_holds_them():
    ops = [(5, 6, "%fusion.1 = f32[4]{0} fusion(...)"), (12, 13, "copy.2"),
           (30, 31, "fusion.1")]
    modules = [(10, 20, "jit_other"), (0, 9, "jit_step")]
    assert pt._with_modules(ops, modules) == [
        (5, 6, "fusion.1", "jit_step", "%fusion.1 = f32[4]{0} fusion(...)"),
        (12, 13, "copy.2", "jit_other", "copy.2"),
        (30, 31, "fusion.1", None, "fusion.1")]


def test_a_program_without_scopes_reads_nothing():
    devices, host = _constructed()
    red = pt.reduce(devices, host, steps=2, hlo_text=None)
    assert red["device_scopes"] == [["unscoped", pytest.approx(8.5 / 2)]]
    assert pt.unscoped_pct(red) is None
    assert pt.scope_ms(red, ("buffer_update",)) is None
    assert pt.reduce(devices, [h for h in host if h[0].startswith("input.")], 2) is None


def test_input_readings_of_two_snapshots():
    a = {"batches": 10, "not_ready": 2, "convert_s": 0.5, "wait_s": 0.1}
    b = {"batches": 30, "not_ready": 7, "convert_s": 0.9, "wait_s": 0.3}
    assert pt.input_readings(a, b, 4.0) == pytest.approx(
        {"input_not_ready_pct": 25.0, "input_convert_ms": 20.0,
         "input_wait_program_pct": 5.0})
    assert pt.input_readings(a, a, 4.0) == {}
    assert pt.input_readings(None, b, 4.0) == {}


def test_trace_program_reads_the_input_counters_of_a_tiny_cell():
    import test_chipbench_runs as runs
    import trace_program

    res = trace_program.measure("cnn", runs.SEED, 0.3, root=runs.ROOT,
                                require_chip=False, spec=runs._spec("cnn"),
                                trace_seconds=0.2)
    assert res["compiles_in_window"] == 0
    for part in ("untraced", "traced"):
        assert res[part]["steps"] > 0
        assert 0.0 <= res[part]["input_not_ready_pct"] <= 100.0
        assert res[part]["input_convert_ms"] > 0.0
        assert 0.0 <= res[part]["input_wait_program_pct"] <= 100.0
    slow = res["untraced"]["slow_steps"]
    assert slow["median_ms"] > 0 and len(slow["slowest"]) == min(slow["n"], 10)
    # no device plane on the CPU: nothing to reduce by scope
    assert "breakdown" not in res
    assert res["span_cost_us"]["profiler_off"] > 0


def test_slow_steps_split_each_long_interval_by_span():
    import trace_program

    comps = [1.0, 2.0, 5.0, 6.0]  # the third interval takes 3 s
    spans = {"fetch": [0.1] * 5, "dispatch": [0.2] * 5, "wait": [0.5, 0.5, 2.5, 0.5]}
    out = trace_program.slow_steps(0.0, comps, spans)
    assert out["median_ms"] == pytest.approx(1000.0)
    assert out["n"] == 1 and out["lost_s"] == pytest.approx(2.0)
    assert out["slowest"] == [[2, 5.0, pytest.approx(3000.0), pytest.approx(100.0),
                               pytest.approx(200.0), pytest.approx(2500.0)]]


def test_require_scopes_by_cell():
    hlo = _compiled_step_text()  # train and optimizer, no buffer stage
    pt.require_scopes(hlo, rehearsal=False)
    with pytest.raises(pt.MissingScopes, match="buffer_sample, buffer_update"):
        pt.require_scopes(hlo, rehearsal=True)
    with pytest.raises(pt.MissingScopes, match="train"):
        pt.require_scopes("HloModule jit_step\n", rehearsal=False)


_STALE = textwrap.dedent("""
    import sys
    import jax
    import jax.numpy as jnp
    from chipbench import program_trace as pt

    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      sys.argv[2] == "1")

    def make(scoped):
        def step(w, x):  # the same program, with and without its scope
            if scoped:
                with jax.named_scope("train"):
                    return jnp.tanh(x @ w).sum()
            return jnp.tanh(x @ w).sum()
        return step

    args = (jnp.ones((8, 8)), jnp.ones((4, 8)))
    jax.jit(make(False)).lower(*args).compile()  # an entry made before scopes
    hlo = jax.jit(make(True)).lower(*args).compile().as_text()
    try:
        pt.require_scopes(hlo, rehearsal=False)
    except pt.MissingScopes:
        print("refused")
    else:
        print("scoped")
""")


@pytest.mark.parametrize("meta_in_key,expect", [(False, "refused"), (True, "scoped")])
def test_a_cached_step_without_scopes_is_caught(tmp_path, meta_in_key, expect):
    """The persistent cache keys a program without its metadata: a step
    compiled before its scopes is handed out unscoped, and refused; with
    metadata in the key (as ``trace_program.py`` sets) it compiles anew."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([HERE, os.path.join(HERE, "..", "..", "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _STALE, str(tmp_path / "cache"),
                          "1" if meta_in_key else "0"],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [expect]
