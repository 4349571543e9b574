"""Unified telemetry (repro.obs, DESIGN.md §11).

The load-bearing contract: telemetry NEVER perturbs the run. Obs-off compiles
the exact pre-obs program; obs-on adds output leaves only — the
rep_checksum / buffer_fill / loss fingerprints are bit-identical with the
switch in either position, on both backends, flat + tiered + DER++. The rest
of the file covers the host-side half (tracer, event bus, exporters, the
instrumented runtime publishers) and the two logging satellites.
"""
import json
import logging
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as obs_mod
from repro.configs.base import ObsConfig, RehearsalConfig
from repro.core import init_carry, make_cl_step
from repro.obs.events import EventBus, read_events
from repro.obs.exporters import (
    MetricsRegistry,
    MetricsWriter,
    prom_name,
    start_metrics_server,
)
from repro.obs.metrics import estimate_obs_cost, obs_keys
from repro.obs.trace import Tracer, validate_trace
from repro.utils.logging import CSVWriter, get_logger


@pytest.fixture(autouse=True)
def _reset_global_obs():
    """Every test leaves the module-global tracer/bus disabled again."""
    yield
    obs_mod.shutdown()


# ---------------------------------------------------------------------------
# Satellites: CSVWriter lazy header, get_logger
# ---------------------------------------------------------------------------


def test_csv_writer_lazy_header(capsys):
    w = CSVWriter()
    assert capsys.readouterr().out == ""  # nothing until the first row
    w.row("a", 1, "")
    w.row("b", 2, "x")
    out = capsys.readouterr().out.splitlines()
    assert out == ["name,us_per_call,derived", "a,1,", "b,2,x"]


def test_csv_writer_silent_when_unused(capsys):
    CSVWriter(header=("k", "v"))
    assert capsys.readouterr().out == ""


def test_get_logger_rank_prefix_and_level(monkeypatch):
    monkeypatch.setenv("REPRO_MP_PID", "3")
    monkeypatch.setenv("REPRO_LOG_LEVEL", "DEBUG")
    log = get_logger("repro.test_obs_rank")
    assert log.level == logging.DEBUG
    assert not log.propagate
    ours = [h for h in log.handlers if getattr(h, "_repro_handler", False)]
    assert len(ours) == 1
    assert "[rank 3]" in ours[0].formatter._fmt

    # repeated calls update in place — no duplicate handlers, env re-read
    monkeypatch.setenv("REPRO_MP_PID", "")
    monkeypatch.setenv("REPRO_LOG_LEVEL", "WARNING")
    log2 = get_logger("repro.test_obs_rank")
    assert log2 is log and len(log.handlers) == 1
    assert log.level == logging.WARNING
    assert "[rank" not in log.handlers[0].formatter._fmt


def test_get_logger_bad_level_falls_back_to_info(monkeypatch):
    monkeypatch.setenv("REPRO_LOG_LEVEL", "NOT_A_LEVEL")
    assert get_logger("repro.test_obs_badlevel").level == logging.INFO


def test_get_logger_leaves_foreign_handlers_alone(monkeypatch):
    monkeypatch.delenv("REPRO_MP_PID", raising=False)
    log = logging.getLogger("repro.test_obs_foreign")
    foreign = logging.NullHandler()
    log.addHandler(foreign)
    get_logger("repro.test_obs_foreign")
    assert log.handlers == [foreign]  # no tagged handler stacked on top


# ---------------------------------------------------------------------------
# Tracer + Chrome trace-event schema
# ---------------------------------------------------------------------------


def test_tracer_spans_save_and_validate(tmp_path):
    tr = Tracer(enabled=True, pid=2)
    with tr.span("issue_sample", cat="pipeline", exchange="local"):
        pass
    with tr.span("checkpoint_save", cat="checkpoint", tid=1):
        pass
    tr.instant("restart", step=3)
    tr.counter("fill", {"hot": 4.0})
    assert tr.span_names() == {"issue_sample", "checkpoint_save"}
    stats = tr.span_stats()
    assert stats["issue_sample"]["count"] == 1
    path = tr.save(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert validate_trace(doc) == []
    assert doc["displayTimeUnit"] == "ms"
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["issue_sample"]["ph"] == "X"
    assert by_name["issue_sample"]["pid"] == 2
    assert by_name["issue_sample"]["args"]["exchange"] == "local"
    assert by_name["checkpoint_save"]["tid"] == 1
    assert by_name["restart"]["ph"] == "i"
    assert by_name["fill"]["ph"] == "C"
    assert by_name["process_name"]["ph"] == "M"  # rank track label


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        pass
    tr.instant("y")
    tr.counter("z", {"a": 1})
    assert tr.events() == []


# ---------------------------------------------------------------------------
# Input thread: Prefetcher spans + counters, on a profiler trace's host plane
# ---------------------------------------------------------------------------


@jax.jit
def _slow_copy(x):
    """A device computation that is still running when its call returns."""
    return jax.lax.fori_loop(0, 500, lambda i, y: jnp.tanh(y @ y), x)


def _input_batch(cur):
    return {"x": np.full((128, 128), 0.01 * (cur.step + 1), np.float32)}


def test_prefetcher_counts_batches_handed_out_in_flight():
    from repro.data import Prefetcher

    pf = Prefetcher(_input_batch, convert=lambda v: _slow_copy(jnp.asarray(v)))
    _slow_copy(jnp.ones((128, 128))).block_until_ready()  # compiled ahead
    _, batch = pf.next()  # synchronous: fetch, convert, hand out at once
    c = pf.counters()
    assert c["batches"] == 1 and c["not_ready"] == 1
    assert c["convert_s"] > 0 and c["wait_s"] == 0
    jax.block_until_ready(batch)
    c["batches"] = 99  # a snapshot, not the live counters
    assert pf.counters()["batches"] == 1


def test_prefetcher_counts_ready_batches_and_waits():
    from repro.data import Prefetcher

    pf = Prefetcher(_input_batch,
                    convert=lambda v: jax.block_until_ready(jnp.asarray(v))).start()
    try:
        for _ in range(3):
            pf.next()
    finally:
        pf.stop()
    c = pf.counters()
    assert c["batches"] == 3 and c["not_ready"] == 0
    assert c["convert_s"] > 0 and c["wait_s"] > 0


def test_prefetcher_counts_numpy_batches_as_ready():
    from repro.data import Prefetcher

    pf = Prefetcher(_input_batch)
    pf.next()
    pf.next()
    assert pf.counters() == {"batches": 2, "not_ready": 0, "convert_s": 0.0,
                             "wait_s": 0.0}


def test_prefetcher_places_on_a_sharding_under_its_own_span():
    """Placement on ``sharding`` runs in ``next()`` under ``input.place``;
    ``input.convert`` and ``convert_s`` hold the input thread's ``convert``
    call alone."""
    from repro.data import Prefetcher
    from repro.obs.trace import get_tracer, set_tracer

    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    old, tr = get_tracer(), set_tracer(Tracer(enabled=True))
    try:
        pf = Prefetcher(_input_batch, sharding={"x": sh},
                        convert=lambda v: v + 1).start()
        try:
            for _ in range(3):
                _, b = pf.next()
                assert isinstance(b["x"], jax.Array) and b["x"].sharding == sh
        finally:
            pf.stop()
    finally:
        set_tracer(old)
    threads = {}
    for e in tr.events():
        if e["name"].startswith("input."):
            threads.setdefault(e["name"], set()).add(e["tid"])
    assert threads["input.place"] == {0} and threads["input.wait"] == {0}
    assert threads["input.convert"] == {2} and threads["input.fetch"] == {2}
    assert tr.span_stats()["input.place"]["count"] == 3
    assert pf.counters()["batches"] == 3 and pf.counters()["convert_s"] > 0


def test_program_spans_land_on_the_profiler_host_plane(tmp_path):
    """With the module-global tracer disabled, the input spans still reach a
    profiler trace's host plane beside the XLA op events of the CPU client,
    on one clock; the tracer itself records nothing."""
    from jax.profiler import ProfileData

    from repro.data import Prefetcher

    step = jax.jit(lambda x: jnp.tanh(x @ x))
    step(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        pf = Prefetcher(lambda cur: {"x": np.ones((64, 64), np.float32)},
                        convert=jnp.asarray).start()
        for _ in range(3):
            _, b = pf.next()
            step(b["x"]).block_until_ready()
        pf.stop()
    finally:
        jax.profiler.stop_trace()
    assert obs_mod.get_tracer().events() == []
    path = next(tmp_path.rglob("*.xplane.pb"))
    host, ops = {}, []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("input."):
                    host.setdefault(e.name, []).append(e.start_ns)
                elif "hlo_op" in dict(e.stats):
                    ops.append(e.start_ns)
    assert set(host) == {"input.fetch", "input.convert", "input.wait"}  # no sharding
    assert ops
    # one clock: the op events fall among the spans, not on another epoch
    spans = [t for ts in host.values() for t in ts]
    assert min(spans) < max(ops) and min(ops) < max(spans)


def test_validate_trace_rejects_malformed():
    assert validate_trace([]) != []
    assert validate_trace({}) != []
    assert validate_trace({"traceEvents": [{"name": "a", "ph": "X"}]}) != []
    # 'X' span without dur
    bad = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0,
                            "pid": 0, "tid": 0}]}
    assert any("dur" in p for p in validate_trace(bad))


# ---------------------------------------------------------------------------
# EventBus + JSONL
# ---------------------------------------------------------------------------


def test_event_bus_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    bus = EventBus(enabled=True, path=path, rank=1)
    bus.publish("restart", source="resilient_loop", step=4, restarts=1)
    bus.publish("reshard", source="scale_carry", n_new=2, seconds=0.1)
    bus.close()
    back = read_events(path)
    assert [e["kind"] for e in back] == ["restart", "reshard"]
    for e in back:
        assert set(e) >= {"kind", "source", "ts", "rank"}
        assert e["rank"] == 1
    assert back[0]["step"] == 4
    assert bus.kinds() == {"restart", "reshard"}
    assert bus.of_kind("reshard")[0]["n_new"] == 2


def test_event_bus_disabled_publishes_nothing(tmp_path):
    bus = EventBus(enabled=False, path=str(tmp_path / "nope.jsonl"))
    assert bus.publish("restart") is None
    assert bus.events == []
    assert not os.path.exists(tmp_path / "nope.jsonl")


def test_configure_shutdown_lifecycle(tmp_path):
    d = str(tmp_path / "obs")
    tracer, bus = obs_mod.configure(d, rank=0)
    assert obs_mod.get_tracer() is tracer and tracer.enabled
    with tracer.span("eval", cat="trainer"):
        pass
    bus.publish("autoscale", source="autoscaler", old=1, new=2)
    path = obs_mod.shutdown()
    assert path == os.path.join(d, "trace.json")
    assert validate_trace(json.load(open(path))) == []
    assert {e["kind"] for e in read_events(os.path.join(d, "events.jsonl"))} \
        == {"autoscale"}
    assert not obs_mod.get_tracer().enabled  # back to disabled no-ops
    assert not obs_mod.get_event_bus().enabled


# ---------------------------------------------------------------------------
# Exporters: Prometheus endpoint + MetricsWriter
# ---------------------------------------------------------------------------


def test_prom_name_sanitizes():
    assert prom_name("obs/replay_fraction") == "obs_replay_fraction"
    assert prom_name("9lives") == "_9lives"
    assert prom_name("") == "unnamed"


def test_metrics_registry_renders_text_format():
    reg = MetricsRegistry()
    reg.set("obs/fill", 12.0, help="records resident")
    reg.set_many({"obs/grad_norm": 0.5})
    text = reg.render()
    assert "# HELP obs_fill records resident" in text
    assert "# TYPE obs_fill gauge" in text
    assert "obs_fill 12.0" in text
    assert "obs_grad_norm 0.5" in text


def test_metrics_server_serves_registry():
    reg = MetricsRegistry()
    reg.set("obs/fill", 3.0)
    server, port = start_metrics_server(reg, port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as resp:
            assert resp.status == 200
            body = resp.read().decode()
        assert "obs_fill 3.0" in body
        reg.set("obs/fill", 4.0)  # live: next scrape sees the new value
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as resp:
            assert "obs_fill 4.0" in resp.read().decode()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/other")
    finally:
        server.shutdown()


def test_metrics_writer_summary_and_bench_rows():
    w = MetricsWriter()
    w.add({"obs/fill": jnp.float32(2.0), "loss": 9.0}, step=0)
    w.add({"obs/fill": 4.0, "obs/grad_norm": 1.0}, step=1)
    s = w.summary()
    assert set(s) == {"obs/fill", "obs/grad_norm"}  # non-obs keys filtered
    assert s["obs/fill"] == {"last": 4.0, "mean": 3.0, "max": 4.0, "n": 2}
    assert w.bench_rows()["obs_fill_last"] == 4.0
    assert all(isinstance(v, float) for vals in w.series.values() for v in vals)


# ---------------------------------------------------------------------------
# Static cost model
# ---------------------------------------------------------------------------


def _rcfg(**kw):
    base = dict(num_buckets=2, slots_per_bucket=8, num_representatives=3,
                num_candidates=6, mode="async", label_field="label")
    base.update(kw)
    return RehearsalConfig(**base)


def test_obs_keys_enumerate_per_config():
    flat = obs_keys(_rcfg())
    assert "obs/fill" in flat and "obs/rep_staleness" in flat
    assert "obs/hot_fill" not in flat
    tiered = obs_keys(_rcfg(tiering="host", hot_slots=4, cold_slots=8))
    assert {"obs/hot_fill", "obs/cold_fill", "obs/demotions",
            "obs/stage_pending"} <= set(tiered)
    assert "obs/grad_norm" not in obs_keys(_rcfg(), grad_norms=False)
    assert "obs/aux_row_bytes" in obs_keys(_rcfg(), has_aux=True)
    assert obs_keys(None) == ["obs/grad_norm", "obs/param_norm"]


def test_estimate_obs_cost_math():
    cost = estimate_obs_cost(_rcfg(tiering="host", hot_slots=4, cold_slots=8))
    assert cost["n_keys"] == len(cost["keys"])
    assert cost["device_bytes_per_step"] == 4 * cost["n_keys"]
    assert cost["host_bytes_per_history_entry"] == 56 * cost["n_keys"]


def test_dryrun_obs_cost_record_shape():
    # the launch/dryrun record is exactly estimate_obs_cost's dict — pin the
    # keys the roofline/report tooling reads
    cost = estimate_obs_cost(_rcfg(), has_aux=True, policy="reservoir")
    assert set(cost) == {"keys", "n_keys", "device_bytes_per_step",
                         "host_bytes_per_history_entry",
                         "json_bytes_per_history_entry"}


# ---------------------------------------------------------------------------
# Jit-safe step metrics: fingerprint bit-exactness + gauge sanity
# ---------------------------------------------------------------------------


def _spec(d=8):
    return {"x": jax.ShapeDtypeStruct((d,), jnp.float32),
            "label": jax.ShapeDtypeStruct((), jnp.int32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}


def _linear_loss(params, batch):
    logits = batch["x"] @ params["w"]
    onehot = jax.nn.one_hot(jnp.maximum(batch["label"], 0), logits.shape[-1])
    mask = (batch["label"] >= 0).astype(jnp.float32)
    ce = -jnp.sum(jax.nn.log_softmax(logits) * onehot, axis=-1)
    return jnp.sum(ce * mask) / jnp.maximum(mask.sum(), 1.0), {}


def _sgd(grads, opt, params):
    return jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads), opt, {}


def _batch(step, b=16, d=8, n_classes=4):
    r = np.random.default_rng(step)
    lab = r.integers(0, n_classes, b).astype(np.int32)
    return {"x": jnp.asarray(r.normal(size=(b, d)).astype(np.float32)),
            "label": jnp.asarray(lab), "task": jnp.asarray(lab % 2)}


def _run_steps(rcfg, obs, steps=6):
    params = {"w": jnp.zeros((8, 4))}
    step = make_cl_step(_linear_loss, _sgd, rcfg, strategy="rehearsal",
                        exchange="local", label_field="label", donate=False,
                        obs=obs)
    carry = init_carry(params, None, _spec(), rcfg, label_field="label", seed=3)
    key = jax.random.PRNGKey(0)
    history = []
    for s in range(steps):
        carry, m = step(carry, _batch(s), jax.random.fold_in(key, s))
        history.append({k: np.asarray(v) for k, v in m.items()})
    return history, carry


@pytest.mark.parametrize("tiering", ["off", "host"])
def test_obs_toggle_is_fingerprint_bit_exact(tiering):
    """THE obs contract: same rcfg, obs off vs on — rep_checksum, buffer_fill
    and loss identical to the bit; obs-on only ADDS obs/* keys."""
    kw = {} if tiering == "off" else dict(tiering="host", hot_slots=8,
                                          cold_slots=16)
    rcfg = _rcfg(**kw)
    h_off, c_off = _run_steps(rcfg, None)
    h_on, c_on = _run_steps(rcfg, ObsConfig(enabled=True))
    for off, on in zip(h_off, h_on):
        for k in ("rep_checksum", "buffer_fill", "loss"):
            assert off[k].tobytes() == on[k].tobytes(), k
        assert set(off) == {k for k in on if not k.startswith("obs/")}
        assert any(k.startswith("obs/") for k in on)
    for a, b in zip(jax.tree_util.tree_leaves(c_off.params),
                    jax.tree_util.tree_leaves(c_on.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_obs_disabled_config_emits_no_keys():
    h, _ = _run_steps(_rcfg(), ObsConfig(enabled=False))
    assert not any(k.startswith("obs/") for k in h[0])


def test_obs_gauge_sanity_flat():
    h, _ = _run_steps(_rcfg(), ObsConfig(enabled=True), steps=8)
    last = h[-1]
    assert float(last["obs/fill"]) > 0
    assert float(last["obs/fill"]) <= 2 * 8  # num_buckets * slots_per_bucket
    assert 0.0 <= float(last["obs/replay_fraction"]) < 1.0
    assert float(last["obs/reps_valid"]) <= 3  # num_representatives
    assert float(last["obs/rep_staleness"]) == 1.0  # async one-step-stale
    assert float(last["obs/grad_norm"]) >= 0
    assert float(last["obs/param_norm"]) > 0
    # fill is monotone for a reservoir that hasn't hit capacity
    fills = [float(m["obs/fill"]) for m in h]
    assert fills == sorted(fills)


def test_obs_gauge_sanity_tiered():
    rcfg = _rcfg(tiering="host", hot_slots=4, cold_slots=8, slots_per_bucket=4)
    h, _ = _run_steps(rcfg, ObsConfig(enabled=True), steps=8)
    last = h[-1]
    assert {"obs/hot_fill", "obs/cold_fill", "obs/demotions",
            "obs/stage_pending"} <= set(last)
    assert float(last["obs/hot_fill"]) <= 2 * 4
    assert float(last["obs/fill"]) == pytest.approx(
        float(last["obs/hot_fill"]) + float(last["obs/cold_fill"]))


def test_grad_norms_flag_gates_norm_gauges():
    h, _ = _run_steps(_rcfg(), ObsConfig(enabled=True, grad_norms=False))
    assert "obs/grad_norm" not in h[0] and "obs/param_norm" not in h[0]
    assert "obs/fill" in h[0]  # the cheap gauges stay


# ---------------------------------------------------------------------------
# Runtime publishers: restart / checkpoint / autoscale / reshard
# ---------------------------------------------------------------------------


def test_runtime_publishers_emit_events_and_spans(tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.runtime.autoscale import Autoscaler, scale_carry
    from repro.runtime.fault_tolerance import InjectedFailure, ResilientLoop

    d = str(tmp_path / "obs")
    obs_mod.configure(d, rank=0)

    rcfg = _rcfg()
    params = {"w": jnp.zeros((8, 4))}
    step = make_cl_step(_linear_loss, _sgd, rcfg, strategy="rehearsal",
                        exchange="local", label_field="label", donate=False)
    carry = init_carry(params, None, _spec(), rcfg, label_field="label", seed=3)
    loop = ResilientLoop(step_fn=step,
                         ckpt=CheckpointManager(str(tmp_path / "ckpt")),
                         checkpoint_every=1, max_restarts=2, backoff_base=0.0)
    fired = []

    def chaos(s):
        if s == 1 and not fired:
            fired.append(s)
            raise InjectedFailure("injected")

    _, _, restarts = loop.run(carry, _batch, jax.random.PRNGKey(0), 3,
                              failure_hook=chaos)
    assert restarts == 1

    scaler = Autoscaler(cooldown_steps=1, max_workers=4)
    assert scaler.observe(step=0, load=3.5, current=1) == 4  # upscale

    dist = init_carry(params, None, _spec(), rcfg, label_field="label",
                      seed=3, n_dp=2)
    _, seconds = scale_carry(dist, 1)
    assert seconds > 0

    tracer, bus = obs_mod.get_tracer(), obs_mod.get_event_bus()
    assert {"restart", "checkpoint_save", "checkpoint_restore", "autoscale",
            "reshard"} <= bus.kinds()
    restart = bus.of_kind("restart")[0]
    assert restart["source"] == "resilient_loop"
    assert restart["error"] == "InjectedFailure"
    auto = bus.of_kind("autoscale")[0]
    assert (auto["old"], auto["new"]) == (1, 4)
    assert bus.of_kind("reshard")[0]["n_new"] == 1
    assert {"restore", "checkpoint_save", "checkpoint_restore",
            "reshard"} <= tracer.span_names()

    obs_mod.shutdown()
    assert validate_trace(json.load(open(os.path.join(d, "trace.json")))) == []
    kinds = {e["kind"] for e in read_events(os.path.join(d, "events.jsonl"))}
    assert {"restart", "reshard"} <= kinds


def test_straggler_policy_publishes_stale_dispatch(tmp_path):
    from repro.runtime.fault_tolerance import StragglerPolicy

    obs_mod.configure(str(tmp_path / "obs"), rank=0)
    pol = StragglerPolicy(delay_prob=0.0, max_staleness=2)
    pol.record_slow()
    assert pol.use_fresh() is False  # reuse → one stale_dispatch event
    ev = obs_mod.get_event_bus().of_kind("stale_dispatch")
    assert len(ev) == 1
    assert ev[0]["source"] == "straggler"
    assert ev[0]["staleness"] == 1


# ---------------------------------------------------------------------------
# Trainer end to end: obs toggle on DER++, artifacts, result.obs — and the
# carry==pjit fingerprint contract with obs ON
# ---------------------------------------------------------------------------


def _token_run(obs, strategy="rehearsal", tiering="off"):
    from repro.configs import get_reduced
    from repro.configs.base import (
        RunConfig,
        ScenarioConfig,
        ShapeConfig,
        StrategyConfig,
        TrainConfig,
    )

    base = get_reduced("smollm-135m")
    cfg = type(base)(**{**base.__dict__, "vocab_size": 128, "num_layers": 2,
                        "name": "smollm-obs"})
    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=4,
                           num_representatives=3, num_candidates=6,
                           mode="async", tiering=tiering, hot_slots=4,
                           cold_slots=8, label_field="labels")
    return RunConfig(
        model=cfg, shape=ShapeConfig("obs", 16, 8, "train"),
        obs=obs,
        train=TrainConfig(optimizer="adamw", peak_lr=1e-3, warmup_steps=5,
                          linear_scaling=False, compute_dtype="float32"),
        rehearsal=rcfg, strategy=StrategyConfig(alpha=0.5, beta=0.5, top_k=8),
        scenario=ScenarioConfig(name="class_incremental", modality="tokens",
                                strategy=strategy, num_tasks=2,
                                epochs_per_task=1, steps_per_epoch=4,
                                batch_size=8, vocab_size=128, seq_len=16,
                                auto_defaults=False))


def _fingerprints(result):
    return [(h["rep_checksum"], h["buffer_fill"], h["loss"])
            for h in result.history]


def test_trainer_obs_toggle_der_pp_and_artifacts(tmp_path):
    """DER++ through ContinualTrainer with obs off vs on: identical
    fingerprints, obs/* in the history + result.obs, trace.json on disk."""
    from repro.scenario import ContinualTrainer

    d = str(tmp_path / "obs")
    off = ContinualTrainer(_token_run(None, strategy="der_pp")).fit()
    on = ContinualTrainer(
        _token_run(ObsConfig(enabled=True, dir=d), strategy="der_pp")).fit()
    assert _fingerprints(off) == _fingerprints(on)
    assert off.obs is None
    assert on.obs and "obs/fill" in on.obs
    assert on.obs["obs/aux_row_bytes"]["last"] > 0  # DER logits aux payload
    assert all(any(k.startswith("obs/") for k in h) for h in on.history)
    doc = json.load(open(os.path.join(d, "trace.json")))
    assert validate_trace(doc) == []
    assert "eval" in {e["name"] for e in doc["traceEvents"]
                      if e.get("ph") == "X"}


def test_carry_equals_pjit_fingerprints_with_obs_on():
    from repro.launch.mesh import make_mesh
    from repro.scenario import ContinualTrainer, TokenClassIncremental

    run = _token_run(ObsConfig(enabled=True))
    sc = TokenClassIncremental(run.scenario)
    mesh = make_mesh((1, 1), ("data", "model"))
    pjit_res = ContinualTrainer(run, sc, mesh=mesh, exchange="local").fit()
    carry_res = ContinualTrainer(run, sc).fit()
    pj = [(h["rep_checksum"], h["buffer_fill"]) for h in pjit_res.history]
    ca = [(h["rep_checksum"], h["buffer_fill"]) for h in carry_res.history]
    assert pj == ca, (pj, ca)
    # both backends emit the obs gauges under the same keys
    assert any(k.startswith("obs/") for k in pjit_res.history[0])
    assert any(k.startswith("obs/") for k in carry_res.history[0])
    assert pjit_res.obs and carry_res.obs
