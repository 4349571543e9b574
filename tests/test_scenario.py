"""Scenario-first API: trainer↔run_continual parity (the pinned contract),
cursor-resume determinism of the new streams, scenario→policy default
selection, and end-to-end smoke for the domain-incremental + blurry scenarios.
"""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import resnet50_cl
from repro.configs.base import (
    RehearsalConfig,
    RunConfig,
    ScenarioConfig,
    TrainConfig,
)
from repro.data import (
    BlurryBoundaryImages,
    BlurryStreamConfig,
    ClassIncrementalImages,
    DomainIncrementalImages,
    DomainStreamConfig,
    ImageStreamConfig,
)
from repro.scenario import (
    BlurryBoundary,
    ClassIncremental,
    ContinualTrainer,
    DomainIncremental,
    get_scenario,
)

T = 2


@pytest.fixture(scope="module")
def vision_setup():
    """The historical hand-wired path, exactly as pre-scenario callers built it."""
    from repro.core import make_cl_step, topk_accuracy
    from repro.models.model_zoo import cross_entropy
    from repro.models.resnet import apply_cnn, init_cnn
    from repro.optim import make_optimizer

    stream = ClassIncrementalImages(ImageStreamConfig(
        num_tasks=T, classes_per_task=3, image_size=8, noise=0.4))
    ccfg = resnet50_cl.reduced(num_classes=stream.num_classes)
    tcfg = TrainConfig(optimizer="sgd", peak_lr=0.05, warmup_steps=10,
                       linear_scaling=False)

    def loss_fn(params, batch):
        logits = apply_cnn(params, batch["images"], ccfg)
        return cross_entropy(logits[:, None, :], batch["label"][:, None]), {}

    opt_init, opt_update = make_optimizer(tcfg)
    item_spec = {"images": jax.ShapeDtypeStruct((8, 8, 3), jnp.float32),
                 "label": jax.ShapeDtypeStruct((), jnp.int32),
                 "task": jax.ShapeDtypeStruct((), jnp.int32)}
    eval_logits = jax.jit(lambda p, im: apply_cnn(p, im, ccfg))

    def eval_fn(params, task):
        ev = stream.eval_set(task)
        return float(topk_accuracy(eval_logits(params, jnp.asarray(ev["images"])),
                                   jnp.asarray(ev["label"]), k=1))

    return dict(stream=stream, ccfg=ccfg, tcfg=tcfg, loss_fn=loss_fn,
                opt_init=opt_init, opt_update=opt_update, item_spec=item_spec,
                eval_fn=eval_fn, init_cnn=init_cnn, make_cl_step=make_cl_step)


def _old_path(s, strategy, rcfg):
    from repro.core import run_continual

    step = s["make_cl_step"](s["loss_fn"], s["opt_update"], rcfg,
                             strategy=strategy, label_field="label")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return run_continual(
            strategy=strategy, num_tasks=T, epochs_per_task=1, steps_per_epoch=6,
            batch_fn=s["stream"].batch,
            cumulative_batch_fn=s["stream"].cumulative_batch,
            eval_fn=s["eval_fn"],
            init_params_fn=lambda k: s["init_cnn"](k, s["ccfg"]),
            init_opt_fn=s["opt_init"], step_fn=step, item_spec=s["item_spec"],
            rcfg=rcfg, batch_size=8, label_field="label")


def _new_path(s, strategy, rcfg):
    run = RunConfig(model=s["ccfg"], train=s["tcfg"], rehearsal=rcfg,
                    scenario=ScenarioConfig(strategy=strategy, num_tasks=T,
                                            epochs_per_task=1, steps_per_epoch=6,
                                            batch_size=8, seed=0,
                                            auto_defaults=False))
    return ContinualTrainer(run, ClassIncremental(stream=s["stream"])).fit()


def test_trainer_matches_run_continual(vision_setup):
    """Acceptance pin: ContinualTrainer on the class-incremental scenario
    reproduces run_continual's accuracy matrix EXACTLY (same seed)."""
    rcfg = RehearsalConfig(num_buckets=T, slots_per_bucket=16,
                           num_representatives=4, num_candidates=8,
                           mode="async", label_field="label")
    old = _old_path(vision_setup, "rehearsal", rcfg)
    new = _new_path(vision_setup, "rehearsal", rcfg)
    assert np.array_equal(old.accuracy_matrix, new.accuracy_matrix)
    assert old.history == new.history
    assert old.final_accuracy == new.final_accuracy


def test_trainer_matches_run_continual_from_scratch(vision_setup):
    """Parity covers the re-init + cumulative-sampling path too."""
    rcfg = RehearsalConfig(mode="off", label_field="label")
    old = _old_path(vision_setup, "from_scratch", rcfg)
    new = _new_path(vision_setup, "from_scratch", rcfg)
    assert np.array_equal(old.accuracy_matrix, new.accuracy_matrix)
    assert old.history == new.history


def test_split_step_form_matches_fused(vision_setup):
    """The trainer's make_pipelined_halves composition (two dispatched XLA
    programs) reproduces the fused make_cl_step path exactly (DESIGN.md §3)."""
    s = vision_setup
    rcfg = RehearsalConfig(num_buckets=T, slots_per_bucket=16,
                           num_representatives=4, num_candidates=8,
                           mode="async", label_field="label")
    run = RunConfig(model=s["ccfg"], train=s["tcfg"], rehearsal=rcfg,
                    scenario=ScenarioConfig(num_tasks=T, epochs_per_task=1,
                                            steps_per_epoch=6, batch_size=8,
                                            auto_defaults=False))
    sc = ClassIncremental(stream=s["stream"])
    fused = ContinualTrainer(run, sc).fit()
    split = ContinualTrainer(run, sc, step_form="split").fit()
    assert np.array_equal(fused.accuracy_matrix, split.accuracy_matrix)
    assert fused.history == split.history


def test_run_continual_warns_deprecated(vision_setup):
    s = vision_setup
    from repro.core import run_continual

    rcfg = RehearsalConfig(mode="off", label_field="label")
    step = s["make_cl_step"](s["loss_fn"], s["opt_update"], rcfg,
                             strategy="incremental", label_field="label")
    with pytest.warns(DeprecationWarning, match="ContinualTrainer"):
        run_continual(strategy="incremental", num_tasks=1, epochs_per_task=1,
                      steps_per_epoch=1, batch_fn=s["stream"].batch,
                      eval_fn=s["eval_fn"],
                      init_params_fn=lambda k: s["init_cnn"](k, s["ccfg"]),
                      init_opt_fn=s["opt_init"], step_fn=step,
                      item_spec=s["item_spec"], rcfg=rcfg, batch_size=8,
                      label_field="label")


# ---------------------------------------------------------------------------
# Cursor-resume determinism (fault-tolerance contract) for the new streams
# ---------------------------------------------------------------------------


def _trace(stream, task, cursors, batch_size=8):
    return [stream.batch(task, batch_size, c) for c in cursors]


@pytest.mark.parametrize("make", [
    lambda: DomainIncrementalImages(DomainStreamConfig(
        num_tasks=3, num_classes=4, image_size=8)),
    lambda: BlurryBoundaryImages(BlurryStreamConfig(
        num_tasks=3, classes_per_task=3, image_size=8, task_len=10, blur=0.5)),
])
def test_cursor_resume_reproduces_exact_sequence(make):
    """Restarting mid-task reproduces the exact sample sequence: batches are
    pure functions of (seed, task, cursor), with no hidden generator state."""
    stream = make()
    full = _trace(stream, 1, range(10, 20))
    resumed = _trace(make(), 1, range(14, 20))  # fresh instance, mid-task cursor
    for a, b in zip(full[4:], resumed):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def test_blurry_stream_mixes_without_task_ids():
    cfg = BlurryStreamConfig(num_tasks=3, classes_per_task=4, image_size=8,
                             task_len=20, blur=0.6)
    stream = BlurryBoundaryImages(cfg)
    b = stream.batch(1, 32, cursor=1 * 20)  # first step of task 1: boundary
    assert "task" not in b  # no clean task id — the whole point
    # at the boundary ~half the samples defect to the previous task's classes
    prev = np.isin(b["label"], stream.task_classes(0)).mean()
    assert 0.15 < prev < 0.85
    mid = stream.batch(1, 32, cursor=1 * 20 + 10)  # mid-task: no mixing
    assert np.isin(mid["label"], stream.task_classes(1)).all()
    # last step of task 1: mixes with task 2, never task 0
    end = stream.batch(1, 32, cursor=2 * 20 - 1)
    assert not np.isin(end["label"], stream.task_classes(0)).any()
    assert np.isin(end["label"], stream.task_classes(2)).any()


def test_domain_stream_shares_label_space():
    stream = DomainIncrementalImages(DomainStreamConfig(
        num_tasks=3, num_classes=5, image_size=8, domain_shift=1.0))
    b0, b2 = stream.batch(0, 64, 0), stream.batch(2, 64, 0)
    assert set(np.unique(b0["label"])) <= set(range(5))
    assert set(np.unique(b2["label"])) <= set(range(5))
    # the domain transform actually shifts the input distribution
    assert np.abs(b0["images"].mean() - b2["images"].mean()) > 0.01 or \
        np.abs(b0["images"].std() - b2["images"].std()) > 0.05


# ---------------------------------------------------------------------------
# Scenario -> rehearsal-policy default selection
# ---------------------------------------------------------------------------


def test_chunked_vision_eval_equals_whole_set():
    """An eval set larger than one chunk is evaluated in fixed-size chunks
    (the last zero-padded) and gives the whole-set top-1 accuracy."""
    from repro.core import topk_accuracy
    from repro.models.resnet import apply_cnn
    from repro.scenario.scenarios import EVAL_CHUNK

    sc = ClassIncremental(ScenarioConfig(num_tasks=2, classes_per_task=20,
                                         image_size=8))
    ev = sc.eval_set(1)
    assert len(ev["label"]) > EVAL_CHUNK and len(ev["label"]) % EVAL_CHUNK
    assert sc.eval_set(1) is ev  # generated once per task
    ccfg = resnet50_cl.reduced(num_classes=sc.num_classes)
    run = RunConfig(model=ccfg, train=TrainConfig(compute_dtype="float32"))
    problem = sc.build_problem(run)
    params = problem.init_params_fn(jax.random.PRNGKey(3))
    whole = float(topk_accuracy(apply_cnn(params, jnp.asarray(ev["images"]), ccfg),
                                jnp.asarray(ev["label"]), k=1))
    assert problem.eval_fn(params, 1) == whole


def test_scenario_policy_default_selection():
    ci = get_scenario(ScenarioConfig(num_tasks=3, classes_per_task=2))
    dom = get_scenario(ScenarioConfig(name="domain_incremental", num_tasks=3,
                                      num_classes=4))
    blur = get_scenario(ScenarioConfig(name="blurry_boundary", num_tasks=3,
                                       classes_per_task=2))
    base = RehearsalConfig()
    r_ci = ci.apply_defaults(base)
    assert (r_ci.policy, r_ci.num_buckets, r_ci.task_field) == ("reservoir", 3, "task")
    r_dom = dom.apply_defaults(base)
    assert (r_dom.policy, r_dom.task_field) == ("class_balanced", "task")
    r_blur = blur.apply_defaults(base)
    # no clean task id: bucket by label over all 6 classes
    assert (r_blur.policy, r_blur.num_buckets, r_blur.task_field) == \
        ("reservoir", 6, "label")
    assert blur.task_field is None and blur.buffer_task_field == "label"
    # explicit user choices always beat the recommendation
    explicit = RehearsalConfig(policy="grasp", num_buckets=7)
    r = dom.apply_defaults(explicit)
    assert r.policy == "grasp" and r.num_buckets == 7


def test_scenario_by_name_uses_run_scenario_params():
    """Passing a registry name selects the kind; the stream is still built
    from run.scenario (shape and schedule must not desync)."""
    run = RunConfig(scenario=ScenarioConfig(num_tasks=5, classes_per_task=3,
                                            image_size=8, steps_per_epoch=4))
    tr = ContinualTrainer(run, "blurry_boundary")
    assert tr.scenario.num_tasks == 5
    assert tr.scenario.num_classes == 15
    assert tr.scenario.stream.cfg.task_len == 4  # blur tied to the schedule
    assert tr.num_tasks == 5


def test_blurry_buckets_by_label_even_without_auto_defaults():
    """The blurry stream has no task id; the trainer buckets by the label field
    regardless of the rcfg's task_field (scenario schema is authoritative)."""
    run = RunConfig(
        rehearsal=RehearsalConfig(mode="async"),  # task_field='task' default
        scenario=ScenarioConfig(name="blurry_boundary", num_tasks=2,
                                classes_per_task=2, image_size=8,
                                steps_per_epoch=4, auto_defaults=False))
    tr = ContinualTrainer(run)
    assert tr.scenario.buffer_task_field == "label"
    assert "task" not in tr.item_spec


def test_blurry_from_scratch_raises_not_hangs():
    """No clean cumulative view exists for a blurry stream; the error must
    propagate out of the background prefetch thread instead of deadlocking."""
    run = RunConfig(
        train=TrainConfig(optimizer="sgd", warmup_steps=2, linear_scaling=False),
        scenario=ScenarioConfig(name="blurry_boundary", strategy="from_scratch",
                                num_tasks=2, classes_per_task=2, image_size=8,
                                epochs_per_task=1, steps_per_epoch=3,
                                batch_size=4))
    with pytest.raises(NotImplementedError, match="from_scratch"):
        ContinualTrainer(run).fit()


def test_missing_bucket_field_rejected():
    """A scenario that declares a bucket field its records do not carry must
    fail at trainer construction, not mid-jit."""
    cfg = ScenarioConfig(name="blurry_boundary", num_tasks=2,
                         classes_per_task=2, image_size=8, steps_per_epoch=4)

    class BrokenSchema(BlurryBoundary):
        task_field = "task"  # claims a task id ...

        @property
        def item_spec(self):
            spec = dict(super().item_spec)
            spec.pop("task", None)  # ... that the records do not carry
            return spec

    run = RunConfig(rehearsal=RehearsalConfig(mode="async"),
                    scenario=cfg)
    with pytest.raises(ValueError, match="declares bucket field 'task'"):
        ContinualTrainer(run, BrokenSchema(cfg))


# ---------------------------------------------------------------------------
# End-to-end smoke: domain + blurry train/eval/rehearse through the trainer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,extra", [
    ("domain_incremental", {"num_classes": 4, "domain_shift": 1.2}),
    ("blurry_boundary", {"classes_per_task": 3, "blur": 0.5}),
])
def test_scenarios_end_to_end(name, extra):
    run = RunConfig(
        train=TrainConfig(optimizer="sgd", peak_lr=0.05, warmup_steps=5,
                          linear_scaling=False),
        rehearsal=RehearsalConfig(slots_per_bucket=8, num_representatives=4,
                                  num_candidates=8, mode="async"),
        scenario=ScenarioConfig(name=name, num_tasks=2, epochs_per_task=1,
                                steps_per_epoch=6, batch_size=8, image_size=8,
                                **extra))
    trainer = ContinualTrainer(run)
    assert trainer.rcfg.enabled  # rehearsal really on (buffer exercised)
    res = trainer.fit()
    assert res.accuracy_matrix.shape == (2, 2)
    assert np.isfinite(res.accuracy_matrix[np.tril_indices(2)]).all()
    assert all(np.isfinite(h["loss"]) for h in res.history)
    assert res.accuracy_matrix[1, 1] > 0.3  # learned the current task


# ---------------------------------------------------------------------------
# Tiered pjit backend: carry-vs-pjit sampled-representative parity
# ---------------------------------------------------------------------------


def _token_run(tiering: str, policy: str = "reservoir"):
    from repro.configs import get_reduced
    from repro.configs.base import ShapeConfig

    base = get_reduced("smollm-135m")
    cfg = type(base)(**{**base.__dict__, "vocab_size": 128, "num_layers": 2,
                        "name": "smollm-parity"})
    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=4,
                           num_representatives=3, num_candidates=6,
                           mode="async", tiering=tiering, hot_slots=4,
                           cold_slots=8, policy=policy, label_field="labels")
    return RunConfig(
        model=cfg, shape=ShapeConfig("parity", 16, 8, "train"),
        train=TrainConfig(optimizer="adamw", peak_lr=1e-3, warmup_steps=5,
                          linear_scaling=False, compute_dtype="float32"),
        rehearsal=rcfg,
        scenario=ScenarioConfig(name="class_incremental", modality="tokens",
                                strategy="rehearsal", num_tasks=2,
                                epochs_per_task=1, steps_per_epoch=6,
                                batch_size=8, vocab_size=128, seq_len=16,
                                auto_defaults=False))


@pytest.mark.parametrize("tiering", ["off", "host"])
def test_pjit_backend_matches_carry_fingerprints(tiering):
    """The acceptance pin of the tiered distributed path: a class-incremental
    run with ``tiering='on'`` through the pjit backend (1×1 mesh) produces
    bit-identical sampled-representative fingerprints (rep_checksum) and buffer
    fill levels to the carry backend — same seed, same RunConfig, same RNG
    lineage. ``tiering='off'`` pins the flat path to the same contract."""
    from repro.launch.mesh import make_mesh
    from repro.scenario import TokenClassIncremental

    run = _token_run(tiering)
    sc = TokenClassIncremental(run.scenario)
    mesh = make_mesh((1, 1), ("data", "model"))
    # exchange='local' on 1 worker == the carry backend's single-device draw
    pjit_res = ContinualTrainer(run, sc, mesh=mesh, exchange="local").fit()
    carry_res = ContinualTrainer(run, sc).fit()
    pj = [(h["rep_checksum"], h["buffer_fill"]) for h in pjit_res.history]
    ca = [(h["rep_checksum"], h["buffer_fill"]) for h in carry_res.history]
    assert pj == ca, (pj, ca)
    assert any(fill > 0 for _, fill in pj)
    assert any(ck != 0 for ck, _ in pj)  # representatives actually consumed
    if tiering == "host":
        # the tiered run really exceeded hot capacity at some point
        assert max(fill for _, fill in pj) > 2 * 4
        # the carry backend keeps both tiers in device memory and says so
        assert carry_res.step_meta == {"tiering": "host",
                                       "cold_placement": "device"}


def test_pjit_tiered_step_builder_no_longer_raises():
    """build_train_step materializes a TieredState (cold tier worker-sharded,
    device-fallback placement on CPU) instead of raising NotImplementedError."""
    from repro.buffer import TieredState
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step
    from repro.utils.compat import set_mesh

    run = _token_run("host")
    mesh = make_mesh((1, 1), ("data", "model"))
    with set_mesh(mesh):
        built = build_train_step(run, mesh, exchange="local", donate=False)
    assert built.meta["tiering"] == "host"
    assert built.meta["cold_slots_per_bucket"] == 8
    assert built.meta["cold_placement"] in ("pinned_host", "device")
    buffer_s = built.args[2]
    assert isinstance(buffer_s, TieredState)
    # worker axis on every leaf, hot + cold + staging all present
    assert buffer_s.hot.data["tokens"].shape == (1, 2, 4, 16)
    assert buffer_s.cold.data["tokens"]["raw"].shape == (1, 2, 8, 16)
    assert buffer_s.stage_valid.shape[0] == 1


# ---------------------------------------------------------------------------
# Dry-run tiered buffer cost model (satellite)
# ---------------------------------------------------------------------------


def test_rehearsal_buffer_cost_models_cold_tier():
    import types

    jax.devices()  # force backend init before dryrun touches XLA_FLAGS
    before = os.environ.get("XLA_FLAGS")
    try:
        from repro.launch.dryrun import rehearsal_buffer_cost
    finally:
        if before is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = before

    reps = {"tokens": jax.ShapeDtypeStruct((2, 7, 128), jnp.int32),
            "x": jax.ShapeDtypeStruct((2, 7, 64), jnp.float32)}
    built = types.SimpleNamespace(
        meta={"mode": "async", "slots_per_bucket": 16}, args=(0, 0, 0, reps, 0))
    flat = rehearsal_buffer_cost(
        built, RehearsalConfig(num_buckets=4, mode="async"))
    assert flat["cold_host_bytes"] == 0
    assert flat["hot_hbm_bytes"] == 4 * 16 * (128 * 4 + 64 * 4)
    assert flat["cold_placement"] is None
    tier = rehearsal_buffer_cost(
        built, RehearsalConfig(num_buckets=4, mode="async", tiering="host",
                               hot_slots=16, cold_slots=48))
    # the placement is decided by platform: the CPU keeps the cold tier in
    # device memory (pinned_host on an accelerator)
    assert tier["cold_placement"] == "device"  # CPU test runner
    # cold rows: int leaves raw (128*4B) + float leaves as one 128-lane int8
    # row (64 elements padded to 128) + 4B scale
    assert tier["cold_host_bytes"] == 4 * 48 * (128 * 4 + 128 + 4)
    assert tier["capacity_multiplier"] == 4.0
    assert tier["hot_hbm_bytes"] > flat["hot_hbm_bytes"]  # demotion staging rows
    off = rehearsal_buffer_cost(
        types.SimpleNamespace(meta={"mode": "off"}, args=()),
        RehearsalConfig(mode="off"))
    assert off["total_bytes"] == 0
