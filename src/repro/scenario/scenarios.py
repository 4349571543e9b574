"""The shipped scenarios: class-incremental (paper §VI-A), domain-incremental,
and blurry-boundary — each pairing a deterministic stream from ``repro.data``
with the rehearsal defaults that fit its shape (DESIGN.md §7).

``class_incremental`` is pinned to reproduce ``run_continual``'s results
bit-for-bit (tests/test_scenario.py::test_trainer_matches_run_continual); the
other two exist so scenario×policy combinations are expressible without
hand-wiring a fourth copy of the trainer plumbing.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import resnet50_cl
from repro.configs.base import ScenarioConfig
from repro.data import (
    BlurryBoundaryImages,
    BlurryStreamConfig,
    ClassIncrementalImages,
    DomainIncrementalImages,
    DomainStreamConfig,
    DriftStreamConfig,
    DriftTokenStream,
    ImageStreamConfig,
    TaskTokenStream,
    TokenStreamConfig,
)
from repro.scenario.base import Problem, Scenario, register_scenario
from repro.utils.platform import compute_dtype_of


def _stream_seed(cfg: ScenarioConfig) -> int:
    """Vision stream seed derived from the run seed, offset so data and model
    init never share a seed (tokens thread cfg.seed into TokenStreamConfig the
    same way): seed sweeps must change the data, not just the init."""
    return 1234 + cfg.seed


# ---------------------------------------------------------------------------
# Vision scenarios (CNN classifier, top-1 accuracy matrix)
# ---------------------------------------------------------------------------


# Images per evaluation call. A full-width eval set (1000 classes x 16 images
# at 224 px) is 2.4 GB of float32 input, and ResNet-50's activations for it do
# not fit one chip, so larger sets run in chunks of this size.
EVAL_CHUNK = 256


class _VisionScenario(Scenario):
    """Shared vision plumbing: CNN problem + top-1 accuracy eval."""

    label_field = "label"
    stream: Any  # set by subclass __init__
    _eval_cache: Optional[Dict[int, Dict[str, Any]]] = None

    @property
    def num_tasks(self) -> int:
        return self.stream.cfg.num_tasks

    @property
    def num_classes(self) -> int:
        return self.stream.num_classes

    @property
    def item_spec(self) -> Dict[str, Any]:
        c = self.stream.cfg
        spec = {
            "images": jax.ShapeDtypeStruct((c.image_size, c.image_size, c.channels),
                                           jnp.float32),
            "label": jax.ShapeDtypeStruct((), jnp.int32),
        }
        if self.task_field is not None:
            spec[self.task_field] = jax.ShapeDtypeStruct((), jnp.int32)
        return spec

    def batch(self, task, batch_size, cursor):
        return self.stream.batch(task, batch_size, cursor)

    def cumulative_batch(self, upto_task, batch_size, cursor):
        return self.stream.cumulative_batch(upto_task, batch_size, cursor)

    def eval_set(self, task):
        # the stream is a pure function of (seed, task): generate each task's
        # set once (the accuracy matrix reads task j after every task >= j)
        if self._eval_cache is None:
            self._eval_cache = {}
        if task not in self._eval_cache:
            self._eval_cache[task] = self.stream.eval_set(task)
        return self._eval_cache[task]

    def build_problem(self, run) -> Problem:
        from repro.models.model_zoo import cross_entropy
        from repro.models.resnet import apply_cnn, cnn_outputs, init_cnn

        ccfg = run.model if run.model is not None else resnet50_cl.reduced(
            num_classes=self.num_classes)
        if getattr(ccfg, "num_classes", self.num_classes) < self.num_classes:
            raise ValueError(
                f"model has {ccfg.num_classes} classes but scenario "
                f"{self.name!r} emits labels up to {self.num_classes - 1}"
            )

        dtype = compute_dtype_of(run.train.compute_dtype)

        def loss_fn(params, batch):
            logits = apply_cnn(params, batch["images"].astype(dtype), ccfg)
            return cross_entropy(logits[:, None, :],
                                 batch[self.label_field][:, None]), {}

        def forward_outputs(params, batch):
            return cnn_outputs(params, batch["images"].astype(dtype), ccfg)

        eval_logits = jax.jit(lambda p, im: apply_cnn(p, im.astype(dtype), ccfg))

        def eval_fn(params, task):
            ev = self.eval_set(task)
            images, labels = ev["images"], ev[self.label_field]
            n = len(labels)
            chunk = min(EVAL_CHUNK, n)
            # fixed-size chunks (the last one zero-padded) so one program
            # serves every chunk; the top-1 hits are joined before the mean,
            # so the result is the whole-set accuracy
            hits = []
            for i in range(0, n, chunk):
                im, lab = images[i:i + chunk], labels[i:i + chunk]
                pad = chunk - len(lab)
                if pad:
                    im = np.concatenate([im, np.zeros((pad,) + im.shape[1:], im.dtype)])
                    lab = np.concatenate([lab, np.zeros((pad,), lab.dtype)])
                top1 = jax.lax.top_k(eval_logits(params, jnp.asarray(im)), 1)[1]
                hits.append(jnp.any(top1 == jnp.asarray(lab)[:, None], axis=-1))
            return float(jnp.mean(jnp.concatenate(hits)[:n].astype(jnp.float32)))

        return Problem(lambda k: init_cnn(k, ccfg), loss_fn, eval_fn,
                       forward_outputs=forward_outputs)


class ClassIncremental(_VisionScenario):
    """The paper's scenario: T disjoint tasks, each introducing new classes.
    Buckets by task id, reservoir policy — exactly Algorithm 1."""

    name = "class_incremental"
    task_field = "task"

    def __init__(self, cfg: Optional[ScenarioConfig] = None, stream=None):
        cfg = cfg or ScenarioConfig()
        self.stream = stream if stream is not None else ClassIncrementalImages(
            ImageStreamConfig(
                num_tasks=cfg.num_tasks, classes_per_task=cfg.classes_per_task,
                image_size=cfg.image_size, noise=cfg.noise, seed=_stream_seed(cfg)))

    def recommended(self):
        return {"num_buckets": self.num_tasks, "policy": "reservoir",
                "label_field": "label", "task_field": "task"}


class DomainIncremental(_VisionScenario):
    """One label space, T input distributions (per-domain style transform).
    Buckets by domain; the class-balanced policy keeps per-class coverage
    inside each domain bucket, which reservoir sampling does not guarantee
    when domains repeat classes unevenly."""

    name = "domain_incremental"
    task_field = "task"

    def __init__(self, cfg: Optional[ScenarioConfig] = None, stream=None):
        cfg = cfg or ScenarioConfig(name="domain_incremental")
        self.stream = stream if stream is not None else DomainIncrementalImages(
            DomainStreamConfig(
                num_tasks=cfg.num_tasks, num_classes=cfg.num_classes,
                image_size=cfg.image_size, noise=cfg.noise,
                domain_shift=cfg.domain_shift, seed=_stream_seed(cfg)))

    def recommended(self):
        return {"num_buckets": self.num_tasks, "policy": "class_balanced",
                "label_field": "label", "task_field": "task"}


class BlurryBoundary(_VisionScenario):
    """Probabilistic task mixing near boundaries; batches carry NO task id, so
    the buffer buckets by label (the task_field-free path): K = num_classes,
    one bucket per class — the paper's vision bucketing mode, minus the clean
    task signal."""

    name = "blurry_boundary"
    task_field = None

    def __init__(self, cfg: Optional[ScenarioConfig] = None, stream=None):
        cfg = cfg or ScenarioConfig(name="blurry_boundary")
        self.stream = stream if stream is not None else BlurryBoundaryImages(
            BlurryStreamConfig(
                num_tasks=cfg.num_tasks, classes_per_task=cfg.classes_per_task,
                image_size=cfg.image_size, noise=cfg.noise,
                task_len=cfg.steps_per_task, blur=cfg.blur,
                seed=_stream_seed(cfg)))

    def recommended(self):
        # task_field -> the label field: bucketing keyed on class ids
        return {"num_buckets": self.num_classes, "policy": "reservoir",
                "label_field": "label", "task_field": "label"}

    def cumulative_batch(self, upto_task, batch_size, cursor):
        raise NotImplementedError(
            "blurry_boundary has no clean per-task view to accumulate "
            "(no task ids) — the from_scratch strategy does not apply")


# ---------------------------------------------------------------------------
# Token (LM) class-incremental: the quickstart / CLI-trainer stream
# ---------------------------------------------------------------------------


def build_token_lm(run, vocab_size: int):
    """Build the token-scenario LM and its forward contexts from a RunConfig.

    Shared by :class:`TokenClassIncremental`, :class:`DriftStream` and the
    serving engine (``repro.serving``) so the params trained online are the
    exact tree the decode path consumes. Returns ``(model, ctx, eval_ctx)``
    where ``ctx`` honours the run's compute dtype / remat / scan_layers and
    ``eval_ctx`` is the float32 no-remat evaluation context.
    """
    from repro.configs import get_reduced
    from repro.models import StackCtx, build_model

    cfg = run.model
    if cfg is None:
        base = get_reduced("smollm-135m")
        cfg = type(base)(**{**base.__dict__,
                            "vocab_size": vocab_size,
                            "num_layers": 2})
    model = build_model(cfg)
    dtype = compute_dtype_of(run.train.compute_dtype)
    # scan_layers mirrors the pjit backend's StackCtx so tap strategies
    # (DER stored logits) produce bit-identical forwards on both backends
    ctx = StackCtx(cfg=cfg, compute_dtype=dtype, remat=run.train.remat,
                   scan_layers=run.train.scan_layers)
    eval_ctx = StackCtx(cfg=cfg, compute_dtype=jnp.float32, remat="none")
    return model, ctx, eval_ctx


class TokenClassIncremental(Scenario):
    """Class-incremental over token distributions: each task a disjoint Markov-1
    vocab range (the LM analogue of new classes). Metric: per-task eval LOSS
    (lower is better) — recorded in the same matrix slot accuracy occupies for
    the vision scenarios."""

    name = "class_incremental"
    label_field = "labels"
    task_field = "task"

    def __init__(self, cfg: Optional[ScenarioConfig] = None, stream=None,
                 eval_n: int = 16):
        cfg = cfg or ScenarioConfig(modality="tokens")
        self.cfg = cfg
        self.eval_n = eval_n
        self.stream = stream if stream is not None else TaskTokenStream(TokenStreamConfig(
            num_tasks=cfg.num_tasks, vocab_size=cfg.vocab_size,
            seq_len=cfg.seq_len, seed=cfg.seed))

    @property
    def num_tasks(self) -> int:
        return self.stream.cfg.num_tasks

    @property
    def seq_len(self) -> int:
        return self.stream.cfg.seq_len

    @property
    def item_spec(self) -> Dict[str, Any]:
        s = self.seq_len
        return {"tokens": jax.ShapeDtypeStruct((s,), jnp.int32),
                "labels": jax.ShapeDtypeStruct((s,), jnp.int32),
                "task": jax.ShapeDtypeStruct((), jnp.int32)}

    def batch(self, task, batch_size, cursor):
        return self.stream.batch(task, batch_size, cursor)

    def eval_set(self, task):
        return self.stream.eval_set(task, n=self.eval_n)

    def recommended(self):
        return {"num_buckets": self.num_tasks, "policy": "reservoir",
                "label_field": "labels", "task_field": "task"}

    def build_problem(self, run) -> Problem:
        model, ctx, eval_ctx = build_token_lm(run, self.stream.cfg.vocab_size)

        def loss_fn(params, batch):
            loss, _ = model.loss(params, batch, ctx)
            return loss, {}

        def forward_outputs(params, batch):
            return model.outputs(params, batch, ctx)

        eval_loss = jax.jit(lambda p, ev: model.loss(p, ev, eval_ctx)[0])

        def eval_fn(params, task):
            ev = {k: jnp.asarray(v) for k, v in self.eval_set(task).items()}
            return float(eval_loss(params, ev))

        return Problem(lambda k: model.init(k, self.seq_len), loss_fn, eval_fn,
                       forward_outputs=forward_outputs)


class DriftStream(Scenario):
    """Task-free LM stream: the token distribution drifts continuously across
    ``num_tasks`` anchors with **no task ids and no schedule** (the AML
    ``task_free`` setting). Records carry a content-derived scalar ``label``
    (majority vocab band) and the buffer buckets by it — the token analogue of
    ``blurry_boundary``'s label bucketing. ``num_tasks`` is reinterpreted as
    the anchor count: eval slices are the pure anchors, so the accuracy matrix
    stays well-defined even though training never sees a clean phase.

    Metric: next-token top-1 **accuracy** (higher is better) — the online
    serving freshness benchmarks (fig8) compare drifted-slice accuracy of a
    continually-updated model against frozen weights.
    """

    name = "drift_stream"
    label_field = "labels"
    task_field = None

    def __init__(self, cfg: Optional[ScenarioConfig] = None, stream=None,
                 eval_n: int = 16):
        cfg = cfg or ScenarioConfig(name="drift_stream", modality="tokens")
        self.cfg = cfg
        self.eval_n = eval_n
        self.stream = stream if stream is not None else DriftTokenStream(
            DriftStreamConfig(
                num_phases=cfg.num_tasks, vocab_size=cfg.vocab_size,
                seq_len=cfg.seq_len, phase_len=cfg.steps_per_task,
                seed=cfg.seed))

    @property
    def num_tasks(self) -> int:
        return self.stream.cfg.num_phases

    @property
    def seq_len(self) -> int:
        return self.stream.cfg.seq_len

    @property
    def buffer_task_field(self) -> str:
        # label_field stays "labels" (the [S] shifted targets the loss masks
        # on); bucketing keys on the scalar content-derived band instead.
        return "label"

    @property
    def item_spec(self) -> Dict[str, Any]:
        s = self.seq_len
        return {"tokens": jax.ShapeDtypeStruct((s,), jnp.int32),
                "labels": jax.ShapeDtypeStruct((s,), jnp.int32),
                "label": jax.ShapeDtypeStruct((), jnp.int32)}

    def batch(self, task, batch_size, cursor):
        # task-free: the stream only reads the global cursor
        return self.stream.batch(task, batch_size, cursor)

    def eval_set(self, task):
        return self.stream.eval_set(task, n=self.eval_n)

    def recommended(self):
        # one bucket per vocab band; task_field -> the scalar band label
        return {"num_buckets": self.num_tasks, "policy": "reservoir",
                "label_field": "labels", "task_field": "label"}

    def cumulative_batch(self, upto_task, batch_size, cursor):
        raise NotImplementedError(
            "drift_stream has no per-task view to accumulate (task-free "
            "stream) — the from_scratch strategy does not apply")

    def build_problem(self, run) -> Problem:
        model, ctx, eval_ctx = build_token_lm(run, self.stream.cfg.vocab_size)

        def loss_fn(params, batch):
            loss, _ = model.loss(params, batch, ctx)
            return loss, {}

        def forward_outputs(params, batch):
            return model.outputs(params, batch, ctx)

        eval_logits = jax.jit(lambda p, b: model.forward(p, b, eval_ctx)[0])

        def eval_fn(params, task):
            ev = {k: jnp.asarray(v) for k, v in self.eval_set(task).items()}
            pred = jnp.argmax(eval_logits(params, {"tokens": ev["tokens"]}),
                              axis=-1)
            return float(jnp.mean((pred == ev["labels"]).astype(jnp.float32)))

        return Problem(lambda k: model.init(k, self.seq_len), loss_fn, eval_fn,
                       forward_outputs=forward_outputs)


def _class_incremental_factory(cfg: ScenarioConfig) -> Scenario:
    if cfg.modality == "tokens":
        return TokenClassIncremental(cfg)
    return ClassIncremental(cfg)


register_scenario("class_incremental", _class_incremental_factory)
register_scenario("domain_incremental", DomainIncremental)
register_scenario("blurry_boundary", BlurryBoundary)
register_scenario("drift_stream", DriftStream)
