"""Continual-learning orchestration: the paper's experimental loop (§VI-A).

The loop itself now lives in ``repro.scenario.trainer.ContinualTrainer`` — one
facade composing scenario + step + buffer + prefetch + checkpoint + the Eq.-(1)
accuracy-matrix evaluation:

    accuracy_T = (1/T) * sum_j a_{T,j}

``run_continual`` remains as a **deprecated shim** mapping the historical
17-kwarg signature onto trainer overrides (bit-for-bit identical results —
the pinned parity contract in tests/test_scenario.py). New code should build a
``Scenario`` + ``ContinualTrainer`` instead.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class CLRunResult:
    strategy: str
    accuracy_matrix: np.ndarray  # a[i, j]: accuracy on task j after training task i
    task_runtimes: List[float]
    final_accuracy: float  # Eq. 1 at the end of training
    history: List[Dict[str, float]] = field(default_factory=list)
    # fault-tolerance accounting (zeros unless the trainer ran with resilience=)
    restarts: int = 0
    resilience_stats: Optional[Dict[str, float]] = None
    # per-key {last, mean, max, n} over the ``obs/*`` gauges folded into
    # ``history`` (None unless the run had ``run.obs.enabled``)
    obs: Optional[Dict[str, Dict[str, float]]] = None
    # the pjit backend's ``BuiltStep.meta`` (n_dp, cold_placement, ...); the
    # carry backend gives a tiered run's tiering and cold_placement ('device')
    step_meta: Optional[Dict[str, Any]] = None
    # the rehearsal buffer as training left it (None when nothing was stored)
    buffer: Any = None


def run_continual(
    *,
    strategy: str,
    num_tasks: int,
    epochs_per_task: int,
    steps_per_epoch: int,
    batch_fn: Callable[[int, int, int], Any],  # (task, batch_size, cursor) -> batch
    cumulative_batch_fn: Optional[Callable] = None,  # (upto_task, bs, cursor) -> batch
    eval_fn: Callable[[Any, int], float],  # (params, task) -> accuracy
    init_params_fn: Callable[[jax.Array], Any],
    init_opt_fn: Callable[[Any], Any],
    step_fn: Callable,  # from make_cl_step
    item_spec=None,
    rcfg=None,
    batch_size: int = 16,
    seed: int = 0,
    label_field: Optional[str] = None,  # None -> rcfg.label_field
    checkpoint_cb: Optional[Callable] = None,
) -> CLRunResult:
    """Deprecated: use ``repro.scenario.ContinualTrainer`` (DESIGN.md §7).

    Thin shim: the historical kwargs become trainer overrides; the trainer's
    carry backend runs the identical loop (same RNG lineage, same init, same
    history/eval cadence), so results are bit-for-bit unchanged.
    """
    from repro.configs.base import RunConfig, ScenarioConfig
    from repro.scenario.trainer import ContinualTrainer

    warnings.warn(
        "run_continual is deprecated; build a Scenario and use "
        "repro.scenario.ContinualTrainer instead (DESIGN.md §7)",
        DeprecationWarning, stacklevel=2)

    run = RunConfig(scenario=ScenarioConfig(
        strategy=strategy, num_tasks=num_tasks, epochs_per_task=epochs_per_task,
        steps_per_epoch=steps_per_epoch, batch_size=batch_size, seed=seed,
        auto_defaults=False))
    # prefetch=False: the legacy contract calls batch_fn synchronously on the
    # caller's thread, exactly n_steps times, in order — stateful batch_fns
    # that relied on that stay correct (scenario streams are pure and use the
    # prefetching path)
    trainer = ContinualTrainer(run, prefetch=False, overrides={
        "batch_fn": batch_fn,
        "cumulative_batch_fn": cumulative_batch_fn,
        "eval_fn": eval_fn,
        "init_params_fn": init_params_fn,
        "init_opt_fn": init_opt_fn,
        "step_fn": step_fn,
        "item_spec": item_spec,
        "rcfg": rcfg,
        "label_field": label_field,
        "checkpoint_cb": checkpoint_cb,
    })
    return trainer.fit()


def topk_accuracy(logits, labels, k: int = 5) -> jnp.ndarray:
    """Paper's metric: top-5 classification accuracy."""
    topk = jax.lax.top_k(logits, k)[1]
    return jnp.mean(jnp.any(topk == labels[:, None], axis=-1).astype(jnp.float32))
