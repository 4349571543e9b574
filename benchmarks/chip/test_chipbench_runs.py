"""CPU runs of the on-chip benchmark at a tiny size, with the look for a chip
skipped: a sound run of each kind of cell is correct, and a traced run reports
the per-layer metrics it can read there. The tiny cells are shared with
test_chipbench_faults.py."""
import io
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import runner  # noqa: E402

SEED = 2 ** 33 + 17

TINY_RESNET = {
    "name": "tiny-resnet", "source": "test", "family": "resnet", "entry": "carry",
    "image_size": 16, "channels": 3, "width": 8, "stage_blocks": [1, 1],
    "bottleneck_expansion": 4, "stem_kernel": 7, "stem_stride": 2, "stem_pool": 3,
    "num_classes": 8, "norm": "groupnorm", "norm_groups": 8, "norm_eps": 1e-5,
    "compute_dtype": "bfloat16",
    "train": {"optimizer": "sgd", "peak_lr": 0.0125, "warmup_steps": 100,
              "momentum": 0.9, "weight_decay": 1e-5, "grad_clip": 1.0,
              "linear_scaling": True, "max_scaled_lr": 64.0}}
TINY_IMAGES = {
    "records": "images", "num_tasks": 4, "classes_per_task": 2, "image_size": 16,
    "noise": 0.35, "window_task": 3, "batch_per_chip": 8, "reps": 2, "candidates": 4,
    "mode": "async", "policy": "reservoir", "buckets": 4, "slots_per_bucket": 6,
    "exchange": "full", "pool_batches": 4, "prefill_chunk": 8, "checked_steps": 3,
    "warmup_steps": 1, "trace_seconds": 0.2, "reference_block_rows": 4}
TINY_LLAMA = {
    "name": "tiny-llama", "source": "test", "family": "llama", "entry": "mesh",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "max_position_embeddings": 32, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": True, "hidden_act": "silu",
    "attention_bias": False, "compute_dtype": "bfloat16",
    "train": {"optimizer": "adamw", "peak_lr": 0.003, "warmup_steps": 20,
              "weight_decay": 1e-5, "grad_clip": 1.0, "linear_scaling": False,
              "max_scaled_lr": 64.0, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
              "remat": "full"}}
TINY_TOKENS = {
    "records": "tokens", "num_tasks": 4, "vocab_active": 256, "shared_frac": 0.25,
    "dirichlet_alpha": 0.05, "seq_len": 32, "window_task": 3, "batch_per_chip": 4,
    "reps": 1, "candidates": 2, "mode": "async", "policy": "reservoir", "buckets": 4,
    "slots_per_bucket": 8, "exchange": "full", "pool_batches": 4, "prefill_chunk": 8,
    "checked_steps": 3, "warmup_steps": 1, "trace_seconds": 0.2,
    "reference_block_rows": 1}
# each tiny cell is held to the limits of the cell it stands for
CELLS = {"cnn": (TINY_RESNET, TINY_IMAGES, "resnet50_async_flat"),
         "cnn_off": (TINY_RESNET, dict(TINY_IMAGES, mode="off", reps=0, candidates=0),
                     "resnet50_incremental"),
         "lm": (TINY_LLAMA, TINY_TOKENS, "smollm135m_async_flat")}


def _spec(name):
    cfg, tr, cell = CELLS[name]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    limits = json.load(open(os.path.join(HERE, "limits", cell + ".json")))
    return {"cell": {"name": name, "chips": 1}, "config": cfg, "traffic": tr,
            "limits": limits, "end_to_end": bench["end_to_end"],
            "per_layer": runner.cell_metrics(bench["per_layer"], cell)}


def _run(name, hook=None, trace=False, capture=None):
    return runner.run(name, SEED, 0.3, trace, root=ROOT, t_start=time.time(),
                      require_chip=False, spec=_spec(name), entry_hook=hook,
                      capture=capture, log=io.StringIO())


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"samples_per_s", "step_ms_p90", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0


def test_traced_run_reports_per_layer_metrics():
    res = _run("cnn", trace=True)
    assert res["correct"], res["checks"]
    # no peak and no device plane on the CPU: the readers of the peak and of
    # the device trace stay silent; the input counters are read
    assert set(res["metrics"]) == {"input_wait_pct", "dispatch_ms",
                                   "input_not_ready_pct", "input_convert_ms"}
