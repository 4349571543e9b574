"""Compile-only checks of the chip path against a described TPU v5e.

The TPU compiler is installed without a chip attached: it compiles for a
topology that is described, not present, and refuses what Mosaic or XLA:TPU
would refuse on the chip (unaligned slices, memory-space moves it cannot
lower). Interpret mode on the CPU sees none of that.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under a multi-worker pytest
every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import quantize as qz
from repro.kernels import rehearsal_ops as ro

L = 224 * 224 * 3  # one ImageNet record: 150528 elements
W = L // 128  # lane rows of its int8 slab
TABLE_ROWS = 4096
STAGED, SAMPLED = 56, 7


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_cases(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return {
        "quantize_rows": (qz.quantize_rows, (s((STAGED, L), jnp.float32),)),
        "dequantize_rows": (qz.dequantize_rows,
                            (s((SAMPLED, L), jnp.int8),
                             s((SAMPLED, 1), jnp.float32))),
        "gather_dequant_rows": (ro.gather_dequant_rows,
                                (s((TABLE_ROWS, W, 128), jnp.int8),
                                 s((SAMPLED, 1), jnp.float32),
                                 s((SAMPLED,), jnp.int32))),
        "encode_scatter_rows": (ro.encode_scatter_rows,
                                (s((TABLE_ROWS, W, 128), jnp.int8),
                                 s((STAGED, W, 128), jnp.float32),
                                 s((STAGED,), jnp.int32))),
    }


@pytest.mark.parametrize("name", ["quantize_rows", "dequantize_rows",
                                  "gather_dequant_rows", "encode_scatter_rows"])
def test_kernel_compiles_for_v5e_at_image_width(one_chip, name):
    fn, args = _kernel_cases(one_chip)[name]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("record,dtype", [((W, 128), jnp.int8),
                                          ((2048,), jnp.int32)])
def test_host_row_moves_compile_for_v5e(one_chip, record, dtype):
    """The cold tier's host-memory rows: an image slab and a token row move
    between pinned_host and HBM one row at a time, and the table stays in
    host memory (its bytes never appear in the program's device memory)."""
    from repro.buffer.tiered import host_gather_rows, host_scatter_rows

    host = one_chip.with_memory_kind("pinned_host")
    k, slots = 4, 768
    table = jax.ShapeDtypeStruct((k, slots) + record, dtype, sharding=host)
    sampled = jax.ShapeDtypeStruct((SAMPLED,), jnp.int32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((STAGED,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((STAGED,) + record, dtype, sharding=one_chip)
    table_bytes = k * slots * int(np.prod(record)) * jnp.dtype(dtype).itemsize

    got = jax.jit(host_gather_rows, out_shardings=one_chip).lower(
        table, sampled).compile()
    put = jax.jit(host_scatter_rows, out_shardings=host,
                  donate_argnums=0).lower(table, rows, vals).compile()
    for compiled in (got, put):
        assert compiled.memory_analysis().temp_size_in_bytes < table_bytes // 4


def test_tiered_lm_step_compiles_with_cold_tier_in_pinned_host(topo):
    """The pjit train step with a tiered buffer, built for one described
    v5e chip: the cold tier's token rows are placed in pinned_host and the
    step moves only the sampled and demoted rows."""
    from jax.sharding import Mesh

    from repro.configs import get_reduced
    from repro.configs.base import (RehearsalConfig, RunConfig,
                                    ScenarioConfig, ShapeConfig, TrainConfig)
    from repro.launch.steps import build_train_step

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    run = RunConfig(
        model=get_reduced("smollm-135m"), shape=ShapeConfig("t", 128, 8, "train"),
        train=TrainConfig(optimizer="adamw", compute_dtype="bfloat16"),
        rehearsal=RehearsalConfig(num_buckets=2, mode="async",
                                  slots_per_bucket=16, tiering="host"),
        scenario=ScenarioConfig(modality="tokens", batch_size=8, seq_len=128))
    built = build_train_step(run, mesh, buffer_budget_bytes=None)
    assert built.meta["cold_placement"] == "pinned_host"
    tokens_sh = built.shardings[2].cold.data["tokens"]["raw"]
    assert tokens_sh.memory_kind == "pinned_host"
    args = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        built.args, built.shardings)
    with jax.set_mesh(mesh):
        compiled = built.fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_tiered_image_buffer_update_compiles_with_cold_tier_in_pinned_host(
        topo, monkeypatch):
    """The distributed rehearsal-buffer update at the ImageNet record width,
    built for one described v5e chip: the cold image slabs are placed in
    pinned_host, the step quantizes demoted rows with the Pallas kernel, and
    doubling the cold tier (768 -> 1536 slots per bucket) adds none of its
    bytes to the program's device memory."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.buffer import tiered
    from repro.configs.base import RehearsalConfig
    from repro.core import distributed as dist
    from repro.kernels import ops

    # the process's backend is the CPU, which would interpret the kernels;
    # compile them as the chip does
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, P())
    b, k = 14, 4
    item_s = {"images": jax.ShapeDtypeStruct((224, 224, 3), jnp.float32),
              "label": jax.ShapeDtypeStruct((), jnp.int32),
              "task": jax.ShapeDtypeStruct((), jnp.int32)}

    def build(cold):
        rcfg = RehearsalConfig(num_buckets=k, num_representatives=SAMPLED,
                               num_candidates=b, mode="async", tiering="host",
                               hot_slots=16, cold_slots=cold,
                               label_field="label", task_field="task")
        state_s = jax.eval_shape(
            lambda: dist.init_distributed_from_config(item_s, rcfg, 1))
        state_sh = tiered.cold_shardings(state_s, mesh, ("data",))
        host = [(s, sh) for s, sh in zip(
            jax.tree_util.tree_leaves(state_s.cold.data),
            jax.tree_util.tree_leaves(state_sh.cold.data))
            if tiered.host_resident(s.shape[3:])]
        assert host and all(sh.memory_kind == "pinned_host" for _, sh in host)
        spec = lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
        args = (jax.tree_util.tree_map(spec, state_s, state_sh),
                {n: jax.ShapeDtypeStruct((b,) + s.shape, s.dtype, sharding=rep)
                 for n, s in item_s.items()},
                jax.ShapeDtypeStruct((b,), jnp.int32, sharding=rep),
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep))
        update = dist.make_sharded_update(mesh, ("data",), rcfg,
                                          exchange="local")
        with jax.set_mesh(mesh):
            compiled = jax.jit(update, out_shardings=(state_sh, None, None),
                               donate_argnums=0).lower(*args).compile()
        table = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s, _ in host)
        return compiled, table

    small, table = build(768)
    large, _ = build(1536)
    assert "tpu_custom_call" in small.as_text()
    a, c = small.memory_analysis(), large.memory_analysis()
    device_bytes = lambda m: m.temp_size_in_bytes + m.argument_size_in_bytes
    assert device_bytes(c) - device_bytes(a) < table // 100
