"""Named scopes of the fused continual step (repro.obs.scopes, DESIGN.md §11).

Each stage of the step is written under ``jax.named_scope(<stage>)`` in the
shared code both step builders call, so the compiled HLO of every step form
carries the stage in the ``op_name`` of its instructions, and ``scope_of``
reads it back through JAX's transform wrappers. Scopes are metadata only:
the fingerprint pins elsewhere (test_obs, test_pipelined, test_buffer_policies)
hold unchanged with them in place.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import RehearsalConfig
from repro.core import init_carry, make_cl_step
from repro.obs.scopes import scope, scope_of, scopes_in_hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOCAL_STAGES = {"train", "optimizer", "buffer_update", "buffer_sample", "augment"}


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/train/jvp(loss)/dot_general", "train"),
    ("jit(step)/train/transpose(jvp(loss))/mul", "train"),
    ("jit(f)/transpose(jvp(train))/add_any", "train"),
    ("jit(step)/jvp(optimizer)/mul", "optimizer"),
    ("jit(step)/buffer_update/while/body/scatter", "buffer_update"),
    ("jit(step)/shard_map/buffer_sample/exchange/all_to_all", "exchange"),
    ("jit(step)/buffer_update/vmap(buffer_sample)/gather", "buffer_sample"),
    ("jit(step)/trainer/reduce_sum", None),
    ("jit(step)/reduce_sum", None),
    ("", None),
    (None, None),
])
def test_scope_of_reads_the_innermost_stage(op_name, want):
    assert scope_of(op_name) == want


def test_scope_of_real_names_of_a_differentiated_scope():
    """The names JAX gives a scope's forward and backward ops both read as
    the stage, whether the scope wraps ``value_and_grad`` or sits inside it."""
    def loss(w, x):
        with jax.named_scope("train"):
            return jnp.sum(jnp.tanh(x @ w) ** 2)

    def f(w, x):
        with scope("optimizer"):
            return w - 0.1 * jax.grad(loss)(w, x)

    txt = jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((4, 8))).as_text(
        debug_info=True)
    names = [n for n in txt.split('"') if "transpose(jvp(train))" in n]
    assert names and all(scope_of(n) == "train" for n in names)
    assert scopes_in_hlo(jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((4, 8)))
                         .compile().as_text()) >= {"train", "optimizer"}


def test_unknown_scope_is_refused():
    with pytest.raises(ValueError):
        scope("backward")


def _linear_loss(params, batch):
    logits = batch["x"] @ params["w"]
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits)
    ok = labels >= 0
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], 1)[:, 0]
    return jnp.sum(jnp.where(ok, nll, 0.0)) / jnp.maximum(jnp.sum(ok), 1), {}


def _sgd(grads, opt, params):
    return jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads), opt, {}


@pytest.mark.parametrize("tiering", ["off", "host"])
def test_compiled_cl_step_carries_each_stage(tiering):
    kw = {} if tiering == "off" else dict(tiering="host", hot_slots=4, cold_slots=8)
    rcfg = RehearsalConfig(num_buckets=4, slots_per_bucket=4, num_representatives=2,
                           num_candidates=4, mode="async", **kw)
    spec = {"x": jax.ShapeDtypeStruct((8,), jnp.float32),
            "label": jax.ShapeDtypeStruct((), jnp.int32)}
    step = make_cl_step(_linear_loss, _sgd, rcfg, strategy="rehearsal",
                        exchange="local", label_field="label", task_field="label",
                        donate=False, sanitize=False)
    carry = init_carry({"w": jnp.zeros((8, 4))}, None, spec, rcfg, label_field="label")
    batch = {"x": jnp.ones((6, 8)), "label": jnp.arange(6) % 4}
    hlo = step.lower(carry, batch, jax.random.PRNGKey(0)).compile().as_text()
    assert scopes_in_hlo(hlo) >= LOCAL_STAGES


def _token_run(mode="async"):
    from repro.configs import get_reduced
    from repro.configs.base import RunConfig, ShapeConfig, TrainConfig

    cfg = get_reduced("smollm-135m")
    cfg = type(cfg)(**{**cfg.__dict__, "vocab_size": 128, "num_layers": 1})
    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=2, num_representatives=1,
                           num_candidates=4, mode=mode, label_field="labels")
    return RunConfig(model=cfg, shape=ShapeConfig("t", 16, 4, "train"),
                     rehearsal=rcfg,
                     train=TrainConfig(optimizer="sgd", warmup_steps=5,
                                       linear_scaling=False, compute_dtype="float32"))


@pytest.mark.parametrize("mode,want", [
    ("async", LOCAL_STAGES),
    ("sync", LOCAL_STAGES),
    ("off", {"train", "optimizer"}),
])
def test_compiled_mesh_step_carries_each_stage(mode, want):
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step
    from repro.utils.compat import set_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    with set_mesh(mesh):
        built = build_train_step(_token_run(mode), mesh, exchange="full",
                                 buffer_budget_bytes=None, donate=False)
        hlo = built.fn.lower(*built.args).compile().as_text()
    got = scopes_in_hlo(hlo)
    assert got >= want
    if mode == "off":
        assert not got & {"buffer_update", "buffer_sample", "augment"}


def test_four_device_steps_carry_exchange_and_allreduce():
    """On 4 virtual CPU devices the mesh step's exchange="full" all_to_all
    keeps its ``exchange`` scope, and the manual-DP carry step adds the
    ``grad_allreduce`` of its explicit psum."""
    code = """
        import jax, jax.numpy as jnp
        import sys
        sys.path.insert(0, %r)
        from test_scopes import LOCAL_STAGES, _linear_loss, _sgd, _token_run
        from repro.configs.base import RehearsalConfig
        from repro.core import init_carry, make_cl_step
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_train_step
        from repro.obs.scopes import scopes_in_hlo
        from repro.utils.compat import set_mesh

        mesh = make_mesh((4, 1), ("data", "model"))
        with set_mesh(mesh):
            built = build_train_step(_token_run(), mesh, exchange="full",
                                     buffer_budget_bytes=None, donate=False)
            got = scopes_in_hlo(built.fn.lower(*built.args).compile().as_text())
        assert got >= LOCAL_STAGES | {"exchange"}, got

        rcfg = RehearsalConfig(num_buckets=4, slots_per_bucket=4,
                               num_representatives=2, num_candidates=4,
                               mode="async")
        spec = {"x": jax.ShapeDtypeStruct((8,), jnp.float32),
                "label": jax.ShapeDtypeStruct((), jnp.int32)}
        step = make_cl_step(_linear_loss, _sgd, rcfg, strategy="rehearsal",
                            mesh=mesh, exchange="full", label_field="label",
                            task_field="label", donate=False, sanitize=False)
        carry = init_carry({"w": jnp.zeros((8, 4))}, None, spec, rcfg,
                           label_field="label", n_dp=4)
        batch = {"x": jnp.ones((8, 8)), "label": jnp.arange(8) %% 4}
        # the step builds its shard_map program on first call; lowering it
        # inside an outer jit compiles that program as it runs
        hlo = jax.jit(step).lower(carry, batch, jax.random.PRNGKey(0))
        got = scopes_in_hlo(hlo.compile().as_text())
        assert got >= LOCAL_STAGES | {"exchange", "grad_allreduce"}, got
        print("SCOPES_OK")
    """ % os.path.join(REPO, "tests")
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=480, env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "SCOPES_OK" in p.stdout
