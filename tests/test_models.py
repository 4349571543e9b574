"""Per-architecture smoke tests (reduced configs, CPU) + model-component units.

Every assigned architecture: one forward + one train-grad step, asserting output
shapes and finite values; decode-step consistency where cheap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_reduced
from repro.models import StackCtx, build_model
from repro.models import attention as A
from repro.models.model_zoo import cross_entropy


def batch_for(cfg, b, s, key=None):
    key = key or jax.random.PRNGKey(0)
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    base = {"labels": toks, "task": jnp.zeros((b,), jnp.int32)}
    if cfg.family == "encdec":
        return dict(base, frames=jax.random.normal(key, (b, s, cfg.d_model)) * 0.1,
                    tokens=toks)
    if cfg.frontend == "patch_stub":
        pos = jnp.broadcast_to(jnp.arange(s)[None, :, None], (b, s, 3))
        return dict(base, embeddings=jax.random.normal(key, (b, s, cfg.d_model)) * 0.1,
                    positions=pos)
    return dict(base, tokens=toks)


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_grad(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key, max_seq=32)
    ctx = StackCtx(cfg=cfg, compute_dtype=jnp.float32, remat="none")
    b, s = 2, 32
    batch = batch_for(cfg, b, s)

    logits, aux = jax.jit(lambda p, bt: model.forward(p, bt, ctx))(params, batch)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), arch

    grads = jax.jit(jax.grad(lambda p, bt: model.loss(p, bt, ctx)[0]))(params, batch)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_decode(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), max_seq=32)
    ctx = StackCtx(cfg=cfg, compute_dtype=jnp.float32, remat="none")
    caches = model.init_cache(params, 2, 32)
    db = ({"embedding": jnp.zeros((2, 1, cfg.d_model))} if cfg.frontend == "patch_stub"
          else {"token": jnp.zeros((2, 1), jnp.int32)})
    logits, new_caches = jax.jit(
        lambda p, b_, c, i: model.decode(p, b_, c, i, ctx)
    )(params, db, caches, jnp.int32(5))
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), arch
    assert jax.tree_util.tree_structure(new_caches) == jax.tree_util.tree_structure(caches)


def test_decode_matches_prefill_dense():
    """Greedy decode logits == teacher-forced forward logits (dense llama family)."""
    cfg = get_reduced("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1), max_seq=16)
    ctx = StackCtx(cfg=cfg, compute_dtype=jnp.float32, remat="none")
    b, s = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size)
    full_logits, _ = model.forward(params, {"tokens": toks}, ctx)

    caches = model.init_cache(params, b, s, dtype=jnp.float32)
    outs = []
    for t in range(s):
        logits, caches = model.decode(params, {"token": toks[:, t:t + 1]}, caches,
                                      jnp.int32(t), ctx)
        outs.append(logits)
    dec_logits = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits), np.asarray(full_logits),
                               atol=2e-3, rtol=2e-3)


def test_decode_matches_prefill_swa():
    """Ring-buffer SWA cache reproduces windowed attention exactly."""
    cfg = get_reduced("h2o-danube-1.8b")
    assert cfg.sliding_window and cfg.sliding_window < 128
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1), max_seq=128)
    ctx = StackCtx(cfg=cfg, compute_dtype=jnp.float32, remat="none")
    b, s = 1, 128  # > window: the ring must wrap
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size)
    full_logits, _ = model.forward(params, {"tokens": toks}, ctx)
    caches = model.init_cache(params, b, s, dtype=jnp.float32)
    step = jax.jit(lambda p, bt, c, i: model.decode(p, bt, c, i, ctx))
    outs = []
    for t in range(s):
        logits, caches = step(params, {"token": toks[:, t:t + 1]}, caches, jnp.int32(t))
        outs.append(logits)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec[:, -8:]), np.asarray(full_logits[:, -8:]),
                               atol=5e-3, rtol=5e-3)


def test_blocked_attention_equals_naive():
    cfg = get_reduced("mixtral-8x7b")
    key = jax.random.PRNGKey(0)
    b, s = 2, 128
    q = jax.random.normal(key, (b, s, cfg.num_heads, cfg.head_dim))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, cfg.num_kv_heads, cfg.head_dim))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, cfg.num_kv_heads, cfg.head_dim))
    scale = cfg.head_dim ** -0.5
    scores = A._grouped_scores(q * scale, k).astype(jnp.float32)
    m = A.causal_mask(s, s, cfg.sliding_window)
    scores = jnp.where(m[None, None, None], scores, A.NEG_INF)
    want = A._grouped_out(jax.nn.softmax(scores, -1), v)
    got = A.attend_blocked(q, k, v, cfg, block_k=32)
    np.testing.assert_allclose(np.asarray(got.reshape(want.shape)), np.asarray(want),
                               atol=1e-5)


def test_mrope_sections_differ_from_1d():
    """M-RoPE with distinct (t,h,w) positions must differ from flat positions."""
    from repro.models.layers import rope_angles

    pos3 = jnp.stack([jnp.arange(8), jnp.arange(8) * 2, jnp.arange(8) * 3], axis=-1)[None]
    a3 = rope_angles(pos3, 32, 1e4, m_rope_sections=(6, 5, 5))
    a1 = rope_angles(jnp.arange(8)[None], 32, 1e4)
    assert a3.shape == a1.shape == (1, 8, 16)
    assert not np.allclose(np.asarray(a3), np.asarray(a1))


def test_moe_routing_conservation():
    """Every kept (token, expert) pair contributes gate-weighted output exactly once;
    with capacity_factor >= E/topk nothing drops and gates sum to 1 per token."""
    import dataclasses
    from repro.models import moe as M

    cfg = dataclasses.replace(get_reduced("phi3.5-moe-42b-a6.6b"), capacity_factor=8.0)
    params = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model)) * 0.3
    y, aux = M.moe_ffn(params, x, cfg)
    assert y.shape == x.shape and bool(jnp.isfinite(y).all())
    # compare against dense (every expert on every token, gate-weighted) reference
    gates, experts, _ = M.route(params, x, cfg)
    h_all = jnp.einsum("td,edf->tef", x, params["wi"])
    g_all = jnp.einsum("td,edf->tef", x, params["wg"])
    o_all = jnp.einsum("tef,efd->ted", jax.nn.silu(g_all) * h_all, params["wo"])
    want = jnp.zeros_like(x)
    for kk in range(cfg.num_experts_per_tok):
        want = want + gates[:, kk, None] * o_all[jnp.arange(32), experts[:, kk]]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_cross_entropy_masking():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8))
    labels = jnp.array([[1, 2, -1, -1], [3, -1, -1, -1]])
    ce = cross_entropy(logits, labels)
    # equals mean over the 3 valid positions only
    full = -jax.nn.log_softmax(logits, -1)
    want = (full[0, 0, 1] + full[0, 1, 2] + full[1, 0, 3]) / 3
    np.testing.assert_allclose(float(ce), float(want), rtol=1e-5)


def test_resnet_forward():
    from repro.configs import resnet50_cl
    from repro.models.resnet import apply_cnn, init_cnn

    for variant in ("resnet18", "ghostnet"):
        ccfg = resnet50_cl.reduced(num_classes=10)
        ccfg = type(ccfg)(**{**ccfg.__dict__, "variant": variant})
        params = init_cnn(jax.random.PRNGKey(0), ccfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits = apply_cnn(params, x, ccfg)
        assert logits.shape == (2, 10) and bool(jnp.isfinite(logits).all())


def test_resnet_imagenet_stem_forward():
    """The published ImageNet configs use the 7x7 stride-2 conv + 3x3
    stride-2 max-pool stem; the 32 px reduced config keeps the 3x3 stem."""
    from repro.configs import resnet50_cl
    from repro.models.resnet import apply_cnn, init_cnn

    ccfg = resnet50_cl.full()
    assert ccfg.stem == "imagenet" and resnet50_cl.reduced().stem == "cifar"
    small = type(ccfg)(**{**ccfg.__dict__, "width": 8, "stage_blocks": (1, 1),
                          "num_classes": 10})
    params = init_cnn(jax.random.PRNGKey(0), small)
    assert params["stem"].shape == (7, 7, 3, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
    logits = apply_cnn(params, x, small)
    assert logits.shape == (2, 10) and bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("b,s,v,pieces", [(3, 64, 512, 4), (2, 32, 1000, 4),
                                          (3, 64, 512, 1)])
def test_chunked_cross_entropy_matches_one_block(b, s, v, pieces):
    """The sequence-chunked CE of the decoder loss equals the one-block CE,
    gradients included: bit for bit as one chunk (the size the decoder loss
    uses below ``LOGITS_BLOCK_BYTES``), up to summation order in more."""
    from repro.models.model_zoo import chunked_cross_entropy

    key = jax.random.PRNGKey(0)
    hidden = jax.random.normal(key, (b, s, 16))
    table = jax.random.normal(jax.random.fold_in(key, 1), (v, 16))
    labels = jax.random.randint(jax.random.fold_in(key, 2), (b, s), -1, v)

    def one_block(t):
        return cross_entropy(jnp.einsum("bsd,vd->bsv", hidden, t), labels)

    def chunked(t):
        return chunked_cross_entropy(
            lambda h: jnp.einsum("bsd,vd->bsv", h, t), hidden, labels, s // pieces)

    if pieces == 1:
        assert float(chunked(table)) == float(one_block(table))
        assert np.array_equal(np.asarray(jax.grad(chunked)(table)),
                              np.asarray(jax.grad(one_block)(table)))
        return
    np.testing.assert_allclose(float(chunked(table)), float(one_block(table)),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(jax.grad(chunked)(table)),
                               np.asarray(jax.grad(one_block)(table)),
                               rtol=1e-4, atol=1e-6)


def test_scan_vs_unroll_equivalence():
    """scan_layers=False (dry-run unrolled path) is numerically identical."""
    cfg = get_reduced("jamba-v0.1-52b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), max_seq=16)
    batch = batch_for(cfg, 1, 16)
    ctx_s = StackCtx(cfg=cfg, compute_dtype=jnp.float32, remat="none", scan_layers=True)
    ctx_u = StackCtx(cfg=cfg, compute_dtype=jnp.float32, remat="none", scan_layers=False)
    a, _ = model.forward(params, batch, ctx_s)
    b, _ = model.forward(params, batch, ctx_u)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_decode_fp8_cache_fidelity():
    """fp8 KV-cache storage (serving lever): greedy decode matches bf16-cache argmax."""
    cfg = get_reduced("stablelm-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1), max_seq=16)
    ctx = StackCtx(cfg=cfg, compute_dtype=jnp.float32, remat="none")
    b, s = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab_size)
    full_logits, _ = model.forward(params, {"tokens": toks}, ctx)
    caches = model.init_cache(params, b, s, dtype=jnp.float8_e4m3fn)
    outs = []
    for t in range(s):
        logits, caches = model.decode(params, {"token": toks[:, t:t + 1]}, caches,
                                      jnp.int32(t), ctx)
        outs.append(logits)
    dec = jnp.concatenate(outs, axis=1)
    agree = float(jnp.mean(
        (jnp.argmax(dec, -1) == jnp.argmax(full_logits, -1)).astype(jnp.float32)))
    assert agree >= 0.8, agree
