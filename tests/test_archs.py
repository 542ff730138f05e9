"""Per-architecture smoke tests: reduced configs, one train/decode step on
CPU, asserting output shapes + no NaNs (full configs only via dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch.cells import LONG_OK, cells
from repro.models import layers as L
from repro.models import registry
from repro.models.config import RunConfig, SHAPES
from repro.train import steps as steps_mod
from repro.launch.mesh import make_host_mesh


RC = RunConfig(seq_len=32, global_batch=4, kind="train", attn_impl="ref",
               num_microbatches=1)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_loss(arch):
    cfg = get_smoke_config(arch)
    params = L.tree_init(registry.param_defs(cfg), jax.random.PRNGKey(0))
    batch = steps_mod.make_batch(cfg, RC, jax.random.PRNGKey(1))
    x, prefix_len, cache, _, aux = registry.forward(cfg, params, batch, RC)
    B, S = batch["tokens"].shape
    assert x.shape == (B, S + prefix_len, cfg.d_model)
    assert not np.isnan(np.asarray(x, np.float32)).any()
    loss = steps_mod.loss_fn(cfg, params, batch, RC)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    mesh = make_host_mesh()
    bundle = steps_mod.build_train_step(cfg, RC, mesh)
    params, opt = bundle.init(jax.random.PRNGKey(0))
    l0 = np.asarray(jax.tree.leaves(params)[0])   # before donation
    batch = steps_mod.make_batch(cfg, RC, jax.random.PRNGKey(1))
    p2, o2, m = bundle.fn(params, opt, batch)
    assert np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
    assert int(o2["step"]) == 1
    # params actually changed
    l1 = np.asarray(jax.tree.leaves(p2)[0])
    assert not np.allclose(l0, l1)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    rc = RunConfig(seq_len=64, global_batch=2, kind="decode",
                   attn_impl="ref", param_dtype="float32")
    params = L.tree_init(registry.param_defs(cfg), jax.random.PRNGKey(0))
    cdt = jnp.dtype(rc.compute_dtype)
    spec = registry.init_cache(cfg, 2, 64, cdt)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s[0], s[1]), spec,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    tok = jnp.ones((2, 1), jnp.int32)
    logits, cache2 = registry.decode(cfg, params, cache, tok,
                                     jnp.asarray(3, jnp.int32), rc)
    assert logits.shape == (2, 1, cfg.vocab)
    assert not np.isnan(np.asarray(logits, np.float32)).any()
    # cache got written somewhere
    changed = any(
        not np.array_equal(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache2)))
    assert changed


def test_decode_matches_forward_incrementally():
    """Prefill-forward logits at position t == decoding tokens one by one
    (transformer family)."""
    cfg = get_smoke_config("qwen3-4b")
    rc = RunConfig(seq_len=16, global_batch=2, kind="train",
                   attn_impl="ref", compute_dtype="float32",
                   param_dtype="float32", remat="none")
    params = L.tree_init(registry.param_defs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, cfg.vocab)
    x, _, _, _, _ = registry.forward(cfg, params, {"tokens": toks}, rc)
    full_logits = registry.unembed(cfg, params, x, rc)
    spec = registry.init_cache(cfg, 2, 16, jnp.float32)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s[0], s[1]), spec,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    errs = []
    for t in range(8):
        lg, cache = registry.decode(cfg, params, cache, toks[:, t:t + 1],
                                    jnp.asarray(t, jnp.int32), rc)
        errs.append(np.abs(np.asarray(lg[:, 0]) -
                           np.asarray(full_logits[:, t])).max())
    assert max(errs) < 1e-3, errs


def test_rwkv_state_decode_matches_scan():
    """RWKV: sequential scan == one-token decode chain (state carried)."""
    cfg = get_smoke_config("rwkv6-3b")
    rc = RunConfig(seq_len=8, global_batch=1, kind="train",
                   attn_impl="ref", compute_dtype="float32",
                   param_dtype="float32", remat="none")
    params = L.tree_init(registry.param_defs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab)
    x, _, _, _, _ = registry.forward(cfg, params, {"tokens": toks}, rc)
    full_logits = registry.unembed(cfg, params, x, rc)
    spec = registry.init_cache(cfg, 1, 8, jnp.float32)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s[0], s[1]), spec,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    for t in range(8):
        lg, cache = registry.decode(cfg, params, cache, toks[:, t:t + 1],
                                    jnp.asarray(t, jnp.int32), rc)
        err = np.abs(np.asarray(lg[:, 0]) -
                     np.asarray(full_logits[:, t])).max()
        assert err < 1e-3, (t, err)


def test_microbatch_accumulation_matches_full_batch():
    cfg = get_smoke_config("yi-9b")
    mesh = make_host_mesh()
    rc1 = RunConfig(seq_len=32, global_batch=8, kind="train",
                    attn_impl="ref", num_microbatches=1, remat="none")
    rc2 = RunConfig(seq_len=32, global_batch=8, kind="train",
                    attn_impl="ref", num_microbatches=2, remat="none")
    b1 = steps_mod.build_train_step(cfg, rc1, mesh)
    b2 = steps_mod.build_train_step(cfg, rc2, mesh)
    p1, o1 = b1.init(jax.random.PRNGKey(0))
    p2, o2 = b2.init(jax.random.PRNGKey(0))
    batch = steps_mod.make_batch(cfg, rc1, jax.random.PRNGKey(1))
    batch2 = {k: v.reshape(2, 4, *v.shape[1:]) for k, v in batch.items()}
    _, _, m1 = b1.fn(p1, o1, batch)
    _, _, m2 = b2.fn(p2, o2, batch2)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4


def test_chunked_ce_matches_dense():
    cfg = get_smoke_config("yi-9b")
    rc_a = RunConfig(seq_len=32, global_batch=2, kind="train",
                     attn_impl="ref", remat="none")
    rc_b = RunConfig(seq_len=32, global_batch=2, kind="train",
                     attn_impl="ref", remat="none", chunked_ce=8)
    params = L.tree_init(registry.param_defs(cfg), jax.random.PRNGKey(0))
    batch = steps_mod.make_batch(cfg, rc_a, jax.random.PRNGKey(1))
    la = float(steps_mod.loss_fn(cfg, params, batch, rc_a))
    lb = float(steps_mod.loss_fn(cfg, params, batch, rc_b))
    assert abs(la - lb) < 1e-4


def test_chunked_attention_matches_ref():
    cfg = get_smoke_config("qwen2-7b")
    params = L.tree_init(registry.param_defs(cfg), jax.random.PRNGKey(0))
    rc_ref = RunConfig(seq_len=64, global_batch=2, kind="train",
                       attn_impl="ref", compute_dtype="float32",
                       remat="none")
    rc_ch = RunConfig(seq_len=64, global_batch=2, kind="train",
                      attn_impl="chunked", attn_chunk=16,
                      compute_dtype="float32", remat="none")
    batch = steps_mod.make_batch(cfg, rc_ref, jax.random.PRNGKey(1))
    la = float(steps_mod.loss_fn(cfg, params, batch, rc_ref))
    lb = float(steps_mod.loss_fn(cfg, params, batch, rc_ch))
    assert abs(la - lb) < 1e-4


def test_cells_cover_40_assignments():
    all_cells = list(cells(include_skipped=True))
    assert len(all_cells) == 40
    runnable = list(cells())
    skipped = 40 - len(runnable)
    # long_500k runs only for the sub-quadratic families
    assert skipped == len(ARCHS) - len(LONG_OK)
    for arch in ARCHS:
        assert get_config(arch).name == arch


@pytest.mark.parametrize("windowed", [False, True])
def test_held_expert_yarn_decode_matches_forward(windowed):
    """A 3:1 sliding/full mix with YaRN on the full layers and a held
    share of the experts: decoding one token at a time, with the full
    cache or the ring-buffered local one, gives the forward's logits."""
    from repro.models.config import ModelConfig
    cfg = ModelConfig(
        name="held-yarn", family="transformer", n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, vocab=256, rope_theta=5e5,
        sliding_window=4, global_every=4, n_experts=16, top_k=4,
        d_ff_expert=32, experts_held=4, expert_offset=4, yarn_factor=16.0,
        yarn_original_max_pos=8, yarn_attention_factor=1.2772588722239782)
    rc = RunConfig(seq_len=16, global_batch=2, kind="train",
                   attn_impl="ref", compute_dtype="float32",
                   param_dtype="float32", remat="none")
    params = L.tree_init(registry.param_defs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, cfg.vocab)
    x, _, _, _, _ = registry.forward(cfg, params, {"tokens": toks}, rc)
    full_logits = registry.unembed(cfg, params, x, rc)
    spec = registry.init_cache(cfg, 2, 16, jnp.float32, windowed=windowed)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s[0], s[1]), spec,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    errs = []
    for t in range(8):
        lg, cache = registry.decode(cfg, params, cache, toks[:, t:t + 1],
                                    jnp.asarray(t, jnp.int32), rc)
        errs.append(np.abs(np.asarray(lg[:, 0]) -
                           np.asarray(full_logits[:, t])).max())
    assert max(errs) < 1e-3, errs
