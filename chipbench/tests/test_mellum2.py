"""Mellum2 at a small size on the CPU, seeded random weights: the trainer's
jitted step against the plain reference (loss, gradients, one AdamW
update); the held shares of the expert layer add up to the uncut layer;
a router that sends every token to one held expert drops none; the YaRN
tables against values worked out from the formula by hand; and the
program's expert choices against the reference's."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import registry
from chipbench.reference import adamw as ref_adamw
from chipbench.reference import mellum2 as ref
from repro.models import layers as L

BENCH = registry.load_benchmark()
FULL = registry.config(BENCH, "mellum2-12b-a2.5b-l4e8")
SMALL = dict(FULL, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
             num_experts=4, num_experts_published=16, num_experts_per_tok=4,
             expert_offset=4, vocab_size=256, sliding_window=8)
SEQ, BATCH = 32, 2


def model(cfg=SMALL):
    return registry.arch(cfg).model_config(cfg)


def run_config(compute="float32"):
    from repro.models.config import RunConfig
    return RunConfig(seq_len=SEQ, global_batch=BATCH, kind="train",
                     compute_dtype=compute, attn_impl="ref")


def weights(m, seed=0):
    from chipbench.drivers import train
    from repro.models import registry as mreg
    structs = L.tree_structs(mreg.param_defs(m), jnp.float32)
    gen = train.make_gen(structs, registry.arch(SMALL).NORM_LEAVES)
    return gen(jax.random.PRNGKey(seed))


def tokens(seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, SMALL["vocab_size"], (BATCH, SEQ),
                                    dtype=np.int32))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_trainer_step_matches_the_reference():
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw
    from repro.train import steps
    m = model()
    bundle = steps.build_train_step(m, run_config(),
                                    make_host_mesh(devices=jax.devices()[:1]))
    params = weights(m)
    toks = tokens()
    labels = ref.next_token_labels(toks)
    sp = ref.spec(SMALL)
    want_loss, want_grads, _ = ref.loss_and_grads(sp, params, toks)
    (loss, counts), grads = jax.value_and_grad(
        lambda p: steps.loss_fn(m, p, {"tokens": toks, "labels": labels},
                                run_config()), has_aux=True)(params)
    want_pairs = ref.selections(sp, params, toks)
    held = (want_pairs >= m.expert_offset) & \
        (want_pairs < m.expert_offset + m.experts_held)
    assert int(counts["held_pairs"]) == int(jnp.sum(held))
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    for path, g in jax.tree.flatten_with_path(grads)[0]:
        w = want_grads
        for key in path:
            w = w[key.key]
        assert rel(g, w) < 1e-4, jax.tree_util.keystr(path)

    opt = adamw.AdamWConfig()
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    new, _, _ = bundle.fn(jax.tree.map(jnp.copy, params),
                          {"step": jnp.zeros((), jnp.int32), "m": zeros(),
                           "v": zeros()},
                          {"tokens": toks, "labels": labels})
    gnorm = math.sqrt(sum(float(jnp.sum(g * g))
                          for g in jax.tree.leaves(want_grads)))
    scale = min(1.0, opt.grad_clip / (gnorm + 1e-12))
    lr = opt.lr * min(1.0, 1 / opt.warmup_steps)
    for p, g, n in zip(jax.tree.leaves(params), jax.tree.leaves(want_grads),
                       jax.tree.leaves(new)):
        z = jnp.zeros_like(p)
        want, _, _ = ref_adamw.adamw_leaf(
            jnp.array(p), g, z, jnp.zeros_like(p), lr=lr, scale=scale,
            bc1=1 - opt.b1, bc2=1 - opt.b2, b1=opt.b1, b2=opt.b2,
            eps=opt.eps, wd=opt.weight_decay)
        assert rel(n - p, want - p) < 1e-3


def test_held_shares_add_up_to_the_uncut_layer():
    from repro.models import moe
    m = model()
    E, H = m.n_experts, m.experts_held
    uncut_cfg = dict(SMALL, num_experts=E, expert_offset=0)
    uncut = weights(model(uncut_cfg))["layers"]["mlp"]
    uncut = jax.tree.map(lambda a: a[0], uncut)
    h = jax.random.normal(jax.random.PRNGKey(3), (BATCH, SEQ, 64))
    want, want_aux = ref.sparse_mlp(ref.spec(uncut_cfg), h, uncut)
    total, pairs = 0.0, 0
    for off in range(0, E, H):
        share = {k: (v[off:off + H] if k in ("wg", "wu", "wd") else v)
                 for k, v in uncut.items()}
        y, aux = moe.held_expert_ffn(
            dataclasses.replace(m, expert_offset=off), share,
            h.reshape(-1, 64), run_config())
        total = total + y
        pairs += int(aux["held_pairs"])
        assert float(aux["balance"]) == pytest.approx(float(want_aux),
                                                      rel=1e-5)
    assert rel(total.reshape(want.shape), want) < 1e-5
    # every (token, choice) pair computed by exactly one share
    assert pairs == BATCH * SEQ * m.top_k


def test_a_router_sending_every_token_to_one_held_expert_drops_none():
    from repro.models import moe
    m = model()
    p = jax.tree.map(lambda a: a[0], weights(m)["layers"]["mlp"])
    held = m.expert_offset + 1
    # every token's first choice is held expert `held`, by a wide margin:
    # 64 pairs for one expert, where a capacity of 1.25 times the mean
    # load (64 x 4 / 16) would keep 20
    h = jax.random.normal(jax.random.PRNGKey(4), (BATCH * SEQ, 64))
    h = h.at[:, 0].set(50.0)
    p["router"] = p["router"].at[:, held].set(0.0).at[0, held].set(1.0)
    want, _ = ref.sparse_mlp(ref.spec(SMALL), h[None], p)
    y, aux = moe.held_expert_ffn(m, p, h, run_config())
    top = jax.lax.top_k(h @ p["router"], m.top_k)[1]
    assert bool(jnp.all(top[:, 0] == held))
    assert int(aux["held_pairs"]) >= BATCH * SEQ
    assert rel(y, want[0]) < 1e-5
    assert float(jnp.min(jnp.linalg.norm(y, axis=-1))) > 0


@pytest.mark.parametrize("yarn", [L.yarn_inv_freq, ref.yarn_inv_freq],
                         ids=["program", "reference"])
def test_yarn_tables_by_hand(yarn):
    full = FULL["rope_parameters"]["full_attention"]
    theta, factor = full["rope_theta"], full["factor"]
    inv = yarn(128, theta, factor, full["original_max_position_embeddings"],
               full["beta_fast"], full["beta_slow"])
    # the ramp runs over pair indices 18 to 35:
    # floor(128 ln(8192 / (32 2 pi)) / (2 ln 5e5)) = floor(18.08) = 18,
    # ceil(128 ln(8192 / (1 2 pi)) / (2 ln 5e5)) = ceil(34.98) = 35
    def base(j):
        return theta ** (-2 * j / 128)
    # below the ramp: the frequency kept
    assert inv[10] == pytest.approx(base(10), rel=1e-6)
    # inside: 8/17 of the way to the interpolated frequency
    r = (26 - 18) / (35 - 18)
    assert inv[26] == pytest.approx(base(26) / factor * r
                                    + base(26) * (1 - r), rel=1e-6)
    # above: divided by the factor
    assert inv[50] == pytest.approx(base(50) / factor, rel=1e-6)
    assert inv[17] == pytest.approx(base(17), rel=1e-6)
    assert inv[35] == pytest.approx(base(35) / factor, rel=1e-6)


def test_rope_tables_by_layer_type():
    from repro.models import transformer
    m = model(FULL)
    inv, scale = transformer.rope_tables(m)
    assert list(scale) == pytest.approx([1, 1, 1, 1.2772588722239782])
    default = ref.default_inv_freq(128, 5e5)
    assert inv[0] == pytest.approx(default, rel=1e-6)
    assert inv[3][50] == pytest.approx(default[50] / 16, rel=1e-6)
    assert list(transformer.windows(m)) == [1024, 1024, 1024, 0]


def program_selections(m, params, toks, rc):
    """The program's expert choices at each layer, (layers, B, S, k) in
    ascending order, its own layers run one at a time."""
    from repro.models import moe
    from repro.models import transformer as T
    x = T._embed(m, params, toks, rc)
    inv, scale = T.rope_tables(m)
    win = T.windows(m)
    out = []
    for i in range(m.n_layers):
        pl = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        x, _ = T.attn_block(m, pl["attn"], x, int(win[i]), 0, rc,
                            rope=(jnp.asarray(inv[i]),
                                  jnp.asarray(scale[i])))
        h = L.rms_norm(x, pl["mlp"]["ln"], m.norm_eps)
        _, top_e, _ = moe.route(m, h.reshape(-1, m.d_model),
                                pl["mlp"]["router"])
        out.append(jnp.sort(top_e.reshape(*x.shape[:2], -1), axis=-1))
        x, _ = T.mlp_block(m, pl["mlp"], x, rc)
    return jnp.stack(out)


def test_program_routes_as_the_reference_in_float32():
    m = model()
    params, toks = weights(m), tokens()
    got = program_selections(m, params, toks, run_config())
    want = ref.selections(ref.spec(SMALL), params, toks)
    assert got.shape == want.shape == (4, BATCH, SEQ, m.top_k)
    assert int(jnp.sum(got != want)) == 0


def test_traced_step_counts_the_pairs_it_routed(tmp_path):
    """While the profiler records, each step adds the (token, expert)
    pairs its held experts computed, over its layers, to a `train.counts`
    span; untraced, it records nothing."""
    from repro.core import metrics
    from repro.launch.mesh import make_host_mesh
    from repro.train import steps
    m = model()
    bundle = steps.build_train_step(m, run_config(),
                                    make_host_mesh(devices=jax.devices()[:1]))
    params, toks = weights(m), tokens()
    held = ref.selections(ref.spec(SMALL), params, toks)
    want = int(jnp.sum((held >= m.expert_offset)
                       & (held < m.expert_offset + m.experts_held)))
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    args = lambda: (jax.tree.map(jnp.copy, params),  # noqa: E731
                    {"step": jnp.zeros((), jnp.int32), "m": zeros(),
                     "v": zeros()},
                    {"tokens": toks, "labels": ref.next_token_labels(toks)})
    n0 = len(metrics.read_spans()[0])
    _, _, out = bundle.fn(*args())
    assert int(out["held_pairs"]) == want
    assert len(metrics.read_spans()[0]) == n0
    jax.profiler.start_trace(str(tmp_path))
    try:
        bundle.fn(*args())
    finally:
        jax.profiler.stop_trace()
    evs = [e for e in metrics.read_spans()[0][n0:] if e[0] == "train.counts"]
    assert [e[4] for e in evs] == [{"held_pairs": want}]


def test_gmm_roofline_reads_the_routed_pairs(monkeypatch):
    """FLOPs from the pairs the program counted, over the gmm ops' device
    time; nothing where the program counted none."""
    from chipbench import harness, progspans, trace
    ms = 1_000_000
    tr = trace.Trace(device={"/device:TPU:0": [
        ("jvp_jit_gmm__", 10 * ms, 14 * ms),
        ("transpose_jvp_jit_tgmm___", 20 * ms, 26 * ms),
        ("fusion", 30 * ms, 40 * ms)]},
        spans=[("window", 0, 100 * ms)])
    part = harness.Part(1.0, 1.1, [(1.0, 1.1, {"tokens": 8192})], trace=tr)
    run = harness.Run("mellum2_moe_train", FULL, {}, {"bf16_flops": 197e12},
                      1.0, {"trace": part})
    pairs = 33_000
    monkeypatch.setattr(progspans, "_recorded", lambda: (
        [("train.counts", 1_050 * ms, 1_050 * ms, -1,
          {"held_pairs": pairs})], 0))
    flops = 24.0 * pairs * FULL["hidden_size"] * \
        FULL["moe_intermediate_size"]
    got = registry.reader("gmm_roofline")(run)
    assert got == pytest.approx(100 * flops / 0.010 / 197e12)
    assert registry.reader("moe_gmm_ms_per_step")(run) == pytest.approx(10.0)
    monkeypatch.setattr(progspans, "_recorded", lambda: (
        [("train.batch", 1_050 * ms, 1_051 * ms, -1, {})], 0))
    assert registry.reader("gmm_roofline")(run) is None
