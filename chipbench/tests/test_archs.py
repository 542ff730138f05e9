"""An architecture's file gives the program's model configuration for a
configuration file, as the harness has always built it for Qwen3."""
from chipbench import registry

BENCH = registry.load_benchmark()


def test_qwen3_model_config_fields():
    cfg = registry.config(BENCH, "qwen3-4b-l5")
    m = registry.arch(cfg).model_config(cfg)
    want = {"name": "qwen3-4b-l5", "family": "transformer", "n_layers": 5,
            "d_model": 2560, "n_heads": 32, "n_kv_heads": 8, "head_dim": 128,
            "d_ff": 9728, "vocab": 151936, "rope_theta": 1e6,
            "qk_norm": True, "qkv_bias": False, "act": "silu",
            "norm_eps": 1e-6, "tie_embeddings": True}
    assert {k: getattr(m, k) for k in want} == want
    assert isinstance(m.rope_theta, float)


def test_qwen3_norm_leaves_are_the_program_trees_gains():
    import jax
    from chipbench.drivers import train
    from repro.models import layers as L
    from repro.models import registry as mreg
    cfg = registry.config(BENCH, "qwen3-4b-l5")
    arch = registry.arch(cfg)
    defs = mreg.param_defs(arch.model_config(cfg))
    structs = L.tree_structs(defs, jax.numpy.float32)
    kinds = train.param_kinds(structs, arch.NORM_LEAVES)
    got = {jax.tree_util.keystr(p) for (p, _), k in zip(
        jax.tree.flatten_with_path(structs)[0], kinds) if k == "norm"}
    assert got == {"['final_ln']", "['layers']['attn']['ln']",
                   "['layers']['attn']['qn']", "['layers']['attn']['kn']",
                   "['layers']['mlp']['ln']"}
