"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import flash_attention as fa
from repro.kernels import parity as par


def rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,H,Hkv,S,D", [
    (1, 1, 1, 128, 64),       # MHA
    (2, 4, 2, 128, 64),       # GQA 2:1
    (1, 8, 1, 256, 64),       # MQA
    (1, 4, 4, 64, 128),       # head_dim 128
    (2, 2, 2, 192, 32),       # non-pow2 seq (block 64)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(B, H, Hkv, S, D, dtype):
    q = rand(0, (B, H, S, D), dtype)
    k = rand(1, (B, Hkv, S, D), dtype)
    v = rand(2, (B, Hkv, S, D), dtype)
    out = fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    err = np.abs(out.astype(jnp.float32) - want.astype(jnp.float32)).max()
    assert err < TOL[dtype], (err, dtype)


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_attention_sliding_window(window):
    q = rand(0, (1, 2, 256, 64), jnp.float32)
    k = rand(1, (1, 2, 256, 64), jnp.float32)
    v = rand(2, (1, 2, 256, 64), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=True, window=window,
                             block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    assert np.abs(out - want).max() < 2e-5


def test_flash_attention_noncausal():
    q = rand(0, (1, 2, 128, 64), jnp.float32)
    k = rand(1, (1, 2, 128, 64), jnp.float32)
    v = rand(2, (1, 2, 128, 64), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=False, block_q=64,
                             block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    assert np.abs(out - want).max() < 2e-5


def test_flash_attention_block_shape_independence():
    """Output must not depend on the BlockSpec tiling."""
    q = rand(0, (1, 2, 256, 64), jnp.float32)
    k = rand(1, (1, 1, 256, 64), jnp.float32)
    v = rand(2, (1, 1, 256, 64), jnp.float32)
    outs = [fa.flash_attention(q, k, v, block_q=bq, block_k=bk,
                               interpret=True)
            for bq, bk in [(64, 64), (128, 128), (64, 128), (256, 64)]]
    for o in outs[1:]:
        assert np.abs(o - outs[0]).max() < 1e-5


@pytest.mark.parametrize("K,N,block", [
    (2, 1024, 256), (5, 4096, 4096), (9, 512, 128), (3, 8192, 1024),
])
def test_xor_parity_sweep(K, N, block):
    rng = np.random.default_rng(K * N)
    blocks = jnp.asarray(
        rng.integers(-2**31, 2**31, size=(K, N), dtype=np.int32))
    p = par.xor_parity(blocks, block=block, interpret=True)
    assert (np.asarray(p) == np.asarray(ref.xor_parity_ref(blocks))).all()
    # reconstruct each possible missing row
    for miss in range(K):
        surv = jnp.concatenate([blocks[:miss], blocks[miss + 1:]], 0)
        rec = par.reconstruct(surv, p, block=block, interpret=True)
        assert (np.asarray(rec) == np.asarray(blocks[miss])).all()


def test_parity_bytes_roundtrip_unequal_tails():
    rng = np.random.default_rng(7)
    chunks = [rng.bytes(1000), rng.bytes(737), rng.bytes(1024)]
    p = ops.parity_bytes(chunks)
    assert len(p) == 1024
    pad = [c.ljust(1024, b"\0") for c in chunks]
    back = ops.reconstruct_bytes(pad[1:], p, 1000)
    assert back == pad[0][:1000]


def test_xor_parity_linearity_property():
    """XOR(a) ^ XOR(b) == XOR(a ^ b) — the algebra the erasure code
    relies on."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-2**31, 2**31, (4, 512), dtype=np.int32))
    b = jnp.asarray(rng.integers(-2**31, 2**31, (4, 512), dtype=np.int32))
    pa = par.xor_parity(a, interpret=True)
    pb = par.xor_parity(b, interpret=True)
    pab = par.xor_parity(jnp.bitwise_xor(a, b), interpret=True)
    assert (np.asarray(jnp.bitwise_xor(pa, pb)) == np.asarray(pab)).all()


@pytest.mark.parametrize("K,N,block", [
    (3, 1000, 256),     # ragged tail: 1000 % 256 != 0
    (4, 37, 64),        # whole array smaller than one block
    (2, 513, 512),      # one lane past a block boundary
    (5, 4100, 1024),    # big block, small spill
])
def test_xor_parity_ragged_tail(K, N, block):
    """ISSUE-8: the kernel wrapper zero-pads lane counts that are not a
    multiple of the grid block instead of asserting, and the pad lanes
    never leak into the returned parity."""
    rng = np.random.default_rng(K + N + block)
    blocks = jnp.asarray(
        rng.integers(-2**31, 2**31, size=(K, N), dtype=np.int32))
    p = par.xor_parity(blocks, block=block, interpret=True)
    assert p.shape == (N,)
    assert (np.asarray(p) == np.asarray(ref.xor_parity_ref(blocks))).all()
    for miss in range(K):
        surv = jnp.concatenate([blocks[:miss], blocks[miss + 1:]], 0)
        rec = par.reconstruct(surv, p, block=block, interpret=True)
        assert (np.asarray(rec) == np.asarray(blocks[miss])).all()


def test_parity_bytes_odd_sizes_roundtrip():
    """Byte-level marshalling on sizes that are neither lane- nor
    block-aligned (the raid5 tail-unit case)."""
    rng = np.random.default_rng(11)
    for sizes in [(1, 1), (3, 7, 5), (255, 255, 255), (1023, 1, 509)]:
        chunks = [rng.bytes(s) for s in sizes]
        n = max(sizes)
        p = ops.parity_bytes(chunks)
        assert len(p) == n
        pad = [c.ljust(n, b"\0") for c in chunks]
        for miss in range(len(chunks)):
            surv = [pad[j] for j in range(len(chunks)) if j != miss]
            back = ops.reconstruct_bytes(surv, p, sizes[miss])
            assert back == chunks[miss], sizes


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError):
            ops._interpret()
    else:
        assert ops._interpret() is interpret
