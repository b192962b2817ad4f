"""Pallas flash-attention kernel numerics (interpret mode on CPU) vs the
dense SDPA oracle — global, sliding-window, causal, padded."""

import numpy as np
import pytest

import jax.numpy as jnp

from semantic_router_tpu.ops import padding_bias, sdpa, sliding_window_bias
from semantic_router_tpu.ops.attention import NEG_INF
from semantic_router_tpu.ops.flash_attention import flash_attention_pallas


def rand(*shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=jnp.float32)


def run(q, k, v, **kw):
    return flash_attention_pallas(q, k, v, block_q=16, block_k=16,
                                  interpret=True, **kw)


class TestFlashKernel:
    def test_global_matches_dense(self):
        q, k, v = (rand(2, 2, 64, 32, seed=s) for s in (1, 2, 3))
        out = run(q, k, v)
        ref = sdpa(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_sliding_window_matches_dense(self):
        q, k, v = (rand(1, 2, 64, 16, seed=s) for s in (4, 5, 6))
        out = run(q, k, v, window=16)
        ref = sdpa(q, k, v, bias=sliding_window_bias(64, 16))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_padding_mask(self):
        q, k, v = (rand(2, 1, 48, 16, seed=s) for s in (7, 8, 9))
        mask = jnp.asarray(np.concatenate(
            [np.ones((2, 30)), np.zeros((2, 18))], 1), jnp.float32)
        out = run(q, k, v, key_padding_mask=mask)
        ref = sdpa(q, k, v, bias=padding_bias(mask))
        np.testing.assert_allclose(np.asarray(out)[:, :, :30],
                                   np.asarray(ref)[:, :, :30],
                                   atol=1e-5, rtol=1e-5)

    def test_causal(self):
        q, k, v = (rand(1, 1, 32, 16, seed=s) for s in (10, 11, 12))
        out = run(q, k, v, causal=True)
        bias = jnp.triu(jnp.full((32, 32), NEG_INF, jnp.float32),
                        k=1)[None, None]
        ref = sdpa(q, k, v, bias=bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_non_divisible_seq_padding(self):
        q, k, v = (rand(1, 2, 50, 16, seed=s) for s in (13, 14, 15))
        out = run(q, k, v)
        ref = sdpa(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_window_plus_padding(self):
        q, k, v = (rand(2, 2, 64, 16, seed=s) for s in (16, 17, 18))
        mask = jnp.asarray(np.concatenate(
            [np.ones((2, 40)), np.zeros((2, 24))], 1), jnp.float32)
        out = run(q, k, v, window=16, key_padding_mask=mask)
        ref = sdpa(q, k, v, bias=padding_bias(mask)
                   + sliding_window_bias(64, 16))
        np.testing.assert_allclose(np.asarray(out)[:, :, :40],
                                   np.asarray(ref)[:, :, :40],
                                   atol=1e-5, rtol=1e-5)

    def test_bf16_inputs(self):
        q, k, v = (rand(1, 1, 32, 16, seed=s).astype(jnp.bfloat16)
                   for s in (19, 20, 21))
        out = run(q, k, v)
        ref = sdpa(q.astype(jnp.float32), k.astype(jnp.float32),
                   v.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32), np.asarray(ref),
            atol=2e-2, rtol=2e-2)


def dense_oracle(q, k, v, window=0, causal=False, mask=None):
    S = q.shape[2]
    bias = jnp.zeros((1, 1, S, S), jnp.float32)
    if causal:
        bias = bias + jnp.triu(jnp.full((S, S), NEG_INF, jnp.float32),
                               k=1)[None, None]
    if window > 0:
        bias = bias + sliding_window_bias(S, window)
    if mask is not None:
        bias = bias + padding_bias(mask)
    return sdpa(q, k, v, bias=bias)


# (seq, block_q, block_k, window, causal, real tokens or None)
GEOMETRIES = [
    pytest.param(96, 32, 16, 0, False, None, id="global-bq>bk"),
    pytest.param(96, 16, 48, 0, False, None, id="global-bq<bk"),
    pytest.param(128, 64, 16, 16, False, None, id="bq=4x-window"),
    pytest.param(128, 8, 16, 32, False, None, id="window-over-3-k-blocks"),
    pytest.param(128, 16, 64, 16, False, None, id="window-bk>bq"),
    pytest.param(100, 32, 16, 0, False, 70, id="padding-ends-in-block"),
    pytest.param(100, 32, 64, 24, False, 70, id="window-padding-bq<bk"),
    pytest.param(96, 32, 16, 0, True, None, id="causal-bq>bk"),
    pytest.param(96, 16, 32, 0, True, None, id="causal-bq<bk"),
    pytest.param(96, 32, 16, 16, True, None, id="causal-window-bq>bk"),
    # no blocks passed: the shape's own (blocks_for), one q block of 384
    pytest.param(300, None, None, 0, False, 260, id="rule-global"),
    pytest.param(300, None, None, 128, False, 260, id="rule-window"),
]


class TestBlockGeometries:
    """Blocks the 16x16 tests above never met: unequal, wider than the
    window, a band over several K blocks, padding that ends inside a
    block.  Same oracle, same atol."""

    @pytest.mark.parametrize("seq,bq,bk,window,causal,real", GEOMETRIES)
    def test_matches_dense(self, seq, bq, bk, window, causal, real):
        q, k, v = (rand(2, 2, seq, 16, seed=s) for s in (31, 32, 33))
        mask = None
        if real is not None:
            lens = np.array([real, seq])[:, None]
            mask = jnp.asarray(np.arange(seq)[None, :] < lens, jnp.float32)
        out = flash_attention_pallas(
            q, k, v, key_padding_mask=mask, window=window, causal=causal,
            block_q=bq, block_k=bk, interpret=True)
        ref = dense_oracle(q, k, v, window, causal, mask)
        keep = slice(None) if real is None else slice(0, real)
        np.testing.assert_allclose(np.asarray(out)[0, :, keep],
                                   np.asarray(ref)[0, :, keep],
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out)[1], np.asarray(ref)[1],
                                   atol=1e-5, rtol=1e-5)


class TestDispatcher:
    def test_traces_under_jit(self):
        """The served programs call the dispatcher under jit, where q is
        a tracer: reading its devices() raised on the first chip run."""
        import jax

        from semantic_router_tpu.ops.flash_attention import flash_attention

        q, k, v = (rand(1, 2, 64, 16, seed=s) for s in (22, 23, 24))
        out = jax.jit(lambda q, k, v: flash_attention(q, k, v, window=16))(
            q, k, v)
        ref = sdpa(q, k, v, bias=sliding_window_bias(64, 16))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_sharded_over_dp_tp_matches_unsharded(self):
        """engine.mesh serving: the kernel shard_mapped over (dp, tp) —
        rows over dp, heads over tp — equals the kernel on the whole
        batch (conftest gives 8 CPU devices; interpret mode)."""
        import jax

        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_sharded,
        )
        from semantic_router_tpu.parallel import create_mesh

        mesh = create_mesh({"dp": 2, "tp": 2},
                           devices=jax.devices()[:4])
        q, k, v = (rand(4, 4, 64, 16, seed=s) for s in (25, 26, 27))
        mask = jnp.asarray(np.concatenate(
            [np.ones((4, 50)), np.zeros((4, 14))], 1), jnp.int32)
        kw = dict(window=16, block_q=16, block_k=16, interpret=True)
        got = jax.jit(lambda q, k, v, m: flash_attention_sharded(
            q, k, v, m, mesh, **kw))(q, k, v, mask)
        ref = flash_attention_pallas(q, k, v, mask, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)
        # heads that tp does not divide stay whole on every tensor rank
        q3, k3, v3 = (rand(4, 3, 64, 16, seed=s) for s in (28, 29, 30))
        got = flash_attention_sharded(q3, k3, v3, mask, mesh, **kw)
        ref = flash_attention_pallas(q3, k3, v3, mask, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)
