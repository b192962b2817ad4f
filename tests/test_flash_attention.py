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


# (window, causal, select): the five kinds of call
RAGGED_MODES = [
    pytest.param(0, False, False, id="global"),
    pytest.param(0, True, False, id="causal"),
    pytest.param(32, True, False, id="causal-window"),
    pytest.param(32, False, False, id="window"),
    pytest.param(0, True, True, id="causal-select"),
]
RAGGED_BLOCKS = [pytest.param(16, 16, id="bq=bk"),
                 pytest.param(32, 16, id="bq>bk"),
                 pytest.param(16, 32, id="bq<bk")]
RAGGED_SEQ = 96


def ragged_lengths(bq, bk):
    """A row of no token, of one, rows that end one under / exactly at /
    one over a block boundary of either size, and a full one: mixed in one
    batch (two heads a row)."""
    edge = max(bq, bk)
    return np.array([0, 1, edge - 1, edge, edge + 1, 2 * min(bq, bk) + 1,
                     RAGGED_SEQ], np.int32)


def pallas_calls(jaxpr):
    return [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]


class TestRowLengths:
    """``lengths``: the grid's work ends at each right-padded row's real
    length, and nothing a real position reads or writes changes."""

    @pytest.mark.parametrize("bq,bk", RAGGED_BLOCKS)
    @pytest.mark.parametrize("window,causal,select", RAGGED_MODES)
    def test_real_positions_bitwise_and_padding_blocks_zero(
            self, window, causal, select, bq, bk):
        lens = ragged_lengths(bq, bk)
        B, H, S = len(lens), 2, RAGGED_SEQ
        q, k, v = (rand(B, H, S, 16, seed=s) for s in (41, 42, 43))
        v = v[..., :8]  # v's head size apart from q/k's
        mask = jnp.asarray(np.arange(S)[None, :] < lens[:, None], jnp.int32)
        kw = dict(key_padding_mask=mask, window=window, causal=causal,
                  block_q=bq, block_k=bk, interpret=True)
        if select:
            rng = np.random.default_rng(44)
            kw["select"] = jnp.asarray(rng.random((B, S, S)) < 0.5,
                                       jnp.int8)
        plain = np.asarray(flash_attention_pallas(q, k, v, **kw))
        got = np.asarray(flash_attention_pallas(
            q, k, v, lengths=jnp.asarray(lens), **kw))
        assert np.isfinite(got).all()
        for b, n in enumerate(lens):
            np.testing.assert_array_equal(got[b, :, :n], plain[b, :, :n])
            past = -(-int(n) // bq) * bq  # the first query block past it
            assert not got[b, :, past:].any()
            assert past == S or plain[b, :, past:].any()

    @pytest.mark.parametrize("window,causal,select", RAGGED_MODES)
    def test_no_lengths_no_scalar_prefetch(self, window, causal, select):
        """A caller that hands no lengths gets the call it got before they
        existed: no scalar-prefetch operand, no operand more."""
        import jax

        q = rand(2, 2, 64, 16, seed=45)
        sel = jnp.ones((2, 64, 64), jnp.int8) if select else None

        def call(lengths):
            return jax.make_jaxpr(lambda q: flash_attention_pallas(
                q, q, q, window=window, causal=causal, select=sel,
                block_q=16, block_k=16, interpret=True,
                lengths=lengths))(q).jaxpr

        (plain,), (ragged,) = (pallas_calls(call(n)) for n in (
            None, jnp.asarray([3, 64], jnp.int32)))
        assert plain.params["grid_mapping"].num_index_operands == 0
        assert ragged.params["grid_mapping"].num_index_operands == 1
        assert len(ragged.invars) == len(plain.invars) + 1 == 5 + select

    def test_sharded_lengths_follow_the_rows(self):
        import jax

        from semantic_router_tpu.ops.flash_attention import (
            flash_attention_sharded,
        )
        from semantic_router_tpu.parallel import create_mesh

        mesh = create_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
        q, k, v = (rand(4, 4, 64, 16, seed=s) for s in (46, 47, 48))
        lens = jnp.asarray([5, 64, 17, 40], jnp.int32)
        mask = (jnp.arange(64)[None, :] < lens[:, None]).astype(jnp.int32)
        kw = dict(causal=True, block_q=16, block_k=16, interpret=True)
        got = jax.jit(lambda q, k, v, m, n: flash_attention_sharded(
            q, k, v, m, mesh, lengths=n, **kw))(q, k, v, mask, lens)
        ref = flash_attention_pallas(q, k, v, mask, lengths=lens, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        assert not np.asarray(got)[0, :, 16:].any()


def walked_tiles(seq, window, causal, lengths):
    """``tiles_for`` the slow way: every (row, query block, K step) of the
    grid, asked of ``_kv_start`` / ``_kv_stop`` one at a time; with the
    row's length a pair counts only where both blocks hold a real token."""
    import math

    from semantic_router_tpu.ops import flash_attention as fa

    bq, bk = fa.blocks_for(seq, window)
    padded = -(-seq // math.lcm(bq, bk)) * math.lcm(bq, bk)
    geom = dict(block_q=bq, block_k=bk, window=window, xp=np)
    n_kb = padded // bk
    steps = fa._kv_steps(n_kb, block_q=bq, block_k=bk, window=window,
                         causal=causal)

    def folds(n):
        count = 0
        for qi in range(padded // bq):
            stop = fa._kv_stop(qi, n_kb=n_kb, causal=causal, **geom)
            for j in range(steps):
                kb = fa._kv_start(qi, **geom) + j
                count += int(kb < stop and qi * bq < n and kb * bk < n)
        return count

    return sum(folds(int(n)) for n in lengths), len(lengths) * folds(padded)


class TestTilesFor:
    @pytest.mark.parametrize("seq", [512, 2048, 8192])
    @pytest.mark.parametrize("window,causal", [
        (0, False), (0, True), (1022, True), (128, False)])
    def test_against_a_walk_of_the_rule(self, seq, window, causal):
        from semantic_router_tpu.ops.flash_attention import (
            blocks_for,
            tiles_for,
        )

        bq, bk = blocks_for(seq, window)
        lengths = [0, 1, bq - 1, bq, bq + 1, bk + 1, seq // 2 + 3, seq - 1,
                   seq]
        lengths = [n for n in lengths if n <= seq]
        got = tiles_for(seq, window, causal, lengths)
        assert got == walked_tiles(seq, window, causal, lengths)
        assert got[0] < got[1] or seq <= bq
        assert tiles_for(seq, window, causal, [seq, seq]) == \
            (2 * got[1] // len(lengths),) * 2

    def test_the_bucket_of_8192_at_the_rules_blocks(self):
        """Causal and whole: 8 query blocks of 1024 fold 1 .. 8 K blocks,
        36 a head; a row of 5,000 tokens needs 5 of them: 15."""
        from semantic_router_tpu.ops.flash_attention import (
            causal_tiles,
            tiles_for,
        )

        assert tiles_for(8192, 0, True, [8192]) == (36, 36)
        assert tiles_for(8192, 0, True, [5000, 0]) == (15, 72)
        # under a window of 512 keys: 32 query blocks of 256, the first
        # two inside K block 0, the others over two K blocks of 512
        assert tiles_for(8192, 2 * 511, True, [8192]) == (62, 62)
        assert tiles_for(8192, 2 * 511, True, [1025]) == (2 + 3 * 2, 62)
        assert causal_tiles(8192, [5000], [(48, 0), (72, 1022), (72, 1022)]
                            ) == (48 * 15 + 2 * 72 * 38, 48 * 36 + 2 * 72 * 62)
