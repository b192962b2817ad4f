"""``model_type: olmo_hybrid`` (``models/olmo_hybrid.py`` over
``ops/gated_delta_rule.py``) on the CPU at toy widths that keep what the
published ones force: ``d_k != d_v`` and neither a multiple of 128, a period
of three linear layers to one full layer, float32.

The chunked op against the token recurrence; the decoder — prefill, then
decoding through the cache of K/V, matrix states and conv windows — against
``chipbench/reference/olmo_hybrid.py``'s ONE full forward; each listed fault
fails a comparison; ``from_hf``'s refusals; the generator, the device loop
and the engine's marker.

Tolerances.  Op against recurrence, float32 on both sides: 2e-5 absolute on
outputs and states of order 1 (the chunked form sums a chunk's 64 products
in another order and solves a triangular system: 1e-6 measured, 20 times of
room).  Decoder against reference: 2e-4 on logits of deviation 3 (4 layers
of float32 in another order: 3e-5 measured).
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import per_step_loop
from chipbench import cells
from semantic_router_tpu.models import mapped_prefill
from semantic_router_tpu.models import olmo_hybrid as M
from semantic_router_tpu.models.generate import GreedyGenerator
from semantic_router_tpu.ops import gated_delta_rule as G
from semantic_router_tpu.utils.tokenization import Encoding

MODEL = {
    "model_type": "olmo_hybrid", "vocab_size": 256, "hidden_size": 60,
    "intermediate_size": 96, "num_hidden_layers": 4,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 3, "num_key_value_heads": 3, "hidden_act": "silu",
    "max_position_embeddings": 512, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 12, "linear_value_head_dim": 24,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "torch_dtype": "float32"}
CONFIG = {
    "family": "linear_attn_ar_guard", "model": MODEL,
    "weights": {"std": 0.13, "embed_std": 1.0, "head_std": 0.39,
                "conv_std": 0.5, "qk_norm": 1.41, "a_dev": 0.5, "b_dev": 1.2,
                "decay_min": 1e-4, "decay_max": 0.105, "writer_threads": 2},
    "tasks": {"jailbreak": {"kind": "generative"}}}
ATOL = 2e-4
OP_ATOL = 2e-5

family = cells.load_family(CONFIG)
ref = cells.load_module("reference", "olmo_hybrid")


class WordTokenizer:
    """``w<id>`` is token ``id``, any other piece is token 1."""

    def encode(self, text, max_length=0):
        ids = [int(w[1:]) if w[0] == "w" and w[1:].isdigit() else 1
               for w in family.base.PIECES.findall(text)]
        return Encoding(ids=ids, attention_mask=[1] * len(ids),
                        offsets=[(0, 0)] * len(ids))

    def decode(self, ids):
        return " ".join(f"w{int(i)}" for i in ids)


def words(ids) -> str:
    return " ".join(f"w{int(i)}" for i in ids)


def variant(**changes):
    """(model numbers, state, config, params) of the toy with ``changes``."""
    model = dict(MODEL, **changes)
    state = family.generate_state(dict(CONFIG, model=model), 7)
    cfg = M.OlmoHybridConfig.from_hf(model)
    return model, state, cfg, M.params_from_state(state.__getitem__, cfg)


@pytest.fixture(scope="module")
def toy():
    return variant()


def prompts(seed: int, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 250, n) for n in lengths]


def padded(rows, bucket: int, pad: int = 0):
    ids = np.full((len(rows), bucket), pad, np.int32)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
    return jnp.asarray(ids), jnp.asarray([len(r) for r in rows], jnp.int32)


# -- the op against the token recurrence ------------------------------------------


def recurrence(q, k, v, g, beta, s0=None):
    """The token-by-token rule of the issue's section 1, in float64."""
    q, k, v, g, beta = (np.asarray(a, np.float64)
                        for a in (q, k, v, g, beta))
    B, H, S, dk = q.shape
    s = np.zeros((B, H, dk, v.shape[-1])) if s0 is None \
        else np.array(s0, np.float64)
    o = np.zeros(v.shape)
    for t in range(S):
        s = s * np.exp(g[:, :, t])[..., None, None]
        u = beta[:, :, t, None] * (
            v[:, :, t] - np.einsum("bhkv,bhk->bhv", s, k[:, :, t]))
        s = s + k[:, :, t, :, None] * u[:, :, None, :]
        o[:, :, t] = np.einsum("bhkv,bhk->bhv", s, q[:, :, t])
    return o, s


def op_inputs(seed: int, B=2, H=3, S=150, dk=12, dv=20, decay=(-9.0, 1.5)):
    """Unit keys, ``beta`` up to 1.95, log decays from ``-exp(-9)`` (a
    token keeps all) to ``-exp(1.5)`` (a token keeps 1%), and a stretch of
    REPEATED keys, where the triangular system is at its worst."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, H, S, dk))
    k = rng.standard_normal((B, H, S, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    k[:, :, 40:70] = k[:, :, 40:41]
    v = rng.standard_normal((B, H, S, dv))
    beta = rng.uniform(0.1, 1.95, (B, H, S))
    g = -np.exp(rng.uniform(*decay, (B, H, S)))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("kernel", ["jnp", "interpret"])
@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
def test_the_chunked_op_equals_the_recurrence(chunk, kernel):
    """S = 150 is a multiple of no chunk size; beta above 1, decays near 1
    and near 0, repeated keys."""
    x = op_inputs(0)
    want_o, want_s = recurrence(*x)
    o, s = G.chunk_gated_delta_rule(*x, chunk=chunk, kernel=kernel)
    np.testing.assert_allclose(o, want_o, atol=OP_ATOL)
    np.testing.assert_allclose(s, want_s, atol=OP_ATOL)
    assert s.dtype == jnp.float32 and o.shape == x[2].shape


@pytest.mark.parametrize("kernel", ["jnp", "interpret"])
def test_bfloat16_inputs_go_through_in_exact_passes(kernel):
    """q, k, v as served: their own products in one pass of the matrix
    unit, a float32 matrix against them in three.  Against the recurrence
    over the SAME rounded inputs the state is float32's (1e-5 of order 1),
    the outputs within their own rounding to bfloat16."""
    x = op_inputs(5)
    q, k, v = (a.astype(jnp.bfloat16) for a in x[:3])
    want_o, want_s = recurrence(*(a.astype(jnp.float32) for a in (q, k, v)),
                                *x[3:])
    o, s = G.chunk_gated_delta_rule(q, k, v, *x[3:], chunk=32, kernel=kernel)
    assert o.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    np.testing.assert_allclose(s, want_s, atol=OP_ATOL)
    np.testing.assert_allclose(o.astype(jnp.float32), want_o,
                               atol=2 ** -8 * np.abs(want_o).max())


@pytest.mark.parametrize("decay", [(-12.0, -8.0), (1.0, 2.5)])
def test_decays_near_one_and_near_zero(decay):
    x = op_inputs(1, decay=decay)
    want_o, want_s = recurrence(*x)
    o, s = G.chunk_gated_delta_rule(*x, chunk=32)
    np.testing.assert_allclose(o, want_o, atol=OP_ATOL)
    np.testing.assert_allclose(s, want_s, atol=OP_ATOL)
    assert np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("kernel", ["jnp", "interpret"])
def test_the_final_state_is_the_state_at_the_true_length(kernel):
    """Rows of 150 positions with 97 and 33 real tokens: the final state is
    the recurrence's over the real tokens alone, the outputs at the real
    positions are its outputs, and neither moves by a bit when the padding
    holds other (even enormous) values."""
    x = op_inputs(2)
    lengths = jnp.asarray([97, 33], jnp.int32)
    o, s = G.chunk_gated_delta_rule(*x, lengths=lengths, chunk=32,
                                    kernel=kernel)
    for row, n in enumerate((97, 33)):
        want_o, want_s = recurrence(*(a[row:row + 1, :, :n] for a in x))
        np.testing.assert_allclose(s[row], want_s[0], atol=OP_ATOL)
        np.testing.assert_allclose(o[row, :, :n], want_o[0], atol=OP_ATOL)
    real = (np.arange(150)[None, :] < np.asarray(lengths)[:, None])
    other = tuple(jnp.where(real[:, None, :, None] if a.ndim == 4
                            else real[:, None, :], a, 1e4 * (1 + a))
                  for a in x)
    o2, s2 = G.chunk_gated_delta_rule(*other, lengths=lengths, chunk=32,
                                      kernel=kernel)
    assert np.array_equal(np.asarray(s), np.asarray(s2))
    assert np.array_equal(np.asarray(o)[0, :, :97], np.asarray(o2)[0, :, :97])
    assert np.array_equal(np.asarray(o)[1, :, :33], np.asarray(o2)[1, :, :33])


def test_a_sequence_in_two_pieces_is_the_sequence_in_one():
    x = op_inputs(3)
    o, s = G.chunk_gated_delta_rule(*x, chunk=16)
    cut = 70  # inside a chunk of the whole
    o1, s1 = G.chunk_gated_delta_rule(*(a[:, :, :cut] for a in x), chunk=16)
    o2, s2 = G.chunk_gated_delta_rule(*(a[:, :, cut:] for a in x), chunk=16,
                                      initial_state=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 2), o, atol=OP_ATOL)
    np.testing.assert_allclose(s2, s, atol=OP_ATOL)


def test_the_step_n_times_is_the_op():
    x = op_inputs(4, S=40)
    o, s = G.chunk_gated_delta_rule(*x, chunk=16)
    state = jnp.zeros(s.shape, jnp.float32)
    for t in range(40):
        o_t, state = G.gated_delta_step(state, *(a[:, :, t] for a in x))
        np.testing.assert_allclose(o_t, o[:, :, t], atol=OP_ATOL)
    np.testing.assert_allclose(state, s, atol=OP_ATOL)


def test_the_inverse_is_a_forward_substitution_not_a_power_series():
    """Equal keys and beta near 2 over a whole chunk: ``(I + A)^-1`` stays
    the inverse (the product form ``(I - A)(I + A^2)...`` of the same matrix
    has terms of 1e27 there)."""
    C = 64
    a = jnp.tril(jnp.full((C, C), 1.95, jnp.float32), -1)[None]
    x = G._inv_unit_lower(a)
    np.testing.assert_allclose(x[0] @ (jnp.eye(C) + a[0]), np.eye(C),
                               atol=1e-3)


# -- the decoder against the reference's one full forward ---------------------------


_PREFILL = jax.jit(M.prefill, static_argnums=(0, 4))
_DECODE = jax.jit(M.decode, static_argnums=0)


def _decode_greedily(cfg, params, rows, bucket, steps, pad=0, extra_rows=0,
                     prefill=_PREFILL, decode=_DECODE):
    """Prefill ``rows`` (plus ``extra_rows`` padding rows) and decode
    ``steps`` tokens greedily: ``(tokens [rows, steps + 1], logits [rows,
    steps + 1, V], cache)``."""
    ids, lengths = padded(list(rows) + [[]] * extra_rows, bucket, pad)
    M_len = bucket + steps + 1
    cache, logits, aux = prefill(cfg, params, ids, lengths, M_len)
    assert aux["load"].shape == (0, 4)  # no expert layer
    toks, all_logits = [np.asarray(logits).argmax(-1)], [np.asarray(logits)]
    pos = np.asarray(lengths)
    for _ in range(steps):
        cache, lg, _ = decode(cfg, params, cache,
                              jnp.asarray(toks[-1], jnp.int32),
                              jnp.asarray(pos, jnp.int32))
        pos = pos + 1
        toks.append(np.asarray(lg).argmax(-1))
        all_logits.append(np.asarray(lg))
    n = len(rows)
    return (np.stack(toks, 1)[:n], np.stack(all_logits, 1)[:n], cache)


def _reference_logits(model, state, row, toks):
    seq = np.concatenate([row, toks[:-1]])
    at = [len(row) - 1 + j for j in range(len(toks))]
    return ref.forward(model, state, seq, at)["logits"]


def test_prefill_then_decode_equal_the_full_forward(toy):
    """Rows of different lengths in one batch (one crosses two chunk
    boundaries, one ends inside the first chunk), padded to 160 with a
    padding row beside them: every position that chose a token reads the
    reference's logits."""
    model, state, cfg, params = toy
    rows = prompts(5, (150, 41))
    toks, logits, cache = _decode_greedily(cfg, params, rows, 160, 5,
                                           extra_rows=1)
    for row, t, z in zip(rows, toks, logits):
        np.testing.assert_allclose(
            z, _reference_logits(model, state, row, t), atol=ATOL)
    assert cache["state"][0].dtype == jnp.float32
    assert cache["state"][0].shape == (3, 3, 12, 24)
    assert cache["conv"][0].shape == (3, 3, 3 * (12 + 12 + 24))
    assert cache["full"][0][0].shape == (3, 3, 166, 20)
    assert len(cache["state"]) == len(cache["conv"]) == 3
    assert len(cache["full"]) == 1
    # a padding row leaves the prefill with zeros for a state
    ids, lengths = padded(list(rows) + [[]], 160)
    fresh, _, _ = _PREFILL(cfg, params, ids, lengths, 166)
    assert not np.asarray(fresh["state"][0])[2].any()
    assert not np.asarray(fresh["conv"][0])[2].any()


def test_padding_is_never_seen(toy):
    _, _, cfg, params = toy
    rows = prompts(6, (70, 23))
    a = _decode_greedily(cfg, params, rows, 96, 3, pad=0)
    b = _decode_greedily(cfg, params, rows, 96, 3, pad=77)
    assert np.array_equal(a[0], b[0])
    assert np.array_equal(a[1], b[1])


def test_rows_in_groups_equal_rows_together(toy):
    _, _, cfg, params = toy
    rows = prompts(7, (90, 33, 64, 5))
    together = _decode_greedily(cfg, params, rows, 96, 3)
    for group in (1, 2):
        grouped = jax.jit(
            lambda cfg, params, ids, lengths, cache_len, group=group:
            M._prefill_groups(cfg, params, ids, lengths, cache_len, group),
            static_argnums=(0, 4))
        mapped = _decode_greedily(cfg, params, rows, 96, 3, prefill=grouped)
        assert np.array_equal(mapped[0], together[0])
        # (the scan's first stage sums over another batch's shape)
        np.testing.assert_allclose(mapped[1], together[1], atol=5e-5)
        for a, b in zip(jax.tree.leaves(mapped[2]),
                        jax.tree.leaves(together[2])):
            np.testing.assert_allclose(a, b, atol=5e-5)


def test_the_rows_a_group_follow_from_shapes_and_memory(monkeypatch):
    """At the cell's widths on a v5e's memory a group is one row at every
    row count (a row's normed activations are 63 MB: two rows' are past the
    core's own memory); on the CPU every row."""
    with open(os.path.join(cells.HERE, "configs", "olmo-hybrid-7b-guard",
                           "model.json")) as f:
        cfg = M.OlmoHybridConfig.from_hf(json.load(f))
    params = {"w": jax.ShapeDtypeStruct((3_268_270_000,), jnp.bfloat16)}
    model = M.CachedModel(cfg)
    assert model.rows_per_group(params, 8, 8192, 8256) == 8
    monkeypatch.setattr(mapped_prefill, "device_bytes",
                        lambda: 16_909_336_064)
    for rows in (1, 2, 4, 8):
        assert model.rows_per_group(params, rows, 8192, 8256) == 1
    assert M._cache_bytes(cfg, 8, 8224) == 8 * (
        3 * 2 * 30 * 8224 * 128 * 2
        + 9 * (30 * 96 * 192 * 4 + 3 * 11520 * 2))
    assert 1.5e9 < M._row_bytes(cfg, 8192) < 1.7e9


# -- each of these faults fails the comparison --------------------------------------


@pytest.fixture(scope="module")
def small():
    """One linear layer and one full layer: what the faults are put in."""
    return variant(num_hidden_layers=2,
                   layer_types=["linear_attention", "full_attention"])


def _faulty(monkeypatch, fault, toy):
    """The program with ``fault`` in it: ``(cfg, params, prefill, decode)``
    to decode with (jitted under the fault: the shared programs were
    traced without it)."""
    _, _, cfg, params = toy
    if fault == "beta_not_doubled":
        cfg = dataclasses.replace(cfg, linear_allow_neg_eigval=False)
    elif fault == "rope_though_theta_is_null":
        cfg = dataclasses.replace(cfg, rope_theta=10000.0)
    elif fault == "decay_dropped":
        real = M._in_proj
        monkeypatch.setattr(M, "_in_proj", lambda cfg, p, h: (
            lambda z, gate, g, beta: (z, gate, jnp.zeros_like(g), beta))(
                *real(cfg, p, h)))
    elif fault == "taps_reversed":
        real = M._taps
        monkeypatch.setattr(M, "_taps", lambda p, window: real(
            dict(p, conv_w=p["conv_w"][::-1]), window))
    elif fault == "l2_norm_dropped":
        def heads(cfg, y):
            n, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
            q, k, v = jnp.split(y, (n * dk, 2 * n * dk), -1)
            return tuple(t.reshape(t.shape[:-1] + (n, -1)).astype(cfg.dtype)
                         for t in (q * dk ** -0.5, k, v))
        monkeypatch.setattr(M, "_heads", heads)
    elif fault == "state_at_the_buckets_end":
        real = M.chunk_gated_delta_rule
        monkeypatch.setattr(
            M, "chunk_gated_delta_rule",
            lambda *a, lengths=None: real(*a, lengths=None))
    elif fault == "gates_silu_dropped":
        def gate_norm(cfg, p, o, gate):
            o = M.rms_norm(o, p["o_norm"], cfg.rms_norm_eps, jnp.float32)
            return (o.reshape(gate.shape)
                    * gate.astype(jnp.float32)).astype(cfg.dtype)
        monkeypatch.setattr(M, "_gate_norm", gate_norm)
    else:
        raise AssertionError(fault)
    return (cfg, params, jax.jit(M.prefill, static_argnums=(0, 4)),
            jax.jit(M.decode, static_argnums=0))


FAULTS = ("beta_not_doubled", "decay_dropped", "taps_reversed",
          "l2_norm_dropped", "state_at_the_buckets_end",
          "gates_silu_dropped", "rope_though_theta_is_null")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_moves_the_logits_off_the_reference(monkeypatch, small,
                                                    fault):
    """The seeded weights exercise the mechanism: with the fault the logits
    at the positions that chose a token lie a hundred tolerances or more
    from the reference's, or are not numbers at all (without the L2 norm
    the state diverges); the sound program: within one."""
    model, state, _, _ = small
    cfg, params, prefill, decode = _faulty(monkeypatch, fault, small)
    rows = prompts(5, (150, 41))
    toks, logits, _ = _decode_greedily(cfg, params, rows, 160, 3,
                                       prefill=prefill, decode=decode)
    worst = max(float(np.abs(
        z - _reference_logits(model, state, row, t)).max())
        for row, t, z in zip(rows, toks, logits))
    assert not worst <= 100 * ATOL, (fault, worst)


def test_the_state_at_the_buckets_end_is_wrong_only_after_the_prefill(
        monkeypatch, small):
    """The scan without the rows' lengths: the prefill's own logits are the
    reference's still (they are read at the true last token), the first
    decoded token's are not — what decoding carries is the state."""
    model, state, _, _ = small
    cfg, params, prefill, decode = _faulty(
        monkeypatch, "state_at_the_buckets_end", small)
    row = prompts(5, (41,))[0]
    toks, logits, _ = _decode_greedily(cfg, params, [row], 160, 2,
                                       prefill=prefill, decode=decode)
    want = _reference_logits(model, state, row, toks[0])
    np.testing.assert_allclose(logits[0, 0], want[0], atol=ATOL)
    assert np.abs(logits[0, 1] - want[1]).max() > 100 * ATOL


# -- from_hf ------------------------------------------------------------------------


def test_every_model_number_comes_from_the_checkpoints_config():
    cfg = M.OlmoHybridConfig.from_hf(dict(
        MODEL, rms_norm_eps=3e-4, linear_conv_kernel_dim=3,
        linear_allow_neg_eigval=False, torch_dtype="bfloat16",
        rope_parameters={"rope_theta": 5e5, "rope_type": "default"}))
    assert (cfg.rms_norm_eps, cfg.linear_conv_kernel_dim,
            cfg.linear_allow_neg_eigval, cfg.rope_theta, cfg.head_dim,
            cfg.conv_width) == (3e-4, 3, False, 5e5, 20, 3 * 48)
    assert cfg.dtype == jnp.bfloat16
    assert cfg.layer_types == tuple(MODEL["layer_types"])
    assert M.OlmoHybridConfig.from_hf(MODEL).rope_theta is None


@pytest.mark.parametrize("changes, says", [
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"hidden_act": "gelu"}, "hidden_act 'gelu'"),
    ({"layer_types": ["linear_attention"] * 3}, "layer_types"),
    ({"layer_types": ["linear_attention", "conv"] + ["full_attention"] * 2},
     "layer_types"),
    ({"linear_num_value_heads": 6}, "linear_num_key_heads"),
    ({"num_key_value_heads": 1}, "num_key_value_heads"),
    ({"rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6}},
     "rope_type")])
def test_what_the_architecture_cannot_express_is_refused(changes, says):
    with pytest.raises(ValueError, match=says):
        M.OlmoHybridConfig.from_hf(dict(MODEL, **changes))


def test_the_type_is_a_row_of_the_served_generators():
    from semantic_router_tpu.runtime.bootstrap import (
        GENERATIVE_MODEL_TYPES,
        build_generator,
    )

    assert "olmo_hybrid" in GENERATIVE_MODEL_TYPES
    assert "``olmo_hybrid``" in build_generator.__doc__


# -- the one token-at-a-time loop ---------------------------------------------------


def generator(toy, **kw) -> GreedyGenerator:
    _, _, cfg, params = toy
    return GreedyGenerator(cfg, params, WordTokenizer(),
                           model=M.CachedModel(cfg), gen_length=6,
                           top_logits=4, **kw)


def test_the_loop_serves_the_decoder_with_its_trajectory(toy):
    model, state, _, _ = toy
    rows = prompts(9, (70, 12))
    out = generator(toy).generate([words(r) for r in rows], max_new_tokens=6)
    for row, res in zip(rows, out):
        n = len(row)
        assert res.prompt_tokens == n and len(res.token_ids) == 6
        traj = res.trajectory
        assert [e["kind"] for e in traj] == ["prefill"] + ["decode"] * 5
        assert [e["position"] for e in traj] == list(range(n - 1, n + 5))
        assert [e["token"] for e in traj] == res.token_ids
        assert "experts" not in traj[0]  # a dense model routes nothing
        want = ref.forward(model, state,
                           np.concatenate([row, res.token_ids[:-1]]),
                           [e["position"] for e in traj])
        for e, z in zip(traj, want["logits"]):
            assert e["token"] == z.argmax() == e["top_ids"][0]
            np.testing.assert_allclose(e["top_logits"],
                                       z[e["top_ids"]], atol=ATOL)
            np.testing.assert_allclose(
                e["lse"], jax.nn.logsumexp(jnp.asarray(z)), atol=ATOL)


@pytest.fixture(scope="module")
def looped(toy):
    """The toy with its head's rows rolled, so that rows end at steps of
    their own (the seeded head names tokens that repeat)."""
    _, _, cfg, params = toy
    params = dict(params, lm_head=jnp.roll(params["lm_head"], 1, axis=0))
    return GreedyGenerator(cfg, params, WordTokenizer(),
                           model=M.CachedModel(cfg), gen_length=7,
                           top_logits=4)


@pytest.mark.parametrize("case", per_step_loop.CASES)
def test_the_loop_gives_what_the_hosts_loop_gave(looped, case):
    """The decode loop on the device against a program a step
    (``tests/per_step_loop.py``) over the three kinds of cache: K/V rows,
    every linear layer's float32 state and its conv window carried through
    the loop."""
    texts = [words(r) for r in prompts(31, (70, 12, 9))]
    seen = per_step_loop.check_case(case, looped, texts, 7)
    if seen["done"] is not None:
        assert seen["done"]["load"].shape == (0, 4)
        assert seen["done"]["keys"] is None


# -- through the engine and the batcher ----------------------------------------------


@pytest.fixture()
def engine(tmp_path):
    """A toy ``olmo_hybrid`` checkpoint on disk, loaded the way
    ``build_engine`` loads a ``kind: generative`` task."""
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine
    from semantic_router_tpu.runtime.bootstrap import build_generator

    dirs = family.write_checkpoints(str(tmp_path), CONFIG, 11)
    with open(os.path.join(dirs["jailbreak"], "config.json")) as f:
        hf = json.load(f)
    gen, adapters = build_generator(
        {"generation": {"gen_length": 6}}, hf, dirs["jailbreak"],
        WordTokenizer(), None)
    assert isinstance(gen, GreedyGenerator) and adapters == {}
    assert isinstance(gen.model, M.CachedModel)
    assert gen.config.layer_types == tuple(MODEL["layer_types"])
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=50.0, seq_len_buckets=[64]))
    eng.register_generative("guard", gen)
    yield eng
    eng.shutdown()


def test_guard_classify_writes_the_three_kinds_of_cache_on_the_marker(
        engine, seen):
    rs = engine._runtime_stats
    verdict = engine.guard_classify("guard", words(prompts(12, (9,))[0]))
    assert verdict.safety == "Controversial"  # seeded weights say nothing
    steps = [f for n, f in seen if n == "engine.step"]
    assert [s["flavour"] for s in steps] == ["gen.prefill", "gen.decode"]
    marks = [f for n, f in seen if n == "engine.gen.forward"]
    assert [m["flavour"] for m in marks] == [s["flavour"] for s in steps]
    M_len = 128  # 64 + 6 + 1 rounded up to 64
    want = {"full": 2 * 1 * 3 * M_len * 20 * 4,
            "state": 3 * 1 * 3 * 12 * 24 * 4,
            "conv": 3 * 1 * 3 * 3 * 48 * 4}
    for kind, size in want.items():
        assert marks[0][f"cache_bytes_{kind}"] == size
        assert f"cache_bytes_{kind}" not in marks[1]
        assert rs.gen_cache_bytes.get(task="guard", kind=kind) == size
    assert marks[0]["rows_per_group"] == 1
    assert marks[0]["attn_tiles_visited"] <= marks[0]["attn_tiles_grid"]
    assert marks[1]["forwards"] == 5
    # no expert layer, no routed pairs
    assert marks[0]["layers"] == marks[0]["pairs"] == 0


def test_warmup_compiles_both_programs_of_every_row_count(engine):
    engine.warmup(batch_sizes=(1, 2))
    assert [(r["target"], r["bucket"], r["rows"], r["error"])
            for r in engine.warmup_report()] == [
        ("gen:guard", 64, 1, ""), ("gen:guard", 64, 2, "")]
    gen = engine._tasks["guard"].generator
    keys = (sorted(gen._prefill_cache), sorted(gen._loop_cache))
    assert keys == ([(1, 64, 128), (2, 64, 128)],
                    [(1, 1, 128, 5), (2, 1, 128, 5)])
    engine.guard_classify("guard", words(prompts(14, (9,))[0]))
    assert (sorted(gen._prefill_cache), sorted(gen._loop_cache)) == keys
