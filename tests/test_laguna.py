"""The laguna family at a toy size on the CPU (hidden 64; five layers of
the published pattern — full + dense, sliding x3, full — with 4 query heads
in a full layer and 6 in a sliding one over 2 k/v heads of 16; a window of 8
keys, smaller than the prompts; YaRN over HALF of a head's dims in the full
layers, plain RoPE over all of them in the sliding ones; 16 experts top-3
beside a gated shared one, 8 of them and half the vocabulary held): the
program's prefill and decode loop through BOTH caches against the plain
reference (``chipbench/reference/laguna.py``) on seeded float32 weights, the
ring, the shares, what ``from_hf`` refuses, each dropped mechanism, the
token-at-a-time loop, and two prompt buckets of one task in one batcher.

Tolerances: both sides compute in float32 here and differ only in the order
of their sums, so logits of size 1-10 agree to 2e-4; bfloat16 would miss
that by two orders of magnitude, which ``chipbench``'s limits hold on the
chip."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import per_step_loop
from chipbench import cells
from test_lfm2_moe import ROW_GROUPS, touched_by_group
from semantic_router_tpu.models import checkpoints, gated_window
from semantic_router_tpu.models import experts as expert_layer
from semantic_router_tpu.models import laguna as M
from semantic_router_tpu.models.generate import GreedyGenerator
from semantic_router_tpu.ops import rope as rope_ops
from semantic_router_tpu.utils.tokenization import Encoding

ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 32, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.2079441541679836,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
MODEL = {
    "model_type": "laguna", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 8,
    "rope_parameters": ROPE,
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "gating_types": ["per_head"] * 5, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "moe_router_logit_softcapping": 0, "torch_dtype": "float32"}
EXPERTS, VOCAB = (8, 8), (256, 256)  # the second half of each
CONFIG = {
    "family": "swa_gqa_ar_guard", "model": MODEL,
    "published": {"num_experts": 16, "vocab_size": 512},
    "held": {"experts": list(EXPERTS), "vocab": list(VOCAB)},
    "weights": {"std": 0.15, "embed_std": 0.5, "head_std": 0.3,
                "qk_norm": {"full_attention": 1.1,
                            "sliding_attention": 1.4},
                "router_std": 0.3, "router_row_log_std": 0.3,
                "writer_threads": 2},
    "tasks": {"jailbreak": {"kind": "generative"}},
    "route_margin": 0.01, "route_sample": 8}
ATOL = 2e-4

family = cells.load_family(CONFIG)
ref = cells.load_module("reference", "laguna")


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


def variant(experts=EXPERTS, vocab=VOCAB, **changes):
    """(published numbers, state, config, params) of the toy with
    ``changes``, holding ``experts`` and ``vocab``."""
    config = dict(CONFIG, model=dict(
        MODEL, num_experts=experts[1], vocab_size=vocab[1], **changes),
        held={"experts": list(experts), "vocab": list(vocab)})
    state = family.generate_state(config, 7)
    hf = family.published_model(config)
    cfg = M.LagunaConfig.from_hf(hf, experts_held=experts, vocab_held=vocab)
    return hf, state, cfg, M.params_from_state(state.__getitem__, cfg)


@pytest.fixture(scope="module")
def toy():
    return variant()


def reference(hf, state, ids, rows=None, **kw):
    kw.setdefault("experts_held", EXPERTS)
    kw.setdefault("vocab_held", VOCAB)
    return ref.forward(hf, state, ids, rows, **kw)


def prompts(seed: int, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 250, n) for n in lengths]


def padded(rows, bucket: int, pad: int = 0):
    ids = np.full((len(rows), bucket), pad, np.int32)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
    return jnp.asarray(ids), jnp.asarray([len(r) for r in rows], jnp.int32)


# -- prefill, then the decode steps through both caches ------------------------------


def serve(cfg, params, rows, steps: int, bucket: int = 48, cache_len=64):
    """``rows`` (and a padding row) prefilled together, then ``steps``
    tokens decoded: ``(logits a forward [1 + steps, rows, V], each row's
    ids with what was decoded)``."""
    ids, lengths = padded(list(rows) + [[]], bucket)
    cache, logits, _ = jax.jit(
        lambda p, i, n: M.prefill(cfg, p, i, n, cache_len))(
            params, ids, lengths)
    step = jax.jit(lambda p, c, t, at: M.decode(cfg, p, c, t, at))
    more = prompts(6, (steps,) * len(rows))
    out, at = [np.asarray(logits)], lengths
    for t in range(steps):
        tokens = jnp.asarray([m[t] for m in more] + [0], jnp.int32)
        cache, logits, _ = step(params, cache, tokens, at)
        out.append(np.asarray(logits))
        at = at + 1
    return np.stack(out), [np.concatenate([r, m])
                           for r, m in zip(rows, more)]


@pytest.mark.parametrize("n, steps", [(5, 2), (40, 8), (6, 8)], ids=[
    "shorter_than_the_window", "longer_than_the_window",
    "decode_crosses_the_rings_wrap"])
def test_prefill_then_decode_equal_the_full_forward(toy, n, steps):
    """Logits, not tokens: prefill's at the prompt's last token, then each
    decode step's through the whole cache AND the ring, against ONE causal
    forward of the reference with the window as a mask.  A prompt of 5
    stays inside the window of 8 with its 2 decoded tokens; one of 40 has
    turned the ring over five times; one of 6 decodes positions 6 .. 13,
    across slot 7 -> 0."""
    hf, state, cfg, params = toy
    logits, rows = serve(cfg, params, prompts(5, (n, 17)), steps)
    for r, length in ((0, n), (1, 17)):
        want = reference(hf, state, rows[r],
                         np.arange(length - 1, length + steps))
        np.testing.assert_allclose(logits[:, r], want["logits"], atol=ATOL)


def test_the_ring_holds_the_latest_window(toy):
    """A sliding layer's cache after a prompt of 40: slot ``p mod 8`` holds
    position ``p`` for the 8 latest positions, nothing else; a full layer's
    holds every position a column."""
    _, _, cfg, params = toy
    (row,) = prompts(5, (40,))
    cache, _, _ = M.prefill(cfg, params, *padded([row], 48), 64)
    whole, _, _ = M.prefill(cfg, params, *padded([row], 40), 64)
    assert [k.shape for k, _ in cache["full"]] == [(1, 2, 64, 16)] * 2
    assert [k.shape for k, _ in cache["window"]] == [(1, 2, 8, 16)] * 3
    # the same ring whatever the bucket, and not zero
    for a, b in zip(jax.tree.leaves(cache["window"]),
                    jax.tree.leaves(whole["window"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        assert np.abs(np.asarray(a)).min(-1).max() > 0
    # slot j holds the K of position 32 + j: the layer's own keys there
    k_full = np.asarray(cache["full"][0][0])[0]  # [kv, M, D]
    assert (np.abs(k_full[:, :40]).sum(-1) > 0).all() \
        and (k_full[:, 48:] == 0).all()  # the bucket's padding: never seen
    short, _, _ = M.prefill(cfg, params, *padded([row[:3]], 48), 64)
    ring = np.asarray(short["window"][0][0])[0]  # [kv, W, D]
    assert (np.abs(ring[:, :3]).sum(-1) > 0).all() \
        and (ring[:, 3:] == 0).all()
    assert M.CachedModel(cfg).cache_bytes(cache) == {
        "full": 2 * 2 * 2 * 64 * 16 * 4, "window": 3 * 2 * 2 * 8 * 16 * 4}
    assert M._cache_bytes(cfg, 1, 64) == sum(
        M.CachedModel(cfg).cache_bytes(cache).values())


def test_the_ring_is_dots3s_ring():
    """``gated_window.ring_of`` against the slots written one position at a
    time, for rows shorter and longer than the window and a padding row."""
    rng = np.random.default_rng(0)
    seq = rng.standard_normal((4, 20, 2, 3)).astype(np.float32)
    lengths = np.array([20, 5, 0, 8], np.int32)
    got = np.asarray(gated_window.ring_of(jnp.asarray(seq),
                                          jnp.asarray(lengths), 8))
    want = np.zeros((4, 8, 2, 3), np.float32)
    for b, n in enumerate(lengths):
        for p in range(n):
            want[b, p % 8] = seq[b, p]
    np.testing.assert_array_equal(got, want)
    at = jnp.asarray([3, 7, 12], jnp.int32)
    assert list(np.asarray(gated_window.ring_slot(at, 8))) == [3, 7, 4]
    seen = np.asarray(gated_window.ring_seen(at, 8))
    assert seen.sum(-1).tolist() == [4, 8, 8]


def test_padding_is_never_seen(toy):
    _, _, cfg, params = toy
    (row,) = prompts(9, (21,))
    a = M.prefill(cfg, params, *padded([row], 24), 32)[1]
    b = M.prefill(cfg, params, *padded([row], 48, pad=77), 64)[1]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("rows, group, real", ROW_GROUPS)
def test_a_prefill_in_groups_equals_its_rows_one_at_a_time(toy, rows, group,
                                                           real):
    """``test_lfm2_moe``'s test of the same name on this decoder: what a
    leading batch axis could get wrong here is the ring's gather at each
    row's own last position.  Rows past the window (8), padding rows
    inside a group and a whole group of them."""
    _, _, cfg, params = toy
    lens = [21, 40, 9, 33, 3, 17, 26, 12][:real] + [0] * (rows - real)
    ids, lengths = padded(prompts(22, lens), 48)
    cache, logits, aux = jax.jit(
        lambda p, i, n: M._prefill_groups(cfg, p, i, n, 64, group))(
            params, ids, lengths)
    one = jax.jit(lambda p, i, n: M._prefill_rows(cfg, p, i, n, 64))
    alone = [one(params, ids[b:b + 1], lengths[b:b + 1])
             for b in range(rows)]

    def rows_of(leaf, axis=0):
        return np.concatenate([np.asarray(leaf(a)) for a in alone], axis)

    def close(got, want):  # a group's sums run in another order: 2e-5 seen
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=5e-5)

    close(logits[:real], rows_of(lambda a: a[2])[:real])
    for kind, at in (("full", 0), ("window", 1)):
        for i, pair in enumerate(cache[kind]):
            for j, leaf in enumerate(pair):
                close(leaf, rows_of(lambda a: a[at][i][j]))
    lens = np.asarray(lengths)
    experts, want = np.asarray(aux["experts"]), rows_of(lambda a: a[3], 1)
    for b in range(rows):
        assert (experts[:, b, :lens[b]] == want[:, b, :lens[b]]).all()
    np.testing.assert_array_equal(
        np.asarray(aux["load"])[:, :3],
        touched_by_group(experts, lens, group, EXPERTS))


# -- the two RoPE parameter sets ------------------------------------------------------


def test_the_rotary_front_rotates_half_a_head_and_passes_the_rest():
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.standard_normal((2, 5, 3, 16)), jnp.float32)
            for _ in range(2))
    cos_t, sin_t = rope_ops.RopeSpec(8, 500000.0).tables(5)
    cos, sin = (jnp.asarray(t)[None, :, None, :] for t in (cos_t, sin_t))
    q2, k2 = rope_ops.apply_rotary_front(q, k, cos, sin)
    np.testing.assert_array_equal(np.asarray(q2[..., 8:]),
                                  np.asarray(q[..., 8:]))
    front, _ = rope_ops.apply_rotary(q[..., :8], k[..., :8], cos, sin)
    np.testing.assert_array_equal(np.asarray(q2[..., :8]), np.asarray(front))
    assert np.abs(np.asarray(k2[:, 1:, :, :8] - k[:, 1:, :, :8])).max() > 0.1
    # tables as wide as the head: plain RoPE
    cos_t, sin_t = rope_ops.RopeSpec(16, 10000.0).tables(5)
    cos, sin = (jnp.asarray(t)[None, :, None, :] for t in (cos_t, sin_t))
    for a, b in zip(rope_ops.apply_rotary_front(q, k, cos, sin),
                    rope_ops.apply_rotary(q, k, cos, sin)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", M.LAYER_TYPES)
def test_the_programs_tables_are_the_references(toy, kind):
    """YaRN's blended frequencies and its factor on cos and sin (full
    layers, over half a head), and the plain ones (sliding)."""
    hf, _, cfg, _ = toy
    spec = cfg.rope_of(kind)
    freq, scaling = ref.inv_freq(hf["rope_parameters"][kind],
                                 spec.rotary_dim)
    assert spec.rotary_dim == (8 if kind == "full_attention" else 16)
    cos, sin = spec.tables(50)
    ang = np.arange(50)[:, None] * freq[None, :]
    np.testing.assert_allclose(cos[:, :len(freq)], np.cos(ang) * scaling,
                               atol=1e-5)
    np.testing.assert_allclose(sin[:, len(freq):], np.sin(ang) * scaling,
                               atol=1e-5)
    if kind == "full_attention":
        assert scaling == pytest.approx(1.2079441541679836)
        plain, _ = ref.inv_freq(dict(hf["rope_parameters"][kind],
                                     rope_type="default"), 8)
        assert np.abs(freq - plain).max() > 1e-3  # the blend is active


# -- the expert layer: one router, and a chip's share ------------------------------


def test_the_router_is_the_one_sdar_calls(toy):
    _, _, cfg, params = toy
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((9, 64)),
                    jnp.float32)
    top_e, w = M.route(cfg, p, x)
    other = expert_layer.softmax_route(x, p["router"], 3, True)
    assert (np.asarray(top_e) == np.asarray(other[0])).all()
    np.testing.assert_allclose(np.asarray(w), 2.5 * np.asarray(other[1]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)


@pytest.mark.parametrize("impl", ["ragged_dot", "megablox"])
def test_the_shares_add_up_to_the_uncut_layer(impl, monkeypatch):
    """The routed parts of the shares of all four chips, plus the gated
    shared expert counted ONCE, are the uncut reference layer."""
    if impl == "megablox":
        monkeypatch.setattr(expert_layer, "_grouped_matmul",
                            expert_layer._megablox)
    hf, state, cfg, params = variant(experts=(0, 16))
    x = jnp.asarray(np.random.default_rng(2).standard_normal((11, 64)),
                    jnp.float32)
    valid = jnp.ones(11, bool)
    w = ref.layer_weights(hf, state, 1, "highest", (0, 16))["ff"]
    want, _, _ = ref.moe(hf, w, x, (0, 16))
    p = params["layers"][1]
    total = M.shared_expert(cfg, p, x)
    for first in range(0, 16, 4):
        part = dict(p, gate_up=p["gate_up"][first:first + 4],
                    down=p["down"][first:first + 4])
        top_e, top_w = M.route(cfg, p, x)
        y, _ = expert_layer.routed_experts(part, x, valid, top_e, top_w,
                                       (first, 4), cfg.dtype)
        total = total + y
        routed, _, _ = ref.moe(hf, {**w, **{
            k: w[k][first:first + 4] for k in ("gate", "up", "down")}}, x,
            (first, 4), shared=False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(routed),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_the_vocabularys_slices_give_the_whole_logits_slices():
    """Attention is whole on every chip: with every expert held, each
    ``vocab_held`` slice's logits are that slice of the whole
    vocabulary's, the reference's and the program's alike."""
    hf, state, _, _ = variant(experts=(0, 16), vocab=(0, 512))
    (row,) = prompts(4, (19,))
    whole = ref.forward(hf, state, row, [18])["logits"]
    assert whole.shape == (1, 512)
    for first in (0, 256):
        ids = row + first  # the same rows of the embedding
        cfg = M.LagunaConfig.from_hf(hf, vocab_held=(first, 256))
        params = M.params_from_state(state.__getitem__, cfg)
        # ids are over the slice: row ``first`` of the embedding is id 0
        got = M.prefill(cfg, params, *padded([ids - first], 24), 32)[1]
        want = ref.forward(hf, state, ids, [18])["logits"]
        np.testing.assert_allclose(np.asarray(got)[0],
                                   want[0, first:first + 256], atol=ATOL)
        sliced = ref.forward(hf, state, ids - first, [18],
                             vocab_held=(first, 256))["logits"]
        np.testing.assert_allclose(sliced[0], want[0, first:first + 256],
                                   atol=1e-5)


def test_a_share_of_the_model_equals_the_references_share(toy):
    """The whole model holding the second half of the experts and of the
    vocabulary against the reference given the same shares; and it is not
    what a smaller share gives."""
    hf, state, cfg, params = toy
    (row,) = prompts(4, (19,))
    logits = M.prefill(cfg, params, *padded([row], 24), 32)[1]
    want = reference(hf, state, row, [18])
    np.testing.assert_allclose(np.asarray(logits), want["logits"], atol=ATOL)
    assert logits.shape == (1, 256)
    other = reference(hf, state, row, [18], experts_held=(8, 4))["logits"]
    assert np.abs(other - want["logits"]).max() > 100 * ATOL


def test_params_hold_only_what_is_held(tmp_path):
    """A checkpoint on disk with the WHOLE vocabulary and only the held
    experts' files: the loader reads rows 256-511 and experts 8-15, by
    slice, and nothing else of either."""
    config = dict(CONFIG, model=dict(MODEL))
    dirs = family.write_checkpoints(str(tmp_path), config, 11)
    with open(os.path.join(dirs["jailbreak"], "config.json")) as f:
        hf = json.load(f)
    assert hf["num_experts"] == 16 and hf["vocab_size"] == 512
    cfg = M.LagunaConfig.from_hf(hf, experts_held=EXPERTS, vocab_held=VOCAB)
    asked, sliced = [], []
    with checkpoints.checkpoint_reader(dirs["jailbreak"]) as get:
        def spy(name):
            asked.append(name)
            return get(name)

        def rows(name, first, count):
            sliced.append((name, first, count))
            return get.rows(name, first, count)

        spy.rows = rows
        params = M.params_from_state(spy, cfg)
        whole = get("model.embed_tokens.weight")
    assert params["embed"].shape == (256, 64) == params["lm_head"].shape
    np.testing.assert_array_equal(np.asarray(params["embed"]), whole[256:])
    assert sorted(sliced) == [("lm_head.weight", 256, 256),
                              ("model.embed_tokens.weight", 256, 256)]
    assert not [n for n in asked if "embed_tokens" in n or "lm_head" in n]
    experts = {int(n.split("experts.")[1].split(".")[0]) for n in asked
               if ".experts." in n}
    assert experts == set(range(8, 16))
    assert params["layers"][1]["gate_up"].shape == (8, 64, 64)
    assert params["layers"][1]["router"].shape == (64, 16)
    assert params["layers"][1]["q_proj"].shape == (64, 6 * 16)
    assert params["layers"][0]["q_proj"].shape == (64, 4 * 16)
    assert params["layers"][0]["gate_up"].shape == (64, 2 * 96)


# -- the configuration ---------------------------------------------------------------


def test_every_model_number_comes_from_the_checkpoints_config():
    hf = family.published_model(CONFIG)
    cfg = M.LagunaConfig.from_hf(hf)
    tuples = ("layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer")
    for key, value in hf.items():
        if hasattr(cfg, key) and key not in tuples:
            assert getattr(cfg, key) == value, key
    for key in tuples:
        assert getattr(cfg, key) == tuple(MODEL[key])
    assert cfg.dtype == jnp.float32 and cfg.held == (0, 16)
    assert cfg.vocab == (0, 512)
    assert cfg.heads("full_attention") == 4
    assert cfg.heads("sliding_attention") == 6
    full, sliding = (cfg.rope_of(k) for k in M.LAYER_TYPES)
    assert (full.theta, full.rotary_dim, dict(full.yarn)["factor"]) \
        == (500000.0, 8, 8)
    assert (sliding.theta, sliding.rotary_dim, sliding.yarn) \
        == (10000.0, 16, None)
    real = M.LagunaConfig()
    assert (real.hidden_size, real.num_experts, real.num_experts_per_tok,
            real.sliding_window, real.moe_routed_scaling_factor) \
        == (3072, 256, 10, 512, 2.5)
    hash(cfg)  # a frozen dataclass of hashables


def _rope_with(kind: str, **changes):
    return {"rope_parameters": dict(ROPE, **{kind: dict(ROPE[kind],
                                                        **changes)})}


@pytest.mark.parametrize("changes, says", [
    ({"moe_router_logit_softcapping": 30.0}, "softcapping"),
    ({"moe_apply_router_weight_on_input": True}, "weight_on_input"),
    ({"gating": True}, "gating"),
    ({"gating": "elementwise"}, "gating"),
    (_rope_with("full_attention", rope_type="llama3"), "rope_type"),
    (_rope_with("sliding_attention", rope_type="linear"), "rope_type"),
    (_rope_with("full_attention", partial_rotary_factor=0.05),
     "partial_rotary_factor"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"hidden_act": "gelu"}, "silu"),
    ({"layer_types": ["full_attention"] * 4}, "layer_types"),
    ({"layer_types": ["linear_attention"] * 5}, "layer_types"),
    ({"mlp_layer_types": ["dense"] + ["sparse"] * 3}, "mlp_layer_types"),
    ({"mlp_layer_types": ["sparse"] * 5}, "mlp_only_layers"),
    ({"mlp_layer_types": ["dense", "moe", "sparse", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 6]},
     "num_attention_heads_per_layer"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 6, 6]},
     "one count a layer type"),
    ({"num_attention_heads_per_layer": [4, 5, 5, 5, 4]},
     "multiple of num_key_value_heads"),
    ({"gating_types": ["per_head"] * 4}, "gating_types"),
    ({"gating_types": ["per_head"] * 4 + ["per_element"]}, "gating_types"),
    ({"layer_types": ["sliding_attention"] * 5,
      "num_attention_heads_per_layer": [6] * 5}, "full_attention layer"),
    ({"mlp_layer_types": ["dense"] * 5, "mlp_only_layers": [0, 1, 2, 3, 4]},
     "sparse layer")])
def test_what_the_architecture_cannot_express_is_refused(changes, says):
    with pytest.raises(ValueError, match=says):
        M.LagunaConfig.from_hf(dict(family.published_model(CONFIG),
                                    **changes))


def test_laguna_is_a_row_of_the_served_generators():
    from semantic_router_tpu.runtime import bootstrap

    assert "laguna" in bootstrap.GENERATORS
    assert "``laguna``:" in bootstrap.build_generator.__doc__
    with pytest.raises(ValueError, match=r"'mamba2'.*laguna"):
        bootstrap.build_generator({}, dict(MODEL, model_type="mamba2"), "",
                                  None, lambda path: {})


# -- each mechanism, dropped, fails the comparison -----------------------------------


def _no_gate(p, h, out, dtype):
    return gated_window.gate_out(dict(p, gate_proj=p["gate_proj"] * 0), h,
                                 out * 2, dtype)


@contextlib.contextmanager
def faulty(fault: str, cfg):
    """``cfg`` and the module with one mechanism of the model wrong."""
    if fault == "window_off_by_one":  # 7 keys where the model's window is 8
        yield dataclasses.replace(cfg, sliding_window=7)
    elif fault == "whole_cache_for_a_ring":  # a sliding layer sees it all
        yield dataclasses.replace(cfg, sliding_window=64)
    elif fault == "full_width_rotary":  # all 16 dims of a full layer rotate
        full, sliding = cfg.rope
        yield dataclasses.replace(cfg, rope=(
            dataclasses.replace(full, rotary_dim=16), sliding))
    elif fault == "no_yarn":  # theta alone in the full layers
        full, sliding = cfg.rope
        yield dataclasses.replace(cfg, rope=(
            dataclasses.replace(full, yarn=None), sliding))
    elif fault == "no_gate":  # every head's gate is 1
        with mock.patch.object(M, "gate_out", _no_gate):
            yield cfg
    elif fault == "no_routed_scaling":
        yield dataclasses.replace(cfg, moe_routed_scaling_factor=1.0)
    elif fault == "no_shared_gate":  # the shared expert at weight 1
        inner = M.shared_expert
        with mock.patch.object(M, "shared_expert", lambda c, p, x: inner(
                c, dict(p, shared_gate=p["shared_gate"] * 0), x) * 2):
            yield cfg
    else:
        raise AssertionError(fault)


@pytest.mark.parametrize("fault", [
    "window_off_by_one", "whole_cache_for_a_ring", "full_width_rotary",
    "no_yarn", "no_gate", "no_routed_scaling", "no_shared_gate"])
def test_a_dropped_mechanism_fails_the_comparison(toy, fault):
    """Prefill and eight decode steps of a prompt of 40 with one mechanism
    wrong miss the reference's logits by far more than the tolerance the
    sound program keeps (``test_prefill_then_decode_equal_the_full_forward``)."""
    hf, state, cfg, params = toy
    with faulty(fault, cfg) as wrong:
        logits, rows = serve(wrong, params, prompts(5, (40,)), 8)
    want = reference(hf, state, rows[0], np.arange(39, 48))["logits"]
    worst = np.abs(logits[:, 0] - want).max(-1)
    assert worst[0] > 25 * ATOL, (fault, "prefill", worst)
    assert worst[1:].max() > 25 * ATOL, (fault, "decode", worst)


def test_head_counts_of_the_wrong_kind_do_not_fit(toy):
    """A sliding layer's 6 heads read as the full layers' 4: the
    projections no longer fit, which must not pass in silence."""
    _, _, cfg, params = toy
    wrong = dataclasses.replace(cfg, num_attention_heads_per_layer=(4,) * 5)
    with pytest.raises((TypeError, ValueError)):
        M.prefill(wrong, params, *padded(prompts(5, (12,)), 16), 32)


# -- the one token-at-a-time loop ---------------------------------------------------


def generator(toy, **kw) -> GreedyGenerator:
    _, _, cfg, params = toy
    return GreedyGenerator(cfg, params, WordTokenizer(),
                           model=M.CachedModel(cfg), gen_length=6,
                           top_logits=4, **kw)


def test_the_loop_serves_the_decoder_with_its_trajectory(toy):
    hf, state, _, _ = toy
    rows = prompts(8, (30, 5))
    out = generator(toy).generate([words(r) for r in rows], 6)
    for r, res in zip(rows, out):
        traj = res.trajectory
        assert [e["kind"] for e in traj] == ["prefill"] + ["decode"] * 5
        served = [e["token"] for e in traj]
        assert res.token_ids == served[:len(res.token_ids)]
        n = len(r)
        ids = np.concatenate([r, served[:-1]]).astype(np.int32)
        at = [e["position"] for e in traj]
        assert at == list(range(n - 1, n + 5))
        want = reference(hf, state, ids, at)
        for f, e in enumerate(traj):
            z = want["logits"][f]
            assert int(z.argmax()) == e["token"] \
                or z.max() - z[e["token"]] < 1e-3
            np.testing.assert_allclose(
                e["top_logits"], z[e["top_ids"]], atol=ATOL)
            if f:
                assert e["experts"].shape == (4, 1, 3)
                assert (np.sort(e["experts"][:, 0], -1)
                        == np.sort(want["top_e"][:, at[f]], -1)).all()
        assert traj[0]["experts"].shape == (4, n, 3)
        assert "selected" not in traj[0]


def test_two_rows_a_group_end_at_their_own_lengths_in_the_kernel(
        toy, monkeypatch):
    """Full and sliding layers hand the rows' lengths to the flash kernel
    (here the kernel itself, interpreted, at blocks of 128 so that a bucket
    of 384 is three): two rows of one group — the kernel finds a row's
    length at ``bh // H``, at 4 heads a row in a full layer and 6 in a
    sliding one — each stop at their own end and still give the
    reference's logits; what the kernel folded and what the bucket's grid
    folds without the lengths reach the observer and the marker."""
    from semantic_router_tpu.observability import batchtrace
    from semantic_router_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    monkeypatch.setattr(fa, "GLOBAL_BLOCKS", (128, 128))
    monkeypatch.setattr(fa, "WINDOW_BLOCKS", (128, 128))
    hf, state, cfg, _ = toy
    rows = prompts(31, (130, 300))
    seen = per_step_loop.Steps()
    with mock.patch.object(fa, "flash_attention_pallas",
                           wraps=fa.flash_attention_pallas) as kernel:
        out = generator(toy).generate([words(r) for r in rows], 2,
                                      bucket=384, observer=seen)
    calls = [c.kwargs for c in kernel.call_args_list]
    assert all(c["lengths"].shape == (2,) for c in calls)
    assert [c["window"] for c in calls] == [0, 14, 14, 14, 0]
    for row, res in zip(rows, out):
        e = res.trajectory[0]
        z = reference(hf, state, row, [len(row) - 1])["logits"][0]
        assert int(z.argmax()) == e["token"] \
            or z.max() - z[e["token"]] < 1e-3
        np.testing.assert_allclose(e["top_logits"], z[e["top_ids"]],
                                   atol=ATOL)
    assert fa.tiles_for(384, 0, True, [130, 300]) == (9, 12)
    assert fa.tiles_for(384, 14, True, [130, 300]) == (3 + 5, 2 * 5)
    heads = [cfg.heads(kind) for kind in cfg.layer_types]
    assert heads == [4, 6, 6, 6, 4]
    prefill, loop = seen.closed
    tiles = (2 * 4 * 9 + 3 * 6 * 8, 2 * 4 * 12 + 3 * 6 * 10)
    assert prefill["attn_tiles"] == tiles and "attn_tiles" not in loop
    facts = {}

    def span(name, **kw):
        facts.update(kw)
        return contextlib.nullcontext()

    with mock.patch.object(batchtrace, "trace_span", span):
        batchtrace.gen_forward("gen:t", "gen.prefill", prefill["load"],
                               attn_tiles=prefill["attn_tiles"])
    assert (facts["attn_tiles_visited"], facts["attn_tiles_grid"]) == tiles


def test_a_prefill_reports_both_kinds_of_cache_to_the_observer(toy):
    """``cache_bytes`` by kind reaches ``done`` with the prefill; the
    marker carries each kind and the generation's ``bucket``
    (``batchtrace.gen_forward``)."""
    from semantic_router_tpu.observability import batchtrace

    seen = per_step_loop.Steps()
    (row,) = prompts(2, (20,))
    generator(toy).generate([words(row)], 4, observer=seen)
    prefill, loop = seen.closed
    M_len = 64  # 20 -> bucket 32, + 4 + 1 rounded up to 64
    assert prefill["cache_bytes"] == {
        "full": 2 * 2 * 2 * M_len * 16 * 4, "window": 3 * 2 * 2 * 8 * 16 * 4}
    assert prefill["rows_per_group"] == 1
    assert loop["forwards"] == 3 and loop["load"].shape == (3 * 4, 4)
    facts = {}

    def span(name, **kw):
        facts.update(kw, name=name)
        return contextlib.nullcontext()

    with mock.patch.object(batchtrace, "trace_span", span):
        batchtrace.gen_forward("gen:t", "gen.prefill", prefill["load"],
                               bucket=32, cache_bytes=prefill["cache_bytes"])
    assert facts["bucket"] == 32 and facts["layers"] == 4
    assert facts["cache_bytes_full"] == prefill["cache_bytes"]["full"]
    assert facts["cache_bytes_window"] == prefill["cache_bytes"]["window"]
    with mock.patch.object(batchtrace, "trace_span", span):
        facts.clear()
        batchtrace.gen_forward("gen:t", "gen.decode", loop["load"])
    assert "bucket" not in facts
    assert not [k for k in facts if k.startswith("cache_bytes")]
    with mock.patch.object(batchtrace, "trace_span", span):
        facts.clear()
        batchtrace.queue_wait("abc", "g", 0.25, bucket=512)
        assert facts["bucket"] == 512 and facts["wait_us"] == 250000
        facts.clear()
        batchtrace.queue_wait("abc", "g", 0.25)
        assert "bucket" not in facts


@pytest.fixture(scope="module")
def looped(toy):
    return generator(toy)


@pytest.mark.parametrize("case", per_step_loop.CASES)
def test_the_loop_gives_what_the_hosts_loop_gave(looped, case):
    """The decode loop on the device against a program a step
    (``tests/per_step_loop.py``) over both kinds of cache: the full
    layers' K and V and the sliding layers' rings carried through the loop
    (a prompt of 5 crosses the ring's wrap inside it)."""
    texts = [words(r) for r in prompts(33, (30, 5, 21))]
    seen = per_step_loop.check_case(case, looped, texts, 7)
    for res in seen["out"]:
        for e in res.trajectory[1:]:
            assert e["experts"].shape == (4, 1, 3)
    if seen["done"] is not None:
        assert seen["done"]["load"].shape == (4 * len(seen["steps"]), 4)


# -- two prompt buckets of one task in one batcher ------------------------------------


@pytest.fixture
def engine(tmp_path):
    """A toy ``laguna`` checkpoint on disk, loaded the way ``build_engine``
    loads a ``kind: generative`` task holding its share, behind an engine of
    TWO prompt buckets."""
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine
    from semantic_router_tpu.runtime.bootstrap import build_generator

    dirs = family.write_checkpoints(str(tmp_path), CONFIG, 11)
    with open(os.path.join(dirs["jailbreak"], "config.json")) as f:
        hf = json.load(f)
    gen, adapters = build_generator(
        {"generation": {"gen_length": 6}, "experts_held": list(EXPERTS),
         "vocab_held": list(VOCAB)}, hf, dirs["jailbreak"],
        WordTokenizer(), None)
    assert isinstance(gen, GreedyGenerator) and adapters == {}
    assert isinstance(gen.model, M.CachedModel)
    assert gen.config.held == EXPERTS and gen.config.vocab == VOCAB
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=50.0, seq_len_buckets=[64, 128]))
    eng.register_generative("guard", gen)
    yield eng
    eng.shutdown()


def test_warmup_compiles_every_rows_and_bucket_pair(engine):
    engine.warmup(tasks=["guard"], batch_sizes=[1, 2, 4])
    report = engine.warmup_report()
    assert sorted((r["bucket"], r["rows"]) for r in report) == sorted(
        (b, n) for b in (64, 128) for n in (1, 2, 4))
    assert not [r for r in report if r.get("error")]
    gen = engine._tasks["guard"].generator
    # the cache's length is the bucket's own: 64 + 6 + 1 and 128 + 6 + 1
    assert sorted(gen._prefill_cache) == sorted(
        (n, b, m) for b, m in ((64, 128), (128, 192)) for n in (1, 2, 4))
    assert sorted(gen._loop_cache) == sorted(
        (n, 1, m, 5) for m in (128, 192) for n in (1, 2, 4))
    # a served generation of either bucket compiles nothing more
    before = (len(gen._prefill_cache), len(gen._loop_cache))
    rows = prompts(12, (20, 90))
    engine.generate("guard", [words(r) for r in rows], 6)
    assert (len(gen._prefill_cache), len(gen._loop_cache)) == before


def test_short_and_long_prompts_queue_together_and_run_by_bucket(engine):
    """Two keys of one generative task in one batcher: short (bucket 64)
    and long (bucket 128) prompts submitted together come back each from a
    group of its own bucket, with the ``bucket`` fact on the markers and on
    the queue waits, and with the tokens each gets alone."""
    from concurrent.futures import ThreadPoolExecutor

    from semantic_router_tpu.observability import batchtrace

    rows = prompts(12, (33, 100, 9, 77, 21, 120))
    buckets = [64, 128, 64, 128, 64, 128]
    alone = [engine.generate("guard", [words(r)], 6)[0] for r in rows]
    events = []
    real = batchtrace.trace_span

    def span(name, **kw):
        events.append((name, kw))
        return real(name, **kw)

    with mock.patch.object(batchtrace, "trace_span", span), \
            ThreadPoolExecutor(6) as pool:
        together = list(pool.map(
            lambda r: engine.generate("guard", [words(r)], 6)[0], rows))
    for a, b in zip(together, alone):
        assert a.token_ids == b.token_ids
        for x, y in zip(a.trajectory, b.trajectory):
            np.testing.assert_allclose(x["top_logits"], y["top_logits"],
                                       atol=ATOL)
    waits = [kw for name, kw in events
             if name == batchtrace.QUEUE_WAIT_ANNOTATION]
    assert sorted(w["bucket"] for w in waits) == sorted(buckets)
    for w in waits:
        assert w["group"].split(":")[:3] == ["__generate__", "guard",
                                             str(w["bucket"])]
    marks = [kw for name, kw in events
             if name == batchtrace.GEN_FORWARD_ANNOTATION]
    assert {m["bucket"] for m in marks} == {64, 128}
    prefills = [m for m in marks if m["flavour"] == "gen.prefill"]
    # a prefill's cache is its bucket's: 2 full layers of 2 k/v heads of 16
    # over 128 or 192 columns a padded row, three rings of 8 whatever it is
    for m in prefills:
        cache_len = {64: 128, 128: 192}[m["bucket"]]
        rows_padded = m["cache_bytes_window"] // (3 * 2 * 2 * 8 * 16 * 4)
        assert m["cache_bytes_full"] == \
            rows_padded * 2 * 2 * 2 * cache_len * 16 * 4
    steps = [kw for name, kw in events
             if name == batchtrace.STEP_ANNOTATION
             and kw.get("flavour") == "gen.prefill"]
    assert {s["bucket"] for s in steps} == {64, 128}
    # no group mixed the two: every prefill's real rows fit its bucket
    assert sum(s["rows"] for s in steps) == len(rows)
    assert sum(s["rows"] for s in steps if s["bucket"] == 64) == 3
    stats = engine._runtime_stats
    stats.flush()
    assert stats.gen_cache_bytes.get(task="guard", kind="full") > 0
    assert stats.gen_cache_bytes.get(task="guard", kind="window") > 0
    verdict = engine.guard_classify("guard", words(rows[0]))
    assert verdict.safety in ("Safe", "Unsafe", "Controversial")
