"""The dots3_note family at a toy size on the CPU (hidden 64; full layers
of 4 latent heads under an indexer of 4 heads that keeps 8 keys, sliding
layers of 2 heads over a window of 5; 16 experts top-2 beside a shared one,
8 of them and half the vocabulary held): the program's layers, prefill and
decode through the latent cache against the plain reference
(``chipbench/reference/dots3_note.py``) on seeded float32 weights, the
selection, the ring, the shares, and the token-at-a-time loop through the
engine's batcher.

Tolerances: both sides compute in float32 here and differ only in the order
of their sums, so logits of size 1-10 agree to 2e-4; bfloat16 would miss
that by two orders of magnitude, which ``chipbench``'s limits hold on the
chip."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import per_step_loop
from chipbench import cells
from test_lfm2_moe import ROW_GROUPS, touched_by_group
from semantic_router_tpu.models import checkpoints
from semantic_router_tpu.models import dots3_note as M
from semantic_router_tpu.models import experts as expert_layer
from semantic_router_tpu.models import lfm2_moe
from semantic_router_tpu.models.generate import GreedyGenerator
from semantic_router_tpu.utils.tokenization import Encoding

MODEL = {
    "model_type": "dots3_note", "apply_mla_qkv_lora_rescale": True,
    "attention_bias": False, "attention_gate_type": "headwise",
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
    "index_head_dim": 16, "index_n_heads": 4, "index_topk": 8,
    "intermediate_size": 96, "kv_lora_rank": 16,
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "max_position_embeddings": 512, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_routed_experts": 8, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 4, "q_lora_rank": 32, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_scaling": None,
    "rope_theta": 80000000, "routed_scaling_factor": 1,
    "scoring_func": "sigmoid", "sliding_window_size": 5,
    "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 32,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
    "swa_q_lora_rank": 32, "swa_qk_nope_head_dim": 12,
    "swa_qk_rope_head_dim": 8, "swa_rope_theta": 50000, "swa_v_head_dim": 8,
    "tie_word_embeddings": False, "topk_method": "noaux_tc", "v_head_dim": 8,
    "vocab_size": 256, "torch_dtype": "float32"}
EXPERTS, VOCAB = (8, 8), (256, 256)  # the second half of each
CONFIG = {
    "family": "sparse_latent_ar_guard", "model": MODEL,
    "published": {"n_routed_experts": 16, "vocab_size": 512},
    "held": {"experts": list(EXPERTS), "vocab": list(VOCAB)},
    "weights": {"std": 0.15, "embed_std": 0.5, "head_std": 0.3,
                "index_weight_std": 0.15, "router_std": 0.3,
                "router_row_log_std": 0.3, "expert_bias_std": 0.05,
                "writer_threads": 2},
    "tasks": {"jailbreak": {"kind": "generative"}},
    "route_margin": 0.01, "route_sample": 8}
ATOL = 2e-4

family = cells.load_family(CONFIG)
ref = cells.load_module("reference", "dots3_note")


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
        MODEL, n_routed_experts=experts[1], vocab_size=vocab[1], **changes),
        held={"experts": list(experts), "vocab": list(vocab)})
    state = family.generate_state(config, 7)
    hf = family.published_model(config)
    cfg = M.Dots3NoteConfig.from_hf(hf, experts_held=experts,
                                    vocab_held=vocab)
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


def bits_to_sets(bits, n: int):
    return np.unpackbits(np.asarray(bits), axis=-1)[..., :n].astype(bool)


# -- the layers, one kind at a time -----------------------------------------------


@pytest.mark.parametrize("kind, dense", [
    ("full_attention", 1), ("full_attention", 0), ("sliding_attention", 1),
    ("sliding_attention", 0)],
    ids=["full+mlp", "full+moe", "window+mlp", "window+moe"])
def test_one_layer_of_each_kind_equals_the_reference(kind, dense):
    """Two layers, the second always a full layer with experts (the loop
    reports both kinds' choices), the first of the kind under test."""
    hf, state, cfg, params = variant(
        num_hidden_layers=2, first_k_dense_replace=dense,
        layer_types=[kind, "full_attention"])
    (row,) = prompts(3, (23,))
    ids, lengths = padded([row], 24)
    _, logits, aux = M.prefill(cfg, params, ids, lengths, 32)
    want = reference(hf, state, row, [22])
    np.testing.assert_allclose(np.asarray(logits), want["logits"], atol=ATOL)
    assert (np.sort(np.asarray(aux["experts"])[:, 0, :23], -1)
            == np.sort(want["top_e"], -1)).all()
    assert aux["experts"].shape[0] == 2 - dense


# -- prefill, then decoding through the latent cache ------------------------------


@pytest.fixture(scope="module")
def served(toy):
    """A row of 40 tokens prefilled beside one of 17 and a padding row,
    then 8 tokens decoded: ``(logits a forward [9, rows, V], selected bits
    a decode step, keys a forward, prefill aux, the row's ids)``."""
    hf, state, cfg, params = toy
    long, short = prompts(5, (40, 17))
    ids, lengths = padded([long, short, []], 48)
    cache, logits, aux = jax.jit(
        lambda p, i, n: M.prefill(cfg, p, i, n, 64))(params, ids, lengths)
    step = jax.jit(lambda p, c, t, at: M.decode(cfg, p, c, t, at))
    more = prompts(6, (8, 8))
    out, chosen, keys = [np.asarray(logits)], [], [np.asarray(aux["keys"])]
    at = lengths
    for t in range(8):
        tokens = jnp.asarray([more[0][t], more[1][t], 0], jnp.int32)
        cache, logits, a = step(params, cache, tokens, at)
        out.append(np.asarray(logits))
        chosen.append(np.asarray(a["selected"]))
        keys.append(np.asarray(a["keys"]))
        at = at + 1
    return (np.stack(out), chosen, keys, aux,
            [np.concatenate([long, more[0]]),
             np.concatenate([short, more[1]])])


def test_prefill_then_decode_equal_the_full_forward(toy, served):
    """Logits, not tokens: prefill's at the prompt's last token, then each
    decode step's, against ONE causal forward of the reference; the longer
    row's prompt is 8 times the toy window (the ring has turned over) and
    5 times ``index_topk`` (every step selects)."""
    hf, state, _, _ = toy
    logits, _, _, _, rows = served
    for r, n in ((0, 40), (1, 17)):
        want = reference(hf, state, rows[r], np.arange(n - 1, n + 8))
        np.testing.assert_allclose(logits[:, r], want["logits"], atol=ATOL)


def test_the_selection_is_active_and_is_the_references(toy, served):
    """What each decode step selected in each full layer is the
    reference's set at that query wherever the reference's margin (its
    ``index_topk``-th score over the next) is above float32's noise; and it
    is a selection: 8 keys of 41 and more visible."""
    hf, state, cfg, _ = toy
    _, chosen, keys, aux, rows = served
    n, T = 40, 48
    want = reference(hf, state, rows[0], None, select_rows=np.arange(T))
    k = cfg.index_topk
    sure = agreed = 0
    for t in range(8):
        got = bits_to_sets(chosen[t][:, 0], T)  # [full layers, T]
        for layer in range(got.shape[0]):
            s = np.sort(want["index_scores"][layer, n + t, :n + t + 1])
            if s[-k] - s[-k - 1] > 1e-4:
                sure += 1
                agreed += (got[layer] == want["selected"][layer, n + t]).all()
        # selected and visible, summed over the three full layers
        assert 3 * k <= keys[1 + t][0, 0] <= 3 * k + 8
        assert keys[1 + t][0, 1] == 3 * (n + t + 1)
        assert tuple(keys[1 + t][2]) == (0, 0)  # the padding row
    assert sure >= 20 and agreed == sure
    # the prefill's own count, and its sampled rows past index_topk
    assert keys[0][0, 1] == 3 * n * (n + 1) // 2
    assert keys[0][0, 0] == want["selected"][:, :n].sum()
    at = np.asarray(aux["selected_at"])[0]
    assert ((at >= k) & (at < n)).all()
    got = bits_to_sets(np.asarray(aux["selected"])[:, 0], 48)[..., :T]
    assert (got == want["selected"][:, at]).all()
    assert (np.asarray(aux["selected_at"])[2] == -1).all()


def test_the_ring_holds_the_latest_window(toy, served):
    """A sliding layer's cache after a prompt of 40: slot ``p mod 5`` holds
    position ``p`` for the 5 latest positions, nothing else."""
    hf, state, cfg, params = toy
    (row,) = prompts(5, (40,))
    ids, lengths = padded([row], 48)
    cache, _, _ = M.prefill(cfg, params, ids, lengths, 64)
    whole, _, _ = M.prefill(
        cfg, params, *padded([row], 40), 64)
    assert [w.shape for w in cache["window"]] == [(1, 5, 40)] * 2
    # the same ring whatever the bucket, and not zero
    for a, b in zip(cache["window"], whole["window"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        assert np.abs(np.asarray(a)).min(-1).max() > 0
    short, _, _ = M.prefill(cfg, params, *padded([row[:3]], 48), 64)
    ring = np.asarray(short["window"][0])[0]
    assert (np.abs(ring[:3]).sum(-1) > 0).all() and (ring[3:] == 0).all()
    assert M.CachedModel(cfg).cache_bytes(cache) == {
        "latent": 3 * 64 * (16 + 8) * 4, "index": 3 * 64 * 16 * 4,
        "window": 2 * 5 * (32 + 8) * 4}


def test_padding_is_never_seen(toy):
    hf, state, cfg, params = toy
    (row,) = prompts(9, (21,))
    a = M.prefill(cfg, params, *padded([row], 24), 32)[1]
    b = M.prefill(cfg, params, *padded([row], 48, pad=77), 64)[1]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("rows, group, real", ROW_GROUPS)
def test_a_prefill_in_groups_equals_its_rows_one_at_a_time(toy, rows, group,
                                                           real):
    """``test_lfm2_moe``'s test of the same name on this decoder: what a
    leading batch axis could get wrong here is the per-row selection, the
    ring's gather at each row's own last position and the sampled rows of
    the selection.  Rows past ``index_topk`` (8) and past the window (5),
    padding rows inside a group and a whole group of them."""
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

    def close(got, want):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)

    close(logits[:real], rows_of(lambda a: a[3])[:real])
    for kind, at in (("latent", 0), ("index", 1), ("window", 2)):
        for i, leaf in enumerate(cache[kind]):
            close(leaf, rows_of(lambda a: a[at][i]))
    lens = np.asarray(lengths)
    experts, want = np.asarray(aux["experts"]), rows_of(lambda a: a[4], 1)
    for b in range(rows):
        assert (experts[:, b, :lens[b]] == want[:, b, :lens[b]]).all()
    np.testing.assert_array_equal(np.asarray(aux["keys"]),
                                  rows_of(lambda a: a[6]))
    np.testing.assert_array_equal(np.asarray(aux["selected"]),
                                  rows_of(lambda a: a[7], 1))
    np.testing.assert_array_equal(np.asarray(aux["selected_at"]),
                                  rows_of(lambda a: a[8]))
    np.testing.assert_array_equal(
        np.asarray(aux["load"])[:, :3],
        touched_by_group(experts, lens, group, EXPERTS))


def test_kth_largest_is_exact():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((6, 300)).astype(np.float32)
    x[0, :50] = 0.0
    x[1, ::3] = -0.0
    x[2] = np.abs(x[2])
    x[3] = -np.abs(x[3])
    visible = rng.random((6, 300)) < 0.8
    visible[5, 7:] = False  # fewer visible than k: all of them
    for k in (1, 8, 120):
        got = np.asarray(M.select_keys(jnp.asarray(x), jnp.asarray(visible),
                                       k))
        for r in range(6):
            seen = np.sort(x[r][visible[r]])
            kth = seen[-k] if len(seen) >= k else -np.inf
            assert (got[r] == (visible[r] & (x[r] >= kth))).all(), (k, r)


# -- the expert layer: one router, and a chip's share ------------------------------


def test_the_router_is_the_one_lfm2_calls(toy):
    _, _, cfg, params = toy
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((9, 64)),
                    jnp.float32)
    top_e, w = M.route(cfg, p, x)
    other = lfm2_moe.route(
        lfm2_moe.Lfm2MoeConfig(num_experts=16, num_experts_per_tok=2), p, x)
    assert (np.asarray(top_e) == np.asarray(other[0])).all()
    np.testing.assert_allclose(np.asarray(w), np.asarray(other[1]),
                               rtol=1e-5)  # 1e-20 against 1e-6 under the sum
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("impl", ["ragged_dot", "megablox"])
def test_the_shares_add_up_to_the_uncut_layer(impl, monkeypatch):
    """The routed parts of the shares of all four chips, plus the shared
    expert counted ONCE, are the uncut reference layer."""
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
    total = expert_layer.swiglu(cfg, p["shared"], x)
    for first in range(0, 16, 4):
        part = dict(p, gate_up=p["gate_up"][first:first + 4],
                    down=p["down"][first:first + 4])
        top_e, top_w = M.route(cfg, p, x)
        y, load = expert_layer.routed_experts(part, x, valid, top_e, top_w,
                                          (first, 4), cfg.dtype)
        total = total + y
        routed, _, _ = ref.moe(hf, {**w, **{
            k: w[k][first:first + 4] for k in ("gate", "up", "down")}}, x,
            (first, 4), shared=False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(routed),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_a_share_of_the_model_equals_the_references_share(toy):
    """The whole model holding the second half of the experts and of the
    vocabulary against the reference given the same shares; and it is not
    the uncut model."""
    hf, state, cfg, params = toy
    (row,) = prompts(4, (19,))
    logits = M.prefill(cfg, params, *padded([row], 24), 32)[1]
    want = reference(hf, state, row, [18])
    np.testing.assert_allclose(np.asarray(logits), want["logits"], atol=ATOL)
    assert logits.shape == (1, 256)


def test_params_hold_only_what_is_held(tmp_path):
    """A checkpoint on disk with the WHOLE vocabulary and only the held
    experts' files: the loader reads rows 256-511 and experts 8-15, by
    slice, and nothing else of either."""
    config = dict(CONFIG, model=dict(MODEL))
    dirs = family.write_checkpoints(str(tmp_path), config, 11)
    with open(os.path.join(dirs["jailbreak"], "config.json")) as f:
        hf = json.load(f)
    assert hf["n_routed_experts"] == 16 and hf["vocab_size"] == 512
    cfg = M.Dots3NoteConfig.from_hf(hf, experts_held=EXPERTS,
                                    vocab_held=VOCAB)
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
    assert params["layers"][1]["expert_bias"].dtype == jnp.float32


# -- the configuration ---------------------------------------------------------------


def test_every_model_number_comes_from_the_checkpoints_config():
    hf = family.published_model(CONFIG)
    cfg = M.Dots3NoteConfig.from_hf(hf)
    for key, value in hf.items():
        if hasattr(cfg, key) and key != "layer_types":
            assert getattr(cfg, key) == value, key
    assert cfg.layer_types == tuple(MODEL["layer_types"])
    assert cfg.dtype == jnp.float32 and cfg.held == (0, 16)
    assert cfg.vocab == (0, 512) and cfg.full_layers == (0, 1, 4)
    g = cfg.geometry("sliding_attention")
    assert (g.heads, g.r_kv, g.nope, g.theta) == (2, 32, 12, 50000.0)
    real = M.Dots3NoteConfig()
    assert (real.geometry("full_attention").nope, real.index_topk,
            real.sliding_window_size) == (128, 2048, 513)


@pytest.mark.parametrize("changes, says", [
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"attention_gate_type": "elementwise"}, "headwise"),
    ({"swa_attention_gate_type": "none"}, "headwise"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"n_group": 8}, "n_group"),
    ({"scoring_func": "softmax"}, "sigmoid"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"layer_types": ["full_attention"] * 4}, "layer_types"),
    ({"layer_types": ["linear_attention"] * 5}, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 5}, "full_attention layer"),
    ({"hidden_act": "gelu"}, "silu")])
def test_what_the_architecture_cannot_express_is_refused(changes, says):
    with pytest.raises(ValueError, match=says):
        M.Dots3NoteConfig.from_hf(dict(family.published_model(CONFIG),
                                       **changes))


def test_an_unknown_model_type_is_refused_and_the_served_list_themselves():
    from semantic_router_tpu.runtime import bootstrap

    with pytest.raises(ValueError, match=r"'mamba2'.*qwen3, dots3_note"):
        bootstrap.build_generator({}, dict(MODEL, model_type="mamba2"), "",
                                  None, lambda path: {})
    assert bootstrap.GENERATIVE_MODEL_TYPES == tuple(bootstrap.GENERATORS)
    for name in bootstrap.GENERATORS:
        assert f"``{name}``:" in bootstrap.build_generator.__doc__


# -- the one token-at-a-time loop ---------------------------------------------------


def generator(toy, **kw) -> GreedyGenerator:
    _, _, cfg, params = toy
    return GreedyGenerator(cfg, params, WordTokenizer(),
                           model=M.CachedModel(cfg), gen_length=6,
                           top_logits=4, **kw)


def test_the_loop_serves_the_decoder_with_its_trajectory(toy):
    hf, state, cfg, _ = toy
    rows = prompts(8, (30, 12))
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
        want = reference(hf, state, ids, at, select_rows=at[1:])
        for f, e in enumerate(traj):
            z = want["logits"][f]
            assert int(z.argmax()) == e["token"] \
                or z.max() - z[e["token"]] < 1e-3
            np.testing.assert_allclose(
                e["top_logits"], z[e["top_ids"]], atol=ATOL)
            if f:
                assert e["experts"].shape == (4, 1, 2)
                got = bits_to_sets(e["selected"], len(ids))
                assert got.shape[0] == 3 and got[:, at[f] + 1:].sum() == 0
                assert (got.sum(-1) >= min(cfg.index_topk, at[f] + 1)).all()
        assert traj[0]["experts"].shape == (4, n, 2)
        assert traj[0]["selected"].shape[:2] == (3, M.SELECT_SAMPLE)
        assert traj[0]["selected_at"].shape == (M.SELECT_SAMPLE,)


def test_two_rows_a_group_end_at_their_own_lengths_in_the_kernel(
        toy, monkeypatch):
    """Both cores hand the rows' lengths to the flash kernel (here the
    kernel itself, interpreted, at blocks of 128 so that a bucket of 384 is
    three): two rows of one group — the kernel finds a row's length, and
    the selection's block, at ``bh // H`` — each stop at their own end and
    still give the reference's logits; what the kernel folded and what the
    bucket's grid folds without the lengths reach the observer and the
    marker."""
    import contextlib
    import unittest.mock as mock

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
    assert [c["window"] for c in calls] == [0, 0, 8, 8, 0]
    assert ["select" in c for c in calls] == [True, True, False, False,
                                              True]
    for row, res in zip(rows, out):
        e = res.trajectory[0]
        z = reference(hf, state, row, [len(row) - 1])["logits"][0]
        assert int(z.argmax()) == e["token"] \
            or z.max() - z[e["token"]] < 1e-3
        np.testing.assert_allclose(e["top_logits"], z[e["top_ids"]],
                                   atol=ATOL)
    # whole and causal as lfm2's; under the window of 5 a query block of
    # 128 folds its own K block and, past the first, the one before
    assert fa.tiles_for(384, 0, True, [130, 300]) == (9, 12)
    assert fa.tiles_for(384, 8, True, [130, 300]) == (3 + 5, 2 * 5)
    prefill, loop = seen.closed
    tiles = (3 * 4 * 9 + 2 * 2 * 8, 3 * 4 * 12 + 2 * 2 * 10)
    assert prefill["attn_tiles"] == tiles and "attn_tiles" not in loop
    facts = {}

    def span(name, **kw):
        facts.update(kw)
        return contextlib.nullcontext()

    with mock.patch.object(batchtrace, "trace_span", span):
        batchtrace.gen_forward("gen:t", "gen.prefill", prefill["load"],
                               prefill["keys"],
                               attn_tiles=prefill["attn_tiles"])
    assert (facts["attn_tiles_visited"], facts["attn_tiles_grid"]) == tiles


def test_a_program_reports_its_keys_to_the_observer(toy):
    """``keys [rows, 2]`` reaches ``done`` with the prefill, and with the
    loop its steps' stacked (``[steps x rows, 2]``); the marker sums them
    (``batchtrace.gen_forward``)."""
    from semantic_router_tpu.observability import batchtrace

    seen = per_step_loop.Steps()
    (row,) = prompts(2, (20,))
    generator(toy).generate([words(row)], 4, observer=seen)
    prefill, loop = seen.closed
    assert prefill["keys"].shape == (1, 2)
    assert prefill["keys"][0, 1] == 3 * 20 * 21 // 2
    assert set(prefill["cache_bytes"]) == {"latent", "index", "window"}
    # three steps of one row: 21, 22 and 23 keys visible in 3 full layers
    assert loop["forwards"] == 3 and loop["keys"].shape == (3, 2)
    assert list(loop["keys"][:, 1]) == [3 * 21, 3 * 22, 3 * 23]
    assert loop["load"].shape == (3 * 4, 4)
    facts = {}

    def span(name, **kw):
        import contextlib
        facts.update(kw, name=name)
        return contextlib.nullcontext()

    import unittest.mock as mock
    with mock.patch.object(batchtrace, "trace_span", span):
        batchtrace.gen_forward("gen:t", "gen.decode", loop["load"],
                               loop["keys"], forwards=loop["forwards"])
    assert facts["keys_visible"] == 63 + 66 + 69
    assert facts["keys_selected"] >= 3 * 24
    assert facts["forwards"] == 3 and facts["layers"] == 12
    assert facts["name"] == batchtrace.GEN_FORWARD_ANNOTATION
    with mock.patch.object(batchtrace, "trace_span", span):
        facts.clear()
        batchtrace.gen_forward("gen:t", "gen.decode", loop["load"][:4])
    assert "keys_visible" not in facts and facts["layers"] == 4


@pytest.fixture(scope="module")
def looped(toy):
    return generator(toy)


@pytest.mark.parametrize("case", per_step_loop.CASES)
def test_the_loop_gives_what_the_hosts_loop_gave(looped, case):
    """The decode loop on the device against a program a step
    (``tests/per_step_loop.py``) over the latent cache: latents, index
    keys and the sliding layers' rings carried through the loop, and a
    step's ``selected`` bits in the loop's buffers."""
    texts = [words(r) for r in prompts(33, (30, 12, 21))]
    seen = per_step_loop.check_case(case, looped, texts, 7)
    for res in seen["out"]:
        for e in res.trajectory[1:]:
            assert e["experts"].shape == (4, 1, 2)
            assert e["selected"].shape[0] == 3 and "selected_at" not in e
    if seen["done"] is not None:
        steps = len(seen["steps"])
        assert seen["done"]["load"].shape == (4 * steps, 4)
        rows = 3 + (case == "a_padding_row")
        assert seen["done"]["keys"].shape == (rows * steps, 2)


@pytest.fixture
def engine(tmp_path):
    """A toy ``dots3_note`` checkpoint on disk, loaded the way
    ``build_engine`` loads a ``kind: generative`` task holding its share."""
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
    assert gen.config.held == EXPERTS and gen.config.vocab == VOCAB
    assert gen.params["embed"].shape == (256, 64)
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=50.0, seq_len_buckets=[64]))
    eng.register_generative("guard", gen)
    yield eng
    eng.shutdown()


def test_guard_classify_goes_through_the_batcher(engine):
    rows = prompts(12, (33, 9, 21))
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(3) as pool:
        together = list(pool.map(
            lambda r: engine.generate("guard", [words(r)], 6)[0], rows))
    alone = [engine.generate("guard", [words(r)], 6)[0] for r in rows]
    for a, b in zip(together, alone):
        assert a.token_ids == b.token_ids
        for x, y in zip(a.trajectory, b.trajectory):
            np.testing.assert_allclose(x["top_logits"], y["top_logits"],
                                       atol=ATOL)
    verdict = engine.guard_classify("guard", words(rows[0]))
    assert verdict.safety in ("Safe", "Unsafe", "Controversial")
    stats = engine._runtime_stats
    stats.flush()
    text = "\n".join(str(s) for s in stats.gen_cache_bytes.collect()) \
        if hasattr(stats.gen_cache_bytes, "collect") else ""
    for kind in ("latent", "index", "window"):
        assert kind in text or not text


# -- the kernel's new arguments (interpret mode) -------------------------------------


@pytest.mark.parametrize("case", ["v_head_size", "causal_window", "select"])
def test_the_flash_kernel_takes_what_latent_attention_needs(case):
    from semantic_router_tpu.ops.attention import NEG_INF, sdpa
    from semantic_router_tpu.ops.flash_attention import (
        flash_attention, flash_attention_pallas)

    rng = np.random.default_rng(3)
    B, H, S, D, Dv = 2, 2, 256, 24, 16
    q, k = (jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((B, H, S, Dv)), jnp.float32)
    mask = jnp.asarray(np.arange(S)[None] < np.array([[S], [200]]),
                       jnp.int32)
    at = np.arange(S)
    seen = at[None, :] <= at[:, None]
    kw = {}
    if case == "causal_window":  # 33 keys with the token itself
        kw["window"] = 64
        seen = seen & (at[:, None] - at[None, :] < 33)
    seen = np.broadcast_to(seen, (B, S, S)).copy()
    if case == "select":
        chosen = rng.random((B, S, S)) < 0.3
        chosen[:, at, at] = True
        kw["select"] = jnp.asarray(chosen, jnp.int8)
        seen &= chosen
    seen &= np.asarray(mask, bool)[:, None, :]
    want = sdpa(q, k, v, bias=jnp.where(seen, 0.0, NEG_INF)[:, None])
    for blocks in ({}, {"block_q": 128, "block_k": 128}):
        got = flash_attention_pallas(q, k, v, mask, causal=True,
                                     interpret=True, **blocks, **kw)
        assert got.shape == (B, H, S, Dv)
        rows = np.asarray(mask, bool)  # padding queries see nothing real
        np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0],
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(got)[1][:, rows[1]],
                                   np.asarray(want)[1][:, rows[1]],
                                   atol=2e-5)
    plain = flash_attention(q, k, v, mask, causal=True, **kw)  # the CPU path
    np.testing.assert_allclose(np.asarray(plain)[0], np.asarray(want)[0],
                               atol=2e-5)
    if case == "select":
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, mask, select=kw["select"])
