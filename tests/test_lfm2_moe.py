"""The lfm2_moe family at a toy size on the CPU (hidden 64, 5 layers: conv
with a dense MLP, then attention, conv, conv, attention with 8 experts
top-2 behind the sigmoid router): the program's layers, prefill and decode
through the hybrid cache against the plain reference
(``chipbench/reference/lfm2_moe.py``) on seeded float32 weights, the
router's bias, the expert-parallel share under both routers, and the one
token-at-a-time loop through the engine's batcher.

Tolerances: both sides compute in float32 here and differ only in the order
of their sums, so logits of size 1-15 agree to 2e-4; bfloat16 would miss
that by two orders of magnitude, which ``chipbench``'s limits hold on the
chip."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import per_step_loop
from chipbench import cells
from semantic_router_tpu.models import experts as expert_layer
from semantic_router_tpu.models import lfm2_moe as M
from semantic_router_tpu.models import mapped_prefill, sdar_moe
from semantic_router_tpu.models.generate import GreedyGenerator
from semantic_router_tpu.utils.tokenization import Encoding

MODEL = {
    "model_type": "lfm2_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 512, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "tie_word_embeddings": True, "torch_dtype": "float32"}
CONFIG = {
    "family": "hybrid_ar_guard", "model": MODEL,
    "weights": {"std": 0.08, "embed_std": 0.3, "conv_std": 0.5,
                "qk_norm": 1.5, "router_std": 0.3,
                "router_row_log_std": 0.3, "expert_bias_std": 0.1,
                "writer_threads": 2},
    "tasks": {"jailbreak": {"kind": "generative"}},
    "route_margin": 0.01, "route_sample": 8}
ATOL = 2e-4

family = cells.load_family(CONFIG)
ref = cells.load_module("reference", "lfm2_moe")


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
    cfg = M.Lfm2MoeConfig.from_hf(model)
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


# -- the layers, one kind at a time -----------------------------------------------


@pytest.mark.parametrize("kind, dense", [
    ("conv", 1), ("conv", 0), ("full_attention", 1), ("full_attention", 0)],
    ids=["conv+mlp", "conv+moe", "attn+mlp", "attn+moe"])
def test_one_layer_of_each_kind_equals_the_reference(kind, dense):
    """Two layers, the second always sparse (the reference stacks what the
    expert layers chose), the first of the kind under test."""
    model, state, cfg, params = variant(
        num_hidden_layers=2, num_dense_layers=dense,
        layer_types=[kind, "conv"])
    (row,) = prompts(3, (11,))
    ids, lengths = padded([row], 11)
    _, logits, aux = M.prefill(cfg, params, ids, lengths, 16)
    want = ref.forward(model, state, row, [10])
    np.testing.assert_allclose(np.asarray(logits), want["logits"], atol=ATOL)
    assert (np.sort(np.asarray(aux["experts"])[:, 0], -1)
            == np.sort(want["top_e"], -1)).all()
    assert aux["experts"].shape[0] == 2 - dense


def test_the_full_forward_equals_the_reference_at_every_position(toy):
    """Every prefix of one prompt: the prefill's last-position logits are
    the reference's full forward at that position."""
    model, state, cfg, params = toy
    (row,) = prompts(4, (12,))
    want = ref.forward(model, state, row)
    fn = jax.jit(lambda ids, n: M.prefill(cfg, params, ids, n, 16)[1])
    ids, _ = padded([row], 12)
    for n in range(1, 13):
        got = fn(ids, jnp.asarray([n], jnp.int32))
        np.testing.assert_allclose(np.asarray(got)[0], want["logits"][n - 1],
                                   atol=ATOL)


# -- prefill, then decoding through the hybrid cache --------------------------------


def _decode_greedily(cfg, params, rows, bucket, steps, pad=0, extra_rows=0):
    """Prefill + ``steps`` decode forwards on rows of their own lengths
    (+ ``extra_rows`` padding rows): per row the tokens chosen and the
    logits that chose them, and the prefill's aux."""
    rows = list(rows) + [[]] * extra_rows
    ids, lengths = padded(rows, bucket, pad)
    cache, logits, aux = M.prefill(cfg, params, ids, lengths,
                                   bucket + steps + 1)
    step = jax.jit(lambda c, t, p: M.decode(cfg, params, c, t, p))
    all_logits, tokens, positions = [np.asarray(logits)], [], lengths
    for _ in range(steps):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        tokens.append(np.asarray(tok))
        cache, logits, _ = step(cache, tok, positions)
        all_logits.append(np.asarray(logits))
        positions = positions + 1
    return np.stack(tokens, 1), np.stack(all_logits, 1), aux, cache


def test_prefill_then_decode_equal_the_full_forward(toy):
    """Rows of DIFFERENT lengths and a padding row in one batch: every
    row's logits, step by step, are the reference's one causal forward over
    its prompt and its served tokens — the conv state was taken at each
    row's true last token, every K and V stands at its own position."""
    model, state, cfg, params = toy
    rows = prompts(5, (5, 14, 9))
    tokens, logits, aux, cache = _decode_greedily(cfg, params, rows, 16, 6,
                                                  extra_rows=1)
    for i, row in enumerate(rows):
        n = len(row)
        want = ref.forward(model, state, np.concatenate([row, tokens[i]]),
                           list(range(n - 1, n + 6)))
        np.testing.assert_allclose(logits[i], want["logits"], atol=ATOL)
        assert (np.sort(np.asarray(aux["experts"])[:, i, :n], -1)
                == np.sort(want["top_e"][:, :n], -1)).all()
    # two kinds of state side by side: 2 attention layers of K and V,
    # 3 conv layers of 2 vectors a row
    assert M.CachedModel(cfg).cache_bytes(cache) == {
        "kv": 2 * 2 * 4 * 2 * 23 * 16 * 4, "conv": 3 * 4 * 2 * 64 * 4}


def test_padding_is_never_seen(toy):
    _, _, cfg, params = toy
    rows = prompts(6, (4, 13))
    a = _decode_greedily(cfg, params, rows, 16, 3, pad=0)
    b = _decode_greedily(cfg, params, rows, 16, 3, pad=77, extra_rows=2)
    assert (a[0] == b[0][:2]).all()
    np.testing.assert_allclose(a[1], b[1][:2], atol=1e-5)
    # a padding row routes nowhere: the pairs are the real tokens' alone
    assert float(np.asarray(b[2]["load"])[0, 1]) == (4 + 13) * 2


def test_a_batch_equals_its_rows_one_at_a_time(toy):
    _, _, cfg, params = toy
    rows = prompts(7, (6, 15, 10))
    together = _decode_greedily(cfg, params, rows, 16, 4)
    for i, row in enumerate(rows):
        alone = _decode_greedily(cfg, params, [row], 16, 4)
        assert (alone[0][0] == together[0][i]).all()
        np.testing.assert_allclose(alone[1][0], together[1][i], atol=ATOL)


# -- a prefill's rows in groups ---------------------------------------------------

V5E_BYTES = 16_909_336_064  # a v5e's memory_stats()["bytes_limit"]

# (rows, rows a group, real rows): the padding rows (length 0) come last, so
# (8, 2, 5) has one inside a group and a whole group of them, (8, 4, 3) too
ROW_GROUPS = [(1, 1, 1), (2, 1, 2), (2, 2, 1), (4, 1, 3), (4, 2, 3),
              (4, 4, 3), (8, 1, 8), (8, 2, 5), (8, 4, 3), (8, 4, 8),
              (8, 2, 8)]


def touched_by_group(experts, lengths, group: int, held=None):
    """From the router's choices ``experts [layers, B, S, k]``: per expert
    layer ``(the busiest held expert's pairs in any group, the pairs, the
    held experts with a pair summed over the GROUPS of rows)`` — what
    ``load`` must say when each group is one grouped matmul."""
    layers, B, S, _ = experts.shape
    out = []
    for layer in range(layers):
        busiest = pairs = touched = 0
        for g in range(0, B, group):
            chosen = np.concatenate(
                [experts[layer, b, :lengths[b]].reshape(-1)
                 for b in range(g, g + group)])
            if held is not None:
                chosen = chosen[(chosen >= held[0])
                                & (chosen < held[0] + held[1])]
            counts = np.bincount(chosen.astype(np.int64), minlength=1)
            busiest = max(busiest, int(counts.max()))
            pairs += int(counts.sum())
            touched += int((counts > 0).sum())
        out.append((busiest, pairs, touched))
    return np.asarray(out, np.float32)


@pytest.mark.parametrize("rows, group, real", ROW_GROUPS)
def test_a_prefill_in_groups_equals_its_rows_one_at_a_time(toy, rows, group,
                                                           real):
    """``group`` rows through the layers together (ONE grouped matmul an
    expert layer) against ``_prefill_rows`` called a row at a time: the
    router's choices exactly, every float (cache leaves, last-token
    logits) to 1e-5 (the CPU's matmuls sum a batch of ``group`` x 16 tokens
    in another order than one of 16: up to 3e-6 at logits of size 5, a few
    dozen units of float32's last place), ``load`` the sum over the
    groups."""
    _, _, cfg, params = toy
    lens = [5, 14, 9, 16, 3, 11, 7, 12][:real] + [0] * (rows - real)
    ids, lengths = padded(prompts(21, lens), 16)
    cache, logits, aux = jax.jit(
        lambda p, i, n: M._prefill_groups(cfg, p, i, n, 24, group))(
            params, ids, lengths)
    one = jax.jit(lambda p, i, n: M._prefill_rows(cfg, p, i, n, 24))
    alone = [one(params, ids[b:b + 1], lengths[b:b + 1])
             for b in range(rows)]

    def rows_of(leaf):  # leaf(a row's outputs) [1, ...] -> [rows, ...]
        return np.concatenate([np.asarray(leaf(a)) for a in alone])

    close = functools.partial(np.testing.assert_allclose, rtol=1e-5,
                              atol=1e-5)
    close(np.asarray(logits)[:real], rows_of(lambda a: a[2])[:real])
    for i, (k, v) in enumerate(cache["kv"]):
        close(np.asarray(k), rows_of(lambda a: a[0][i][0]))
        close(np.asarray(v), rows_of(lambda a: a[0][i][1]))
    for i, c in enumerate(cache["conv"]):
        close(np.asarray(c), rows_of(lambda a: a[1][i]))
        assert (np.asarray(c)[real:] == 0).all()
    experts = np.asarray(aux["experts"])
    want = np.concatenate([np.asarray(a[3]) for a in alone], 1)
    lens = np.asarray(lengths)
    for b in range(rows):
        assert (experts[:, b, :lens[b]] == want[:, b, :lens[b]]).all()
    assert experts.shape == (4, rows, 16, 2)
    np.testing.assert_array_equal(
        np.asarray(aux["load"])[:, :3],
        touched_by_group(experts, lens, group))


@pytest.mark.parametrize("name, rows, bucket, limit, want", [
    # the lfm2_moe cell: 10.36 GB of weights, 0.84 GB a row reckoned, 34 MB
    # of activations a row: memory would hold 5 rows, the core's own 2
    ("lfm2-24b-a2b-guard", 8, 8192, V5E_BYTES, 2),
    ("lfm2-24b-a2b-guard", 4, 8192, V5E_BYTES, 2),
    ("lfm2-24b-a2b-guard", 2, 8192, V5E_BYTES, 2),
    ("lfm2-24b-a2b-guard", 1, 8192, V5E_BYTES, 1),
    # a device that holds the weights and not one more row
    ("lfm2-24b-a2b-guard", 8, 8192, 12e9, 1),
    # the dots3_note cell: a row's attention arrays leave no room, and
    # its activations are 84 MB a row
    ("dots3-note-guard", 8, 8192, V5E_BYTES, 1),
    ("dots3-note-guard", 2, 8192, V5E_BYTES, 1),
    ("dots3-note-guard", 1, 8192, V5E_BYTES, 1),
    ("dots3-note-guard", 8, 8192, 4 * V5E_BYTES, 1),
    # the laguna cell's two buckets: 11.14 GB of weights and 2.6 GB a long
    # row reckoned leave room for one; a short row's activations are 3 MB
    ("laguna-s21-guard", 8, 8192, V5E_BYTES, 1),
    ("laguna-s21-guard", 8, 512, V5E_BYTES, 8),
    ("laguna-s21-guard", 3, 512, V5E_BYTES, 3),
    # no limit known (the CPU): every row
    ("lfm2-24b-a2b-guard", 3, 8192, None, 3),
    ("dots3-note-guard", 8, 8192, None, 8),
    ("laguna-s21-guard", 8, 8192, None, 8),
])
def test_the_rows_a_group_follow_from_shapes_and_memory(monkeypatch, name,
                                                        rows, bucket, limit,
                                                        want):
    """``mapped_prefill.rows_per_group``'s table at the cells' published
    widths (cache = the bucket and 64) on a v5e's memory, each model's own
    ``_row_bytes`` and ``_cache_bytes`` beside its cell's weights."""
    from semantic_router_tpu.models import dots3_note, laguna

    with open(os.path.join(os.path.dirname(cells.__file__), "configs", name,
                           "model.json")) as f:
        hf = json.load(f)
    if name.startswith("lfm2"):
        module, weights = M, 10.356e9
        cfg = M.Lfm2MoeConfig.from_hf(hf)
    elif name.startswith("dots3"):
        module, weights = dots3_note, 10.022e9
        cfg = dots3_note.Dots3NoteConfig.from_hf(
            hf, experts_held=(0, 32), vocab_held=(0, 19008))
    else:
        module, weights = laguna, 11.144e9
        cfg = laguna.LagunaConfig.from_hf(
            hf, experts_held=(0, 128), vocab_held=(0, 50176))
    monkeypatch.setattr(mapped_prefill, "device_bytes", lambda: limit)
    assert mapped_prefill.rows_per_group(
        rows, module._row_bytes(cfg, bucket),
        weights + module._cache_bytes(cfg, rows, bucket + 64),
        bucket * cfg.hidden_size * 2) == want


# -- the router --------------------------------------------------------------------


def _route_inputs(toy, n=64):
    _, state, cfg, params = toy
    x = jnp.asarray(np.random.default_rng(8).standard_normal((n, 64)),
                    jnp.float32)
    return state, cfg, params["layers"][1], x


def test_the_router_equals_the_reference(toy):
    state, cfg, p, x = _route_inputs(toy)
    top_e, w = M.route(cfg, p, x)
    _, want_e, want_w = ref.route(
        MODEL, ref.layer_weights(MODEL, state, 1, "highest")["ff"], x)
    assert (np.asarray(top_e) == np.asarray(want_e)).all()
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), atol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weights(toy):
    _, cfg, p, x = _route_inputs(toy)
    top_e, w = M.route(cfg, p, x)
    bare_e, _ = M.route(dataclasses.replace(cfg, use_expert_bias=False),
                        p, x)
    assert (np.sort(np.asarray(top_e)) != np.sort(np.asarray(bare_e))).any()
    # a huge bias on one expert: always chosen, and its weight is still
    # its own UNBIASED sigmoid over the chosen two's sum
    forced = dict(p, expert_bias=p["expert_bias"].at[3].add(100.0))
    e3, w3 = M.route(cfg, forced, x)
    assert (np.asarray(e3)[:, 0] == 3).all()
    s = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    chosen = np.take_along_axis(s, np.asarray(e3), -1)
    np.testing.assert_allclose(
        np.asarray(w3), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
        atol=1e-6)


def test_norm_topk_prob_and_its_epsilon_and_the_scaling_factor(toy):
    _, cfg, p, x = _route_inputs(toy)
    e, w = M.route(cfg, p, x)
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(x @ p["router"])),
                           np.asarray(e), -1)
    total = np.asarray(w).sum(-1)
    np.testing.assert_allclose(total, s.sum(-1) / (s.sum(-1) + 1e-6),
                               rtol=1e-6)
    assert (total < 1.0).all()  # the 1e-6 is there
    _, raw = M.route(dataclasses.replace(cfg, norm_topk_prob=False,
                                         routed_scaling_factor=2.5), p, x)
    np.testing.assert_allclose(np.asarray(raw), 2.5 * s, rtol=1e-6)


@pytest.mark.parametrize("impl", ["ragged_dot", "megablox"])
@pytest.mark.parametrize("router", ["softmax", "sigmoid_and_bias"])
def test_the_shares_add_up_to_the_uncut_layer(toy, router, impl, monkeypatch):
    """Four chips of two experts each, through the ONE expert layer both
    decoders call: every share routes over all eight experts (by either
    router) and computes its own; the four partial results add up to the
    uncut layer's, in the program and in the reference alike."""
    if impl == "megablox":
        monkeypatch.setattr(expert_layer, "_grouped_matmul",
                            expert_layer._megablox)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((128, 64)),
                    jnp.float32)
    valid = jnp.ones(128, bool)
    if router == "softmax":
        from tests import test_sdar_moe as T

        state = T.family.generate_state(T.CONFIG, 7)
        cfg = sdar_moe.SdarMoeConfig.from_hf(T.MODEL)
        p = sdar_moe.params_from_state(state.__getitem__, cfg)["layers"][1]
        moe = sdar_moe.moe
        w = T.ref.layer_weights(T.MODEL, state, 1, "highest")["moe"]
        ref_moe = lambda held: T.ref.moe(T.MODEL, w, x, experts_held=held)
    else:
        _, state, cfg, params = toy
        p, moe = params["layers"][1], M.moe
        w = ref.layer_weights(MODEL, state, 1, "highest")["ff"]
        ref_moe = lambda held: ref.moe(MODEL, w, x, experts_held=held)
    whole, _, _ = moe(cfg, p, x, valid)
    total, ref_total = 0.0, 0.0
    for first in (0, 2, 4, 6):
        held = dataclasses.replace(cfg, experts_held=(first, 2))
        share = dict(p, gate_up=p["gate_up"][first:first + 2],
                     down=p["down"][first:first + 2])
        part, top_e, load = moe(held, share, x, valid)
        ref_part = ref_moe((first, 2))[0]
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part),
                                   atol=ATOL)
        assert float(load[1]) == ((np.asarray(top_e) >= first)
                                  & (np.asarray(top_e) < first + 2)).sum()
        total, ref_total = total + part, ref_total + ref_part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=ATOL)
    np.testing.assert_allclose(np.asarray(ref_total),
                               np.asarray(ref_moe(None)[0]), atol=ATOL)


def test_params_hold_only_the_experts_held(toy):
    _, state, cfg, _ = toy
    held = dataclasses.replace(cfg, experts_held=(4, 2))
    read = []

    def get(name):
        read.append(name)
        return state[name]

    params = M.params_from_state(get, held)
    assert params["layers"][1]["gate_up"].shape == (2, 64, 64)
    assert params["layers"][0]["gate_up"].shape == (64, 192)  # the dense MLP
    experts = {n.split("experts.")[1].split(".")[0] for n in read
               if "experts." in n}
    assert experts == {"4", "5"}


# -- the checkpoint's config.json ----------------------------------------------------


def test_every_model_number_comes_from_the_checkpoints_config():
    cfg = M.Lfm2MoeConfig.from_hf(dict(
        MODEL, norm_eps=3e-4, routed_scaling_factor=1.5,
        norm_topk_prob=False, use_expert_bias=False, conv_L_cache=4,
        num_dense_layers=2, torch_dtype="bfloat16",
        rope_parameters={"rope_theta": 5e5, "rope_type": "default"}),
        experts_held=(2, 4))
    assert (cfg.norm_eps, cfg.routed_scaling_factor, cfg.norm_topk_prob,
            cfg.use_expert_bias, cfg.conv_L_cache, cfg.num_dense_layers,
            cfg.rope_theta, cfg.head_dim, cfg.held) == \
        (3e-4, 1.5, False, False, 4, 2, 5e5, 16, (2, 4))
    assert cfg.dtype == jnp.bfloat16
    assert cfg.layer_types == tuple(MODEL["layer_types"])


@pytest.mark.parametrize("changes, says", [
    ({"conv_bias": True}, "conv_bias"),
    ({"rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6}}, "RoPE"),
    ({"layer_types": ["conv"] * 4}, "layer_types"),
    ({"layer_types": ["conv", "sliding_attention"] + ["conv"] * 3},
     "layer_types")])
def test_what_the_architecture_cannot_express_is_refused(changes, says):
    with pytest.raises(ValueError, match=says):
        M.Lfm2MoeConfig.from_hf(dict(MODEL, **changes))


def test_an_unknown_model_type_is_refused_by_name():
    from semantic_router_tpu.runtime.bootstrap import build_generator

    with pytest.raises(ValueError, match=r"'mamba2'.*sdar_moe, lfm2_moe, "
                                         r"qwen3"):
        build_generator({}, dict(MODEL, model_type="mamba2"), "", None,
                        lambda path: {})


# -- the one token-at-a-time loop ---------------------------------------------------


def generator(toy, **kw) -> GreedyGenerator:
    _, _, cfg, params = toy
    return GreedyGenerator(cfg, params, WordTokenizer(),
                           model=M.CachedModel(cfg), gen_length=6,
                           top_logits=4, **kw)


def test_the_loop_serves_the_hybrid_decoder_with_its_trajectory(toy):
    model, state, _, _ = toy
    rows = prompts(9, (7, 12))
    out = generator(toy).generate([words(r) for r in rows], max_new_tokens=6)
    for row, res in zip(rows, out):
        n = len(row)
        assert res.prompt_tokens == n and len(res.token_ids) == 6
        traj = res.trajectory
        assert [e["kind"] for e in traj] == ["prefill"] + ["decode"] * 5
        assert [e["position"] for e in traj] == list(range(n - 1, n + 5))
        assert [e["token"] for e in traj] == res.token_ids
        assert traj[0]["experts"].shape == (4, n, 2)
        assert traj[1]["experts"].shape == (4, 1, 2)
        want = ref.forward(model, state,
                           np.concatenate([row, res.token_ids[:-1]]),
                           [e["position"] for e in traj])
        for e, z in zip(traj, want["logits"]):
            assert e["token"] == z.argmax() == e["top_ids"][0]
            np.testing.assert_allclose(e["top_logits"],
                                       z[e["top_ids"]], atol=ATOL)
            np.testing.assert_allclose(
                e["lse"], jax.nn.logsumexp(jnp.asarray(z)), atol=ATOL)


def test_two_rows_a_group_end_at_their_own_lengths_in_the_kernel(
        toy, monkeypatch):
    """A prefill hands its rows' lengths to the flash kernel (here the
    kernel itself, interpreted, at blocks of 128 so that a bucket of 384 is
    three): two rows of one group — the kernel finds a row's length at
    ``bh // H`` — each stop at their own end and still give the
    reference's logits; what the kernel folded and what the bucket's grid
    folds without the lengths reach the observer and the marker."""
    import unittest.mock as mock

    from semantic_router_tpu.observability import batchtrace
    from semantic_router_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_platform_of", lambda x: "tpu")
    monkeypatch.setattr(fa, "GLOBAL_BLOCKS", (128, 128))
    model, state, cfg, _ = toy
    rows = prompts(31, (130, 300))
    seen = per_step_loop.Steps()
    with mock.patch.object(fa, "flash_attention_pallas",
                           wraps=fa.flash_attention_pallas) as kernel:
        out = generator(toy).generate([words(r) for r in rows], 2,
                                      bucket=384, observer=seen)
    assert kernel.call_count == 2  # the attention layers, both rows in each
    assert all(c.kwargs["lengths"].shape == (2,)
               for c in kernel.call_args_list)
    for row, res in zip(rows, out):
        e = res.trajectory[0]
        z = ref.forward(model, state, row, [len(row) - 1])["logits"][0]
        assert e["token"] == z.argmax()
        np.testing.assert_allclose(e["top_logits"], z[e["top_ids"]],
                                   atol=ATOL)
    # 130 tokens: 2 query blocks of 128, 1 + 2 folds; 300: 1 + 2 + 3; the
    # grid's 2 x 6; two attention layers of 4 heads
    assert fa.tiles_for(384, 0, True, [130, 300]) == (9, 12)
    prefill, loop = seen.closed
    assert prefill["attn_tiles"] == (8 * 9, 8 * 12)
    assert "attn_tiles" not in loop
    facts = {}

    def span(name, **kw):
        facts.update(kw)
        return contextlib.nullcontext()

    with mock.patch.object(batchtrace, "trace_span", span):
        batchtrace.gen_forward("gen:t", "gen.prefill", prefill["load"],
                               attn_tiles=prefill["attn_tiles"])
    assert (facts["attn_tiles_visited"], facts["attn_tiles_grid"]) \
        == (72, 96)


def test_the_loop_reads_back_small_reports_not_the_vocabulary(toy):
    gen = generator(toy)
    gen.generate([words(prompts(10, (5,))[0])], max_new_tokens=3)
    (key,) = gen._loop_cache
    assert key == (1, 1, 64, 2)  # rows, positions a step, cache, steps
    cfg = gen.config
    cache = jax.eval_shape(
        lambda: M.prefill(cfg, gen.params, jnp.zeros((1, 32), jnp.int32),
                          jnp.ones(1, jnp.int32), 64)[0])
    out = jax.eval_shape(
        gen._loop_cache[key], gen.params, cache, jnp.zeros(1, jnp.int32),
        jnp.zeros(1, jnp.int32), jnp.asarray(0), jnp.zeros(1, bool),
        jnp.zeros(0, jnp.int32), jnp.asarray(3))
    small = jax.tree_util.tree_leaves(out[1:])
    assert max(int(np.prod(a.shape)) for a in small) < cfg.vocab_size
    reports, aux = out[1]  # an entry a step
    assert reports.shape == (2, 1, 2 + 2 * 4)
    assert aux["experts"].shape == (2, 4, 1, 2)
    assert aux["load"].shape == (2, 4, 4)


@pytest.fixture(scope="module")
def looped(toy):
    """The toy with its head untied, the embedding's rows rolled by one:
    the seeded, tied weights name the token they read, and a row that
    repeats one token cannot end at a step of its own."""
    _, _, cfg, params = toy
    cfg = dataclasses.replace(cfg, tie_word_embeddings=False)
    params = dict(params, lm_head=jnp.roll(params["embed"], 1, axis=0))
    return GreedyGenerator(cfg, params, WordTokenizer(),
                           model=M.CachedModel(cfg), gen_length=7,
                           top_logits=4)


@pytest.mark.parametrize("case", per_step_loop.CASES)
def test_the_loop_gives_what_the_hosts_loop_gave(toy, looped, case):
    """The decode loop on the device against a program a step
    (``tests/per_step_loop.py``) over the hybrid cache: K and V columns
    and every conv layer's state carried through the loop."""
    texts = [words(r) for r in prompts(31, (7, 12, 9))]
    seen = per_step_loop.check_case(case, looped, texts, 7)
    for res in seen["out"]:
        assert all(e["experts"].shape == (4, 1, 2)
                   for e in res.trajectory[1:])
    if seen["done"] is not None:  # four expert layers a step, stacked
        assert seen["done"]["load"].shape == (4 * len(seen["steps"]), 4)
        assert seen["done"]["keys"] is None


# -- through the engine and the batcher ----------------------------------------------


@pytest.fixture()
def engine(tmp_path):
    """A toy ``lfm2_moe`` checkpoint on disk, loaded the way
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
    assert gen.config.layer_types == tuple(MODEL["layer_types"])
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=50.0, seq_len_buckets=[64]))
    eng.register_generative("guard", gen)
    yield eng
    eng.shutdown()


def test_guard_classify_goes_through_the_batcher_the_loop_a_step(
        engine, seen):
    rs = engine._runtime_stats
    before = {v: rs.gen_forwards.get(task="guard", flavour=v)
              for v in ("gen.prefill", "gen.decode")}
    tokens0 = rs.gen_tokens.get(task="guard")
    verdict = engine.guard_classify("guard", words(prompts(12, (9,))[0]))
    assert verdict.safety == "Controversial"  # seeded weights say nothing
    steps = [f for n, f in seen if n == "engine.step"]
    assert [s["flavour"] for s in steps] == ["gen.prefill", "gen.decode"]
    n_prompt = steps[0]["tokens_real"]
    assert n_prompt > 9 and steps[0]["group"] == "gen:guard"
    assert steps[0]["bucket"] * steps[0]["padded_rows"] == 64
    assert steps[1]["tokens_real"] == 1  # the live rows' of a step
    marks = [f for n, f in seen if n == "engine.gen.forward"]
    assert [m["flavour"] for m in marks] == [s["flavour"] for s in steps]
    assert marks[0]["layers"] == 4 and marks[0]["pairs"] == 4 * n_prompt * 2
    assert marks[0]["rows_per_group"] == 1 \
        and "rows_per_group" not in marks[1]
    assert rs.gen_rows_per_group.get(task="guard") == 1
    # five steps' sums: four expert layers, one row, two experts a token
    assert marks[1]["forwards"] == 5 and marks[1]["layers"] == 5 * 4
    assert marks[1]["pairs"] == 5 * 4 * 2
    assert marks[1]["experts_touched"] == 5 * 8
    assert {v: rs.gen_forwards.get(task="guard", flavour=v) - before[v]
            for v in before} == {"gen.prefill": 1, "gen.decode": 5}
    assert rs.gen_tokens.get(task="guard") - tokens0 == 6
    # the first count of two kinds of state in one cache
    M_len = 128  # 64 + 6 + 1 rounded up to 64
    assert rs.gen_cache_bytes.get(task="guard", kind="kv") \
        == 2 * 2 * 1 * 2 * M_len * 16 * 4
    assert rs.gen_cache_bytes.get(task="guard", kind="conv") \
        == 3 * 1 * 2 * 64 * 4
    rs.flush()
    fill = [p for p in rs.programs() if p["group"] == "gen:guard"
            and p["variant"] == "gen.prefill"]
    assert fill[0]["tokens_padded"] == 64


def test_a_token_at_a_time_generation_is_two_programs(engine, seen):
    """The observer, the marker and the counters are shared with the block
    generator, whose program is a block of forwards; this loop's program
    is a generation's decode steps: ONE ``gen.decode`` step, marker and
    program, whose ``forwards`` fact and forwards counter say how many
    steps the device ran."""
    rs = engine._runtime_stats
    flavours = ("gen.prefill", "gen.decode", "gen.denoise", "gen.commit")

    def counts():
        return {(c, v): getattr(rs, c).get(task="guard", flavour=v)
                for c in ("gen_forwards", "gen_programs") for v in flavours}

    before = counts()
    engine.guard_classify("guard", words(prompts(14, (7,))[0]))
    after = counts()
    step_facts = {"group", "flavour", "bucket", "rows", "padded_rows",
                  "tokens_real"}
    mark_facts = {"group", "flavour", "forwards", "layers", "pairs",
                  "experts_touched", "load_milli", "bucket"}
    steps = [f for n, f in seen if n == "engine.step"]
    assert [s["flavour"] for s in steps] == ["gen.prefill", "gen.decode"]
    assert all(set(s) == step_facts for s in steps)
    marks = [f for n, f in seen if n == "engine.gen.forward"]
    assert [m["flavour"] for m in marks] == [s["flavour"] for s in steps]
    # a prefill's also says its cache by kind of state
    assert set(marks[0]) == mark_facts | {
        "rows_per_group", "cache_bytes_kv", "cache_bytes_conv",
        "attn_tiles_visited", "attn_tiles_grid"}
    # one row of 7 tokens in a bucket of one block: 2 layers x 4 heads
    assert marks[0]["attn_tiles_visited"] == marks[0]["attn_tiles_grid"] == 8
    assert rs.gen_attn_tiles_grid.get(task="guard") \
        == rs.gen_attn_tiles_visited.get(task="guard") > 0
    assert {m["bucket"] for m in marks} == {steps[0]["bucket"]}
    assert set(marks[1]) == mark_facts
    assert [(m["forwards"], m["layers"]) for m in marks] == [(1, 4), (5, 20)]
    assert [f["after"] for n, f in seen if n == "engine.gen.turn"] == \
        [s["flavour"] for s in steps]
    (done,) = [f for n, f in seen if n == "engine.gen.done"]
    assert done["forwards"] == 6 and done["tokens"] == 6
    for counter, decode in (("gen_forwards", 5), ("gen_programs", 1)):
        assert {v: after[counter, v] - before[counter, v]
                for v in flavours} == {"gen.prefill": 1, "gen.decode": decode,
                                       "gen.denoise": 0, "gen.commit": 0}


def test_three_rows_through_the_batcher_equal_one_at_a_time(engine):
    texts = [words(p) for p in prompts(13, (5, 14, 8))]
    together = engine.generate("guard", texts, max_new_tokens=6)
    stats = engine.batcher.stats()
    assert stats["batches"] == 1 and stats["max_batch"] == 3
    gen = engine._tasks["guard"].generator
    for text, res in zip(texts, together):
        alone = gen.generate([text], max_new_tokens=6)[0]
        assert alone.token_ids == res.token_ids
        for a, b in zip(alone.trajectory, res.trajectory):
            np.testing.assert_allclose(a["top_logits"], b["top_logits"],
                                       atol=ATOL)


def test_warmup_compiles_both_programs_of_every_row_count(engine):
    engine.warmup(batch_sizes=(1, 3))
    assert [(r["target"], r["bucket"], r["rows"], r["error"])
            for r in engine.warmup_report()] == [
        ("gen:guard", 64, 1, ""), ("gen:guard", 64, 3, "")]
    gen = engine._tasks["guard"].generator
    keys = (sorted(gen._prefill_cache), sorted(gen._loop_cache))
    assert keys == ([(1, 64, 128), (4, 64, 128)],
                    [(1, 1, 128, 5), (4, 1, 128, 5)])
    engine.guard_classify("guard", words(prompts(14, (9,))[0]))
    engine.generate("guard", [words(p) for p in prompts(15, (6, 7, 8))],
                    max_new_tokens=gen.gen_length)
    assert (sorted(gen._prefill_cache), sorted(gen._loop_cache)) == keys
    assert all(f._cache_size() == 1 for f in
               list(gen._prefill_cache.values())
               + list(gen._loop_cache.values()))
