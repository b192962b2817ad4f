"""The sdar_moe family at a toy size on the CPU (hidden 64, 8 experts top-2,
2 layers, block 4): the program's expert layer, prefill and every forward of
a block-diffusion generation against the plain reference
(``chipbench/reference/sdar_moe.py``) on seeded float32 weights, the cache
path against the reference's full forward, the expert-parallel share, the
batcher's lock step against one row at a time, and the dense generator
through the same batch group.

Tolerances: both sides compute in float32 here and differ only in the order
of their sums (a grouped matmul over sorted pairs against a dense sum over
all experts, a cache against one long sequence), so logits agree to 2e-4 of
values of size 1-10; bfloat16 (2^-9 a rounding) would miss that by two
orders of magnitude, which ``chipbench``'s limits hold on the chip."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells
from semantic_router_tpu.models import experts as expert_layer
from semantic_router_tpu.models import sdar_moe as M
from semantic_router_tpu.models.generate import (
    BlockDiffusionGenerator,
    transfer_by_confidence,
)
from semantic_router_tpu.utils.tokenization import Encoding

L = 4
MASK = 255
MODEL = {
    "model_type": "sdar_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "hidden_act": "silu",
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "attention_bias": False,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
    "max_position_embeddings": 512, "max_window_layers": 2,
    "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "torch_dtype": "float32"}
CONFIG = {
    "family": "blockdiff_guard", "model": MODEL,
    "weights": {"std": 0.08, "embed_std": 1.0, "router_std": 0.3,
                "router_row_log_std": 0.3, "writer_threads": 2},
    "generation": {"block_length": L, "denoising_steps": 4,
                   "confidence_threshold": 0.9, "mask_token_id": MASK,
                   "gen_length": 8},
    "tasks": {"jailbreak": {"kind": "generative"}}, "route_margin": 0.05}
ATOL = 2e-4

family = cells.load_family(CONFIG)
ref = cells.load_module("reference", "sdar_moe")


class WordTokenizer:
    """``w<id>`` is token ``id``, any other piece is token 1."""

    def encode(self, text, max_length=0):
        ids = [int(w[1:]) if w[0] == "w" and w[1:].isdigit() else 1
               for w in family.PIECES.findall(text)]
        return Encoding(ids=ids, attention_mask=[1] * len(ids),
                        offsets=[(0, 0)] * len(ids))

    def decode(self, ids):
        return " ".join(f"w{int(i)}" for i in ids)


def words(ids) -> str:
    return " ".join(f"w{int(i)}" for i in ids)


@pytest.fixture(scope="module")
def toy():
    state = family.generate_state(CONFIG, 7)
    cfg = M.SdarMoeConfig.from_hf(MODEL)
    return state, cfg, M.params_from_state(state.__getitem__, cfg)


def generator(toy, **kw) -> BlockDiffusionGenerator:
    _, cfg, params = toy
    g = CONFIG["generation"]
    kw = {"mask_token_id": MASK, "block_length": L,
          "denoising_steps": g["denoising_steps"],
          "confidence_threshold": g["confidence_threshold"],
          "gen_length": g["gen_length"], "top_logits": 4, **kw}
    return BlockDiffusionGenerator(cfg, kw.pop("params", params),
                                   WordTokenizer(), **kw)


def prompts(seed: int, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 250, n) for n in lengths]


# -- the layer -------------------------------------------------------------------


def _ref_moe_weights(state, i):
    return ref.layer_weights(MODEL, state, i, "highest")["moe"]


def test_expert_layer_equals_the_reference(toy):
    state, cfg, params = toy
    x = np.random.default_rng(0).standard_normal((24, 64)).astype(np.float32)
    valid = np.ones(24, bool)
    y, top_e, load = M.moe(cfg, params["layers"][0], jnp.asarray(x),
                           jnp.asarray(valid))
    want, _, want_e = ref.moe(MODEL, _ref_moe_weights(state, 0),
                              jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=ATOL)
    assert (np.sort(np.asarray(top_e)) == np.sort(np.asarray(want_e))).all()
    busiest, pairs, touched, ratio = np.asarray(load)
    counts = np.bincount(np.asarray(top_e).ravel(), minlength=8)
    assert (busiest, pairs, touched) == (counts.max(), 48, (counts > 0).sum())
    assert ratio == pytest.approx(counts.max() / (48 / 8))


def test_a_padding_token_routes_nowhere(toy):
    _, cfg, params = toy
    x = jnp.asarray(np.random.default_rng(1).standard_normal((8, 64)),
                    jnp.float32)
    valid = jnp.asarray([True] * 5 + [False] * 3)
    y, _, load = M.moe(cfg, params["layers"][0], x, valid)
    y5, _, _ = M.moe(cfg, params["layers"][0], x[:5], jnp.ones(5, bool))
    np.testing.assert_allclose(np.asarray(y[:5]), np.asarray(y5), atol=1e-6)
    assert np.asarray(y[5:]).max() == 0.0 and float(load[1]) == 10


@pytest.mark.parametrize("impl", ["ragged_dot", "megablox"])
def test_the_shares_add_up_to_the_uncut_layer(toy, impl, monkeypatch):
    """Four chips of two experts each: every share routes over all eight
    experts and computes its own; the four partial results add up to the
    uncut layer's, in the program and in the reference alike.  Under both
    grouped matmuls: the CPU's own and the chip's kernel, interpreted."""
    state, cfg, params = toy
    if impl == "megablox":
        monkeypatch.setattr(expert_layer, "_grouped_matmul",
                            expert_layer._megablox)
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((128, 64)),
                    jnp.float32)
    valid = jnp.ones(128, bool)
    whole, _, _ = M.moe(cfg, p, x, valid)
    w = _ref_moe_weights(state, 1)
    ref_whole, _, _ = ref.moe(MODEL, w, x)
    total, ref_total = 0.0, 0.0
    for first in (0, 2, 4, 6):
        held = dataclasses.replace(cfg, experts_held=(first, 2))
        share = dict(p, gate_up=p["gate_up"][first:first + 2],
                     down=p["down"][first:first + 2])
        part, top_e, load = M.moe(held, share, x, valid)
        ref_part, _, _ = ref.moe(MODEL, w, x, experts_held=(first, 2))
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part),
                                   atol=ATOL)
        # the router still chooses among all eight
        assert np.asarray(top_e).max() > 1 + first or first == 6
        assert float(load[1]) == ((np.asarray(top_e) >= first)
                                  & (np.asarray(top_e) < first + 2)).sum()
        total, ref_total = total + part, ref_total + ref_part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=ATOL)
    np.testing.assert_allclose(np.asarray(ref_total), np.asarray(ref_whole),
                               atol=ATOL)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref_whole),
                               atol=ATOL)


def _routed_experts_before(p, x, valid, top_e, top_w, held, dtype):
    """``routed_experts``' combine as it was until PR 33, kept here as the
    statement of the arithmetic: every sorted row to float32 under the
    mask, a float32 gather back, ``[T, k, H]`` times the weights, summed
    over k."""
    T, H = x.shape
    k, I = top_e.shape[-1], p["down"].shape[-2]
    first, count = held
    local = top_e.reshape(-1) - first
    here = (local >= 0) & (local < count) & jnp.repeat(valid, k)
    group = jnp.where(here, local, count)
    order = jnp.argsort(group, stable=True)
    group_sizes = jnp.bincount(group, length=count + 1)[:count] \
        .astype(jnp.int32)
    xs = jnp.take(x, order // k, axis=0)
    gu = expert_layer._grouped_matmul(xs, p["gate_up"], group_sizes)
    h = (jax.nn.silu(gu[:, :I].astype(jnp.float32))
         * gu[:, I:].astype(jnp.float32)).astype(dtype)
    ys = expert_layer._grouped_matmul(h, p["down"], group_sizes)
    w = jnp.where(here, top_w.reshape(-1), 0.0)
    ys = jnp.where(jnp.take(here, order)[:, None], ys.astype(jnp.float32),
                   0.0)
    back = jnp.argsort(order)
    y = (jnp.take(ys, back, axis=0).reshape(T, k, H)
         * w.reshape(T, k, 1)).sum(1)
    return y.astype(dtype)


def _a_routed_layer(k, tokens, held, real, dtype, experts=16, H=64, I=32):
    """Seeded arguments of ``routed_experts``: ``experts`` routed over, the
    ``held`` of them here, the first ``real`` tokens valid."""
    keys = jax.random.split(jax.random.PRNGKey(31 * k + tokens), 4)
    p = {"gate_up": jax.random.normal(keys[0], (held[1], H, 2 * I), dtype),
         "down": jax.random.normal(keys[1], (held[1], I, H), dtype)}
    x = jax.random.normal(keys[2], (tokens, H), dtype)
    top_w, top_e = jax.lax.top_k(jax.nn.softmax(
        jax.random.normal(keys[3], (tokens, experts))), k)
    return p, x, jnp.arange(tokens) < real, top_e, top_w, held, dtype


# (tokens, held, real tokens): everything here; a held sub-range; padded
# tokens; a token count that is no multiple of 8 with both
ROUTED_CASES = [(24, (0, 16), 24), (24, (4, 8), 24), (24, (0, 16), 17),
                (13, (2, 9), 10)]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("tokens,held,real", ROUTED_CASES)
@pytest.mark.parametrize("k", [2, 4, 8])
def test_the_combine_is_the_arithmetic_it_was(k, tokens, held, real, dtype):
    """Each pair moved once each way in the model's dtype gives, bit for
    bit, what the float32 copies and the ``[T, k, H]`` sum gave: a value
    converts the same before or after it is moved, and the sum over k
    runs in the same order.  (Op by op, as here; inside one compiled
    program the CPU's compiler may contract a multiply and an add of
    EITHER form into one rounding.)"""
    args = _a_routed_layer(k, tokens, held, real, jnp.dtype(dtype))
    y, load = expert_layer.routed_experts(*args)
    want = _routed_experts_before(*args)
    assert y.dtype == want.dtype and y.shape == (tokens, 64)
    assert np.array_equal(np.asarray(y, np.float32),
                          np.asarray(want, np.float32))
    top_e = np.asarray(args[3])[:real]
    counts = np.bincount(top_e.ravel(), minlength=16)[held[0]:sum(held)]
    assert tuple(np.asarray(load)[:3]) == (counts.max(), counts.sum(),
                                           (counts > 0).sum())
    # compiled as one program: to one unit in bfloat16's last place
    jitted, _ = jax.jit(expert_layer.routed_experts,
                        static_argnums=(5, 6))(*args)
    np.testing.assert_allclose(np.asarray(jitted, np.float32),
                               np.asarray(want, np.float32), rtol=2 ** -7,
                               atol=1e-6)


@pytest.mark.parametrize("tokens,held,real", ROUTED_CASES[1:])
def test_rows_the_grouped_matmul_never_wrote_cannot_reach_y(
        tokens, held, real, monkeypatch):
    """The chip's kernel leaves the rows past the held groups unwritten:
    with NaN standing there, ``y`` is what it was."""
    args = _a_routed_layer(4, tokens, held, real, jnp.bfloat16)
    want, _ = expert_layer.routed_experts(*args)
    grouped = expert_layer._grouped_matmul
    unwritten = []

    def leaves_the_rest_unwritten(lhs, rhs, group_sizes):
        rows = jnp.arange(lhs.shape[0])[:, None]
        unwritten.append(lhs.shape[0] - int(group_sizes.sum()))
        return jnp.where(rows < group_sizes.sum(),
                         grouped(lhs, rhs, group_sizes), jnp.nan)

    monkeypatch.setattr(expert_layer, "_grouped_matmul",
                        leaves_the_rest_unwritten)
    y, _ = expert_layer.routed_experts(*args)
    assert unwritten[0] > 0 and unwritten[0] == unwritten[1]
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert np.array_equal(np.asarray(y, np.float32),
                          np.asarray(want, np.float32))
    if real < tokens:
        assert np.asarray(y, np.float32)[real:].max() == 0.0


def test_params_hold_only_the_experts_held(toy):
    state, cfg, _ = toy
    held = dataclasses.replace(cfg, experts_held=(2, 4))
    params = M.params_from_state(state.__getitem__, held)
    assert params["layers"][0]["gate_up"].shape == (4, 64, 64)
    assert params["layers"][0]["router"].shape == (64, 8)
    want = state["model.layers.0.mlp.experts.2.down_proj.weight"].T
    np.testing.assert_array_equal(np.asarray(params["layers"][0]["down"][0]),
                                  want)


# -- the cache path against the reference's full forward -------------------------


def test_prefill_then_blocks_equal_the_full_forward(toy):
    """Twelve tokens prefilled and committed, a block scored, and the next
    block scored by the forward that commits the first: the logits are the
    reference's over the whole sequence under the block-causal mask, and
    the cache holds what a prefill of sixteen tokens leaves."""
    state, cfg, params = toy
    ids = np.random.default_rng(3).integers(2, 250, (2, 20)).astype(np.int32)
    full = [ref.forward(MODEL, state, row, np.arange(20),
                        ref.block_causal(np.arange(20), L)) for row in ids]
    caches, _ = M.prefill(cfg, params, jnp.asarray(ids[:, :16]),
                          jnp.asarray([12, 12]), 64, L)
    start, rows = jnp.asarray([12, 12]), jnp.ones(2, bool)
    logits, none, top_e, _ = M.block_forward(
        cfg, params, caches, jnp.asarray(ids[:, 12:16]), start, rows)
    assert none is None and top_e.shape == (2, 2, L, 2)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(logits[b]),
                                   full[b]["logits"][12:16], atol=ATOL)
    logits, caches, top_e, load = M.block_forward(
        cfg, params, caches, jnp.asarray(ids[:, 16:20]), start + L, rows,
        previous=jnp.asarray(ids[:, 12:16]))
    assert logits.shape == (2, L, 256) and top_e.shape == (2, 2, 2 * L, 2)
    assert np.asarray(load)[:, 1].tolist() == [2 * 2 * L * 2] * 2  # pairs
    sixteen, _ = M.prefill(cfg, params, jnp.asarray(ids[:, :16]),
                           jnp.asarray([16, 16]), 64, L)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(logits[b]),
                                   full[b]["logits"][16:20], atol=ATOL)
        assert (np.sort(np.asarray(top_e[:, b]))
                == np.sort(full[b]["top_e"][:, 12:20])).all()
    for got, want in zip(caches, sixteen):
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g[:, :, :16]),
                                       np.asarray(w[:, :, :16]), atol=ATOL)


def _one_forward_programs(gen):
    """The two block programs the generator had while the host ran the
    loop, one forward each: the reference its block programs are held to,
    and the loop below is the host's of then."""
    def commit(params, caches, previous, tokens, masked, start, rows_valid,
               at_least):
        logits, caches, experts, load = M.block_forward(
            gen.config, params, caches, tokens, start, rows_valid, previous)
        tokens, masked, report = transfer_by_confidence(
            logits, tokens, masked, gen.confidence_threshold, at_least,
            gen.top_logits)
        return caches, tokens, masked, report, experts, load

    def denoise(params, caches, *block):
        return commit(params, caches, None, *block)[1:]

    return jax.jit(denoise), jax.jit(commit)


def test_the_committing_forward_and_the_loop_equal_a_commit_then_a_block(toy):
    """A later block as the generator runs it — ``commit`` (the block
    before beside the new block, 2L positions a row, the new block's state
    made on the device) and ``denoise`` from step 1 on what it left there —
    against a commit and a whole block apart: the cache it leaves is a
    prefill's with that block committed, the new block's tokens and every
    forward's report are ``denoise``'s from step 0 against that cache from
    ``[MASK]`` everywhere, and the experts of the committed block are a
    forward's of its own.  Rows at different starts, and a padding row."""
    _, cfg, params = toy
    gen = generator(toy)
    prefill, denoise, commit = gen.programs(3, 16, 64)
    one_forward, _ = _one_forward_programs(gen)
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.integers(2, 250, (3, 16)), jnp.int32)
    committed = jnp.asarray([4, 8, 0])
    valid = jnp.asarray([True, True, False])
    previous = jnp.stack([ids[0, 4:8], ids[1, 8:12], ids[2, :4]])
    tokens = jnp.full((3, L), MASK, jnp.int32)
    masked = jnp.asarray([[True] * L] * 2 + [[False] * L])

    before, _ = prefill(params, ids, committed)
    after, _ = prefill(params, ids, committed + L)
    nothing = jnp.zeros((3, L), bool)
    experts_before = one_forward(params, before, previous, nothing,
                                 committed, valid, 0)[3]
    w_tok, (w_reports, w_experts, w_loads), w_ran = denoise(
        params, after, tokens, masked, committed + L, valid, 0,
        gen.buffers(3))
    # block 1 of rows whose whole blocks end at ``committed``
    got_cache, *block, outs, experts_committed = commit(
        params, before, previous, committed, valid, 1)
    assert np.asarray(block[2]).tolist() == [8, 12, 4]  # where it starts
    assert np.asarray(block[1]).sum() == 2 * (L - 1)  # a position filled
    tok, (reports, experts, loads), ran = denoise(
        params, got_cache, *block, valid, 1, outs)

    for b, n in enumerate((8, 12)):  # the real rows' committed columns
        for layer, layer_after in zip(got_cache, after):
            for g, w in zip(layer, layer_after):
                np.testing.assert_allclose(np.asarray(g[b, :, :n]),
                                           np.asarray(w[b, :, :n]), atol=ATOL)
    assert int(ran) == int(w_ran) == 4  # never confident: a position each
    assert np.asarray(tok)[:2].tolist() == np.asarray(w_tok)[:2].tolist()
    assert not (np.asarray(tok)[:2] == MASK).any()
    np.testing.assert_allclose(np.asarray(reports[:, :2]),
                               np.asarray(w_reports[:, :2]), atol=ATOL)
    assert reports.shape == (4, 3, L, 4 + 2 * 4)  # the head saw L positions
    assert experts.shape == (4, 2, 3, L, 2)
    assert (np.asarray(experts[:, :, :2])
            == np.asarray(w_experts[:, :, :2])).all()
    assert experts_committed.shape == (2, 3, L, 2)
    assert (np.asarray(experts_committed[:, :2])
            == np.asarray(experts_before[:, :2])).all()
    # the padding row routes nowhere: two rows x 2L tokens x top-2 a layer
    # in the forward that commits, x L in the others
    assert np.asarray(loads)[:, :, 1].tolist() == \
        [[2 * 2 * L * 2] * 2] + [[2 * L * 2] * 2] * 3
    assert np.asarray(w_loads)[:, :, 1].tolist() == [[2 * L * 2] * 2] * 4


def test_rows_of_different_lengths_keep_their_own_positions(toy):
    """Row 0 has 8 committed tokens, row 1 has 12: each block stands at its
    row's own columns and sees its row's own cache."""
    state, cfg, params = toy
    ids = np.random.default_rng(4).integers(2, 250, (2, 16)).astype(np.int32)
    committed = np.asarray([8, 12], np.int32)
    caches, _ = M.prefill(cfg, params, jnp.asarray(ids),
                          jnp.asarray(committed), 64, L)
    block = np.stack([ids[0, 8:12], ids[1, 12:16]])
    logits, _, _, _ = M.block_forward(
        cfg, params, caches, jnp.asarray(block), jnp.asarray(committed),
        jnp.ones(2, bool))
    for b, n in enumerate((12, 16)):
        want = ref.forward(MODEL, state, ids[b, :n], np.arange(n),
                           ref.block_causal(np.arange(n), L))["logits"]
        np.testing.assert_allclose(np.asarray(logits[b]), want[n - L:],
                                   atol=ATOL)


# -- a generation, forward by forward ---------------------------------------------


def _compare(results, texts):
    """Each served trajectory replayed by the reference, through the
    family's own comparison."""
    reference = family.Reference(CONFIG, {"jailbreak": results["state"]})
    parts = {}
    for text, res in zip(texts, results["out"]):
        req = type("Request", (), {"text": text})
        got = {"jailbreak": res}
        raw = reference.outputs(req, {}, got)
        for k, (s, w) in family.compare(CONFIG, req, got, raw).items():
            s0, w0 = parts.get(k, (0.0, 0.0))
            parts[k] = (s0 + s, w0 + w)
    return family.finish(parts)


def _bare(monkeypatch):
    """The family wraps a text in the guard template; here the prompt IS
    the text."""
    monkeypatch.setattr(family, "BEFORE", "")
    monkeypatch.setattr(family, "AFTER", "")


@pytest.mark.parametrize("lengths", [(9,), (12,), (5, 14, 8)])
def test_every_forward_of_a_generation_equals_the_reference(
        toy, monkeypatch, lengths):
    _bare(monkeypatch)
    gen = generator(toy)
    texts = [words(p) for p in prompts(11, lengths)]
    out = gen.generate(texts)
    for res, n in zip(out, lengths):
        assert len(res.token_ids) == 8 and res.prompt_tokens == n
        kinds = [e["kind"] for e in res.trajectory]
        # seeded weights are never confident: one position a forward
        blocks = -(-(n % L + 8) // L)
        # the last block is never committed: nothing reads the cache again
        assert kinds.count("commit") == blocks - 1
        assert [e["block"] for e in res.trajectory
                if e["kind"] == "commit"] == list(range(blocks - 1))
        assert kinds.count("denoise") == blocks * 4 - n % L
    numbers = _compare({"state": toy[0], "out": out}, texts)
    assert numbers["gen_logit_rel_sq_err"] < 1e-9
    assert numbers["gen_transfer_gap_max"] < 1e-3
    assert numbers["moe_route_disagreement_share"] == 0.0
    assert numbers["moe_route_pairs_counted"] > 50 * len(lengths)


# what the tree before the commit rode the next block's first forward
# generated (five forwards a block, 1875b88) for these seeds: folding changes
# when K and V are written, not what they are
TOKENS_BEFORE = {
    (13, (11,)): [[243, 243, 165, 165, 243, 243, 165, 243]],
    (12, (10,)): [[42, 165, 165, 165, 165, 165, 165, 165]],
    (11, (5, 14, 8)): [[165] * 8, [42, 165, 165, 165, 165, 165, 42, 42],
                       [165] * 8],
    (31, (5, 14, 8)): [[165, 165, 165, 42, 165, 165, 165, 165], [165] * 8,
                       [165, 165, 165, 42, 42, 165, 165, 165]],
}
CONFIDENT_TOKENS_BEFORE = [
    [165] * 16,
    [42, 165, 165, 165, 165, 42, 42, 165, 42, 165, 165, 42, 42, 42, 42, 165],
    [165, 165, 165, 42, 42, 42, 165, 165, 165, 42, 42, 42, 42, 42, 42, 42],
    [165] * 16]


@pytest.mark.parametrize("seed,lengths", sorted(TOKENS_BEFORE))
def test_a_seeded_generation_gives_the_tokens_it_gave(toy, seed, lengths):
    out = generator(toy).generate([words(p) for p in prompts(seed, lengths)])
    assert [r.token_ids for r in out] == TOKENS_BEFORE[seed, lengths]


def test_an_altered_token_is_seen(toy, monkeypatch):
    _bare(monkeypatch)
    gen = generator(toy)
    text = words(prompts(12, (10,))[0])
    res = gen.generate([text])[0]
    first = next(e for e in res.trajectory if e["kind"] == "denoise")
    j = int(np.flatnonzero(first["filled"])[0])
    first["tokens_after"] = first["tokens_after"].copy()
    first["tokens_after"][j] = (first["tokens_after"][j] + 1) % 250
    numbers = _compare({"state": toy[0], "out": [res]}, [text])
    assert numbers["gen_transfer_gap_max"] > 0.05


def test_the_lower_precision_control_is_seen(toy, monkeypatch):
    """float8 weights in the program's place: the logits' number moves by
    orders of magnitude (a toy of eight experts has too few close calls
    for the router's number to move as well)."""
    _bare(monkeypatch)
    gen = generator(toy)
    text = words(prompts(13, (11,))[0])
    got = {"jailbreak": gen.generate([text])[0]}
    reference = family.Reference(CONFIG, {"jailbreak": toy[0]})
    req = type("Request", (), {"text": text})
    raw = reference.outputs(req, {}, got)
    low = reference.answers(req, {}, got, "float8_e4m3_weights")
    numbers = family.finish(family.compare(CONFIG, req, low, raw))
    assert numbers["gen_logit_rel_sq_err"] > 1e-5
    assert numbers["moe_route_disagreement_share"] >= 0.0


@pytest.fixture(scope="module")
def confident(toy):
    """The same model with a head twenty times as wide: softmax
    probabilities cross the 0.9 threshold at some positions and not at
    others, so blocks finish after one to four denoising forwards."""
    state, cfg, params = toy
    return state, cfg, dict(params, lm_head=params["lm_head"] * 20.0)


def test_rows_finish_a_block_at_different_forwards(confident, monkeypatch):
    _bare(monkeypatch)
    state, cfg, params = confident
    gen = generator(confident, gen_length=16)
    rows = prompts(21, (8, 8, 12, 16))
    texts = [words(p) for p in rows]
    together = gen.generate(texts)
    assert [r.token_ids for r in together] == CONFIDENT_TOKENS_BEFORE
    per_block = []
    for res in together:
        kinds = [(e["kind"], e["block"]) for e in res.trajectory]
        per_block.append([sum(1 for k, b in kinds
                              if k == "denoise" and b == blk)
                          for blk in range(4)])
        many = [int(e["filled"].sum()) for e in res.trajectory
                if e["kind"] == "denoise"]
        assert max(many) >= 2, "some forward filled several positions"
    assert len({tuple(x) for x in per_block}) > 1, per_block
    assert min(min(x) for x in per_block) < 4
    # the device's transfer is the reference's, at every forward
    scaled = dict(state, **{"lm_head.weight": state["lm_head.weight"] * 20.0})
    numbers = _compare({"state": scaled, "out": together}, texts)
    assert numbers["gen_logit_rel_sq_err"] < 1e-9
    assert numbers["gen_transfer_gap_max"] < 1e-3
    for res in together:
        for e in res.trajectory:
            if e["kind"] != "denoise":
                continue
            conf_m = np.where(e["masked"], e["confidence"], -np.inf)
            want = e["masked"] & (conf_m > 0.9)
            if want.sum() < 1:
                want = np.zeros(L, bool)
                want[int(np.argmax(conf_m))] = True
            assert (e["filled"] == want).all()
    # lock step changes nobody's tokens: one at a time gives the same
    for text, res in zip(texts, together):
        alone = gen.generate([text])[0]
        assert alone.token_ids == res.token_ids


def _forward_by_forward(gen, params, texts):
    """The generation as the host ran it while a block's forwards were a
    program each: one report read a forward, the next forward launched from
    what it said.  Returns each row's tokens and trajectory and the
    forwards every block took."""
    denoise, commit = _one_forward_programs(gen)
    encs = [gen.tokenizer.encode(t) for t in texts]
    n = B = len(encs)
    bucket = -(-max(len(e) for e in encs) // 32) * 32
    cache_len = gen.cache_len(bucket, gen.gen_length)
    lengths = np.asarray([len(e) for e in encs], np.int32)
    base = lengths // L * L
    tail = lengths - base
    blocks_of = -(-(tail + gen.gen_length) // L)
    valid = jnp.ones(B, bool)
    ids = np.zeros((B, bucket), np.int32)
    for i, e in enumerate(encs):
        ids[i, :lengths[i]] = e.ids
    caches, _ = gen.programs(B, bucket, cache_len)[0](
        params, jnp.asarray(ids), jnp.asarray(base))
    generated = [[] for _ in range(n)]
    trajectory = [[] for _ in range(n)]
    forwards, finished = [], None
    k = gen.top_logits
    for b in range(int(blocks_of.max())):
        tokens = np.full((B, L), MASK, np.int32)
        masked = np.ones((B, L), bool)
        if b == 0:
            for i in range(n):
                tokens[i, :tail[i]] = ids[i, base[i]:lengths[i]]
                masked[i, :tail[i]] = False
        live = [i for i in range(n) if b < blocks_of[i]]
        start = jnp.asarray(base + b * L)
        forwards.append(0)
        for step in range(gen.denoising_steps):
            block = (jnp.asarray(tokens), jnp.asarray(masked), start, valid,
                     gen.transfer_schedule()[step])
            commits = finished is not None
            if commits:
                caches, *out = commit(params, caches, jnp.asarray(finished),
                                      *block)
            else:
                out = denoise(params, caches, *block)
            report, experts = np.asarray(out[2]), np.asarray(out[3])
            forwards[-1] += 1
            after = report[..., 0].astype(np.int32)
            filled = report[..., 1] > 0.5
            for i in live:
                if commits:
                    trajectory[i].append({
                        "kind": "commit", "block": b - 1,
                        "tokens": finished[i], "masked": np.zeros(L, bool),
                        "experts": experts[:, i, :L]})
                if masked[i].any():
                    trajectory[i].append({
                        "kind": "denoise", "block": b,
                        "tokens": tokens[i].copy(),
                        "masked": masked[i].copy(), "filled": filled[i],
                        "tokens_after": after[i],
                        "confidence": report[i, :, 2],
                        "lse": report[i, :, 3],
                        "top_ids": report[i, :, 4:4 + k].astype(np.int32),
                        "top_logits": report[i, :, 4 + k:],
                        "experts": experts[:, i, -L:]})
            tokens, masked, finished = after, masked & ~filled, None
            if not masked.any():
                break
        for i in live:
            generated[i].extend(
                int(t) for t in tokens[i, tail[i] if b == 0 else 0:])
        finished = tokens
    return ([g[:gen.gen_length] for g in generated], trajectory, forwards)


class _Programs:
    """An observer that keeps what the generator tells one."""

    def __init__(self):
        self.opened, self.closed = [], []

    def forward(self, flavour, **facts):
        self.opened.append((flavour, facts))
        return self

    def stage(self, name):
        import contextlib

        return contextlib.nullcontext()

    def done(self, **after):
        self.closed.append(after)


@pytest.mark.parametrize("lengths", [(9,), (12, 5), (5, 14, 8)])
@pytest.mark.parametrize("weights", ["unconfident", "confident"])
def test_the_block_programs_give_what_the_hosts_loop_gave(
        toy, confident, weights, lengths):
    """Forward for forward: the same tokens, the same trajectory entries
    in the same order, the same number of forwards a block, whether every
    block takes all its forwards or some end early."""
    _, _, params = confident if weights == "confident" else toy
    gen = generator(toy, params=params, gen_length=12)
    texts = [words(p) for p in prompts(17, lengths)]
    want_tokens, want, want_forwards = _forward_by_forward(gen, params, texts)
    seen = _Programs()
    out = gen.generate(texts, observer=seen)
    assert [r.token_ids for r in out] == want_tokens
    assert [c["forwards"] for c in seen.closed[1:]] == want_forwards
    if weights == "confident":
        assert min(want_forwards) < 4, want_forwards
    else:
        assert set(want_forwards[1:]) == {4}
    assert [f for f, _ in seen.opened] == \
        ["gen.prefill", "gen.denoise"] + ["gen.commit"] * (
            len(want_forwards) - 1)
    layers = 2
    for done, ran in zip(seen.closed[1:], want_forwards):
        assert done["load"].shape == (ran * layers, 4)
        assert done["committed_tokens"] == done["committed_blocks"] * L
    for res, entries in zip(out, want):
        assert len(res.trajectory) == len(entries)
        for got, e in zip(res.trajectory, entries):
            assert sorted(got) == sorted(e)
            for key, value in e.items():
                if np.asarray(value).dtype.kind == "f":
                    np.testing.assert_allclose(got[key], value, atol=1e-5,
                                               err_msg=key)
                else:
                    assert np.array_equal(got[key], value), key
                    assert np.asarray(got[key]).dtype == \
                        np.asarray(value).dtype, key


@pytest.mark.parametrize("seed", range(4))
def test_transfer_on_the_device_equals_the_references(seed):
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((3, L, 50)).astype(np.float32) * 6
    tokens = rng.integers(0, 50, (3, L)).astype(np.int32)
    masked = rng.random((3, L)) < 0.7
    masked[2] = False
    at_least = 1 + seed % 2
    new, still, report = transfer_by_confidence(
        jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(masked), 0.5,
        at_least, 3)
    for b in range(3):
        x0, conf, filled = ref.transfer(logits[b], masked[b], 0.5, at_least)
        assert (np.asarray(report[b, :, 1]) > 0.5).tolist() == filled.tolist()
        np.testing.assert_allclose(np.asarray(report[b, :, 2]), conf,
                                   rtol=1e-5)
        assert np.asarray(new[b]).tolist() == \
            np.where(filled, x0, tokens[b]).tolist()
        assert np.asarray(still[b]).tolist() == (masked[b] & ~filled).tolist()
        order = np.argsort(-logits[b], -1, kind="stable")[:, :3]
        assert np.asarray(report[b, :, 4:7]).astype(int).tolist() \
            == order.tolist()


# -- through the engine and the batcher -------------------------------------------


@pytest.fixture()
def engine(toy):
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine

    eng = InferenceEngine(InferenceEngineConfig(max_batch_size=4, max_wait_ms=50.0,
                                       seq_len_buckets=[32]))
    eng.register_generative("guard", generator(toy))
    yield eng
    eng.shutdown()


def test_three_rows_through_the_batcher_equal_one_at_a_time(engine, toy):
    texts = [words(p) for p in prompts(31, (5, 14, 8))]
    together = engine.generate("guard", texts, max_new_tokens=8)
    stats = engine.batcher.stats()
    assert stats["batches"] == 1 and stats["max_batch"] == 3
    gen = generator(toy)
    for text, res in zip(texts, together):
        alone = gen.generate([text], max_new_tokens=8)[0]
        assert alone.token_ids == res.token_ids
        assert len(alone.trajectory) == len(res.trajectory)
        for a, b in zip(alone.trajectory, res.trajectory):
            assert a["kind"] == b["kind"] and a["block"] == b["block"]
            assert (a["tokens"] == b["tokens"]).all()
            if a["kind"] == "denoise":
                np.testing.assert_allclose(a["top_logits"], b["top_logits"],
                                           atol=ATOL)


def _gen_counts(engine):
    """(steps by flavour, rows of the commit steps, committed blocks and
    tokens, forwards by flavour, programs by flavour) so far: the default
    RuntimeStats is the process's, so tests take differences."""
    rs = engine._runtime_stats
    rs.flush()
    rows = {p["variant"]: p for p in rs.programs()
            if p["group"] == "gen:guard"}
    flavours = ("gen.prefill", "gen.denoise", "gen.commit")
    return ({v: p["executes"] + p["compiles"] for v, p in rows.items()},
            rows.get("gen.commit", {}).get("rows_real", 0),
            (rs.gen_blocks.get(task="guard"),
             rs.gen_tokens.get(task="guard")),
            {v: rs.gen_forwards.get(task="guard", flavour=v)
             for v in flavours},
            {v: rs.gen_programs.get(task="guard", flavour=v)
             for v in flavours})


def test_every_block_is_a_step_and_every_forward_a_count(engine):
    steps0, rows0, g0, f0, p0 = _gen_counts(engine)
    texts = [words(p) for p in prompts(32, (8, 10))]
    engine.generate("guard", texts, max_new_tokens=8)
    steps1, rows1, g1, f1, p1 = _gen_counts(engine)
    # the 10-token row has a partial block, so the batch runs 3 blocks; the
    # 8-token row starts every block with 4 masks: 4 forwards a block, the
    # first of the second and third blocks committing the block before.
    # A block is a program and a step of the host; its forwards are counted
    want = {"gen.prefill": 1, "gen.denoise": 1, "gen.commit": 2}
    assert {v: steps1[v] - steps0.get(v, 0) for v in steps1} == want
    assert {v: p1[v] - p0[v] for v in p1} == want
    assert {v: f1[v] - f0[v] for v in f1} == \
        {"gen.prefill": 1, "gen.denoise": 10, "gen.commit": 2}
    assert rows1 - rows0 == 4
    # blocks and tokens FINISHED, the last block among them
    assert (g1[0] - g0[0], g1[1] - g0[1]) == (2 + 3, 20)


def test_an_early_end_runs_fewer_forwards_and_says_so(toy, confident, seen):
    """With a confident head some block fills its masks before its fourth
    forward: the loop ends on the device, and the marker's ``forwards``,
    the two counters and ``engine.gen.done`` all say how many ran."""
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine

    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=50.0, seq_len_buckets=[32]))
    try:
        eng.register_generative(
            "guard", generator(toy, params=confident[2], gen_length=16))
        _, _, _, f0, p0 = _gen_counts(eng)
        texts = [words(p) for p in prompts(21, (8, 8, 12, 16))]
        out = eng.generate("guard", texts, max_new_tokens=16)
        _, _, _, f1, p1 = _gen_counts(eng)
    finally:
        eng.shutdown()
    assert [r.token_ids for r in out] == CONFIDENT_TOKENS_BEFORE
    marks = [f for n, f in seen if n == "engine.gen.forward"]
    assert [m["flavour"] for m in marks] == \
        ["gen.prefill", "gen.denoise"] + ["gen.commit"] * 3
    ran = [m["forwards"] for m in marks]
    assert ran[0] == 1 and all(1 <= r <= 4 for r in ran) and min(ran[1:]) < 4
    # a block's forwards are as many as its slowest row's denoise entries
    for blk, r in enumerate(ran[1:]):
        assert r == max(sum(1 for e in res.trajectory
                            if e["kind"] == "denoise" and e["block"] == blk)
                        for res in out)
    assert [m["layers"] for m in marks] == [2 * r for r in ran]
    forwards = {v: f1[v] - f0[v] for v in f1}
    programs = {v: p1[v] - p0[v] for v in p1}
    assert programs == {"gen.prefill": 1, "gen.denoise": 1, "gen.commit": 3}
    assert forwards == {"gen.prefill": 1, "gen.commit": 3,
                        "gen.denoise": sum(ran) - 4}
    assert sum(forwards.values()) / sum(programs.values()) < 4
    (done,) = [f for n, f in seen if n == "engine.gen.done"]
    assert done["forwards"] == sum(ran) and done["blocks"] == 4 * 4


def test_warmup_compiles_the_generative_programs(engine):
    engine.warmup(batch_sizes=(1, 3))
    report = engine.warmup_report()
    assert [(r["target"], r["bucket"], r["rows"], r["error"])
            for r in report] == [("gen:guard", 32, 1, ""),
                                 ("gen:guard", 32, 3, "")]
    gen = engine._tasks["guard"].generator
    warmed = dict(gen._programs)
    # the cache length of the generator's own gen_length (8: 32 + 8 + 3
    # rounded up to 64), which is also what guard_classify asks for
    assert gen.gen_length == 8
    assert sorted(warmed) == [(1, 32, 64), (4, 32, 64)]
    sizes = [[f._cache_size() for f in fns] for fns in warmed.values()]
    assert all(min(row) == 1 for row in sizes)  # prefill, denoise, commit
    engine.guard_classify("guard", words(prompts(33, (9,))[0]))
    out = engine.generate("guard", [words(p) for p in prompts(34, (6, 7, 8))],
                          max_new_tokens=gen.gen_length)
    # three blocks: every program of the loop ran, the committing one twice
    assert [e["block"] for e in out[0].trajectory
            if e["kind"] == "commit"] == [0, 1]
    assert dict(gen._programs) == warmed
    assert [[f._cache_size() for f in fns]
            for fns in warmed.values()] == sizes


def test_step_facts_on_the_profilers_clock(engine, seen):
    engine.generate("guard", [words(prompts(35, (8,))[0])],
                    max_new_tokens=8)
    steps = [f for n, f in seen if n == "engine.step"]
    # a step a block: the second block's program commits the first; the
    # second block is the last, and nothing commits it
    assert [s["flavour"] for s in steps] == \
        ["gen.prefill", "gen.denoise", "gen.commit"]
    assert steps[0]["group"] == "gen:guard" and steps[0]["tokens_real"] == 8
    assert [s["masks_left"] for s in steps[1:]] == [4, 4]
    assert [s["block"] for s in steps[1:]] == [0, 1]
    # what a program takes in: a block, or the block before beside it
    assert [s["tokens_real"] for s in steps[1:]] == [L, 2 * L]
    assert all(s["rows"] == 1 for s in steps[1:])
    # how many forwards a program ran is known after it: the marker's fact
    assert all("forwards" not in s for s in steps)
    marks = [f for n, f in seen if n == "engine.gen.forward"]
    assert [m["flavour"] for m in marks] == [s["flavour"] for s in steps]
    assert [m["forwards"] for m in marks] == [1, 4, 4]
    assert [m["layers"] for m in marks] == [2, 4 * 2, 4 * 2]
    # forwards x layers x tokens x top-2; a commit's first is of two blocks
    assert marks[1]["pairs"] == 4 * 2 * L * 2
    assert marks[2]["pairs"] == (4 + 1) * 2 * L * 2
    assert marks[1]["load_milli"] >= 1000
    # a turn between two steps has no stage of its own: a block's state is
    # made on the device
    assert not [n for n, _ in seen if n.startswith("engine.gen.turn.")]
    assert [f["after"] for n, f in seen if n == "engine.gen.turn"] == \
        ["gen.prefill", "gen.denoise", "gen.commit"]
    (done,) = [f for n, f in seen if n == "engine.gen.done"]
    assert (done["forwards"], done["blocks"], done["tokens"]) == (9, 2, 8)


def test_the_dense_generator_through_the_batcher_gives_its_tokens():
    from semantic_router_tpu.engine.classify import InferenceEngine
    from semantic_router_tpu.models.generate import GreedyGenerator
    from semantic_router_tpu.models.qwen3 import Qwen3Config, Qwen3ForCausalLM

    cfg = Qwen3Config(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      tie_word_embeddings=True)
    params = Qwen3ForCausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    texts = [words(p) for p in prompts(41, (6, 11, 4))]
    direct = [GreedyGenerator(cfg, params, WordTokenizer()).generate(
        [t], max_new_tokens=6)[0].token_ids for t in texts]
    eng = InferenceEngine()
    eng.register_generative(
        "gen", GreedyGenerator(cfg, params, WordTokenizer()))
    try:
        out = eng.generate("gen", texts, max_new_tokens=6)
        assert [r.token_ids for r in out] == direct
        assert [r.prompt_tokens for r in out] == [6, 11, 4]
        rs = eng._runtime_stats
        rs.flush()
        assert {p["variant"] for p in rs.programs()
                if p["group"] == "gen:gen"} == {"gen.prefill", "gen.decode"}
    finally:
        eng.shutdown()


def test_a_dense_task_takes_gen_length_and_nothing_else():
    """``generation: {gen_length}`` reaches the dense generator too (it is
    what ``guard_classify`` asks for and ``warmup`` compiles); a block
    generator's settings on a dense checkpoint are refused."""
    from semantic_router_tpu.models.generate import GEN_LENGTH
    from semantic_router_tpu.runtime.bootstrap import build_generator

    hf = {"model_type": "qwen3", "vocab_size": 64, "hidden_size": 32,
          "intermediate_size": 64, "num_hidden_layers": 1,
          "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
          "tie_word_embeddings": True, "eos_token_id": 3}
    state = {"model.embed_tokens.weight": np.zeros((64, 32), np.float32),
             "model.norm.weight": np.ones(32, np.float32)}

    def build(spec):
        return build_generator(spec, hf, "", WordTokenizer(),
                               lambda path: state)[0]

    assert build({"generation": {"gen_length": 12}}).gen_length == 12
    assert build({}).gen_length == GEN_LENGTH == 32
    with pytest.raises(ValueError, match="gen_length only"):
        build({"generation": {"block_length": 4}})


# -- a prompt longer than the largest bucket ----------------------------------------


def _dense_generator():
    from semantic_router_tpu.models.generate import GreedyGenerator
    from semantic_router_tpu.models.qwen3 import Qwen3Config, Qwen3ForCausalLM

    cfg = Qwen3Config(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      tie_word_embeddings=True)
    params = Qwen3ForCausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return GreedyGenerator(cfg, params, WordTokenizer(), gen_length=4)


@pytest.fixture(params=["blockdiff", "dense"])
def long_engine(request, toy):
    """Bucket 64 alone, and a spy on what the generator is handed."""
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine

    gen = generator(toy, gen_length=4) if request.param == "blockdiff" \
        else _dense_generator()
    handed, inner = [], gen.generate

    def spy(prompts, *args, **kw):
        handed.extend(kw["encodings"])
        return inner(prompts, *args, **kw)

    gen.generate = spy
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=2, max_wait_ms=1.0, seq_len_buckets=[64]))
    eng.register_generative("guard", gen)
    yield eng, handed
    eng.shutdown()


def _overflows(eng) -> float:
    return eng._series().bucket_overflows.get(task="guard")


def test_a_prompt_longer_than_the_bucket_is_cut_flagged_and_counted(
        long_engine):
    eng, handed = long_engine
    before = _overflows(eng)
    long, short = words(range(2, 102)), words(range(2, 30))
    out = eng.generate("guard", [long, short], max_new_tokens=4,
                       keep_tail=3)
    assert [r.truncated for r in out] == [True, False]
    assert [r.prompt_tokens for r in out] == [64, 28]
    assert _overflows(eng) - before == 1
    cut = next(e for e in handed if e.truncated)
    # the first 61 tokens and the last 3; the whole count is kept
    assert cut.ids == list(range(2, 63)) + [99, 100, 101]
    assert cut.n_total == 100 and len(cut.attention_mask) == 64


def test_guard_classify_cuts_the_text_not_the_templates_tail(long_engine):
    """The template is 40 pieces (token 1 each), its closing words the last
    two; a text of 100 words keeps its first 24 and loses its end."""
    from semantic_router_tpu.models.generate import (
        GUARD_PROMPT_TAIL,
        build_guard_prompt,
    )

    eng, handed = long_engine
    tail = len(WordTokenizer().encode(GUARD_PROMPT_TAIL))
    whole = WordTokenizer().encode(
        build_guard_prompt(words(range(2, 102)))).ids
    assert len(whole) == 140 and tail == 2
    before = _overflows(eng)
    verdict = eng.guard_classify("guard", words(range(2, 102)))
    assert verdict.truncated and _overflows(eng) - before == 1
    assert handed[-1].ids == whole[:62] + whole[-2:]
    assert handed[-1].ids[38:62] == list(range(2, 26))  # the text's head
    # a text that fits is not flagged, and nothing is counted
    verdict = eng.guard_classify("guard", words(range(2, 12)))
    assert not verdict.truncated and _overflows(eng) - before == 1
    assert len(handed[-1]) == 50 and not handed[-1].truncated


@pytest.mark.parametrize("kind", ["blockdiff", "dense"])
def test_a_generator_refuses_a_prompt_longer_than_its_bucket(toy, kind):
    """Nothing is dropped silently below the engine: a direct call with a
    bucket too small raises; without one, the prompts' own longest is it."""
    gen = generator(toy, gen_length=4) if kind == "blockdiff" \
        else _dense_generator()
    text = words(range(2, 42))
    with pytest.raises(ValueError, match="40 tokens does not fit"):
        gen.generate([text], 4, bucket=32)
    assert gen.generate([text], 4)[0].prompt_tokens == 40


# -- the kernel's block-causal mask -----------------------------------------------


def test_flash_kernel_block_causal_mask():
    from semantic_router_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_pallas,
    )

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 2, 128, 16)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(np.arange(128)[None, :] < np.asarray([[100], [128]]),
                       jnp.int32)
    got = flash_attention_pallas(q, k, v, mask, causal=True, causal_block=L,
                                 interpret=True)
    want = flash_attention(q, k, v, mask, causal=True, causal_block=L)
    grp = np.arange(128) // L
    bias = np.where((grp[None, :] <= grp[:, None])[None]
                    & np.asarray(mask, bool)[:, None, :], 0.0, -1e30)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / 4.0 + bias[:, None]
    p = np.exp(s - s.max(-1, keepdims=True))
    plain = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(want), plain, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), plain, atol=1e-5)


# -- bootstrap: every model number from the checkpoint's config.json --------------


def _router_yaml(tmp_path, ckpt, generation=""):
    path = tmp_path / "router.yaml"
    path.write_text(f"""
default_model: m
engine:
  max_batch_size: 2
  seq_len_buckets: [64]
routing:
  modelCards:
  - name: m
classifier_models:
  jailbreak:
    checkpoint: '{ckpt}/jailbreak'
    tokenizer: '{ckpt}/tokenizer'
    kind: generative
{generation}""")
    return str(path)


def test_bootstrap_builds_the_block_generator_from_the_checkpoint(tmp_path):
    """``kind: generative`` + ``model_type: sdar_moe``: the expert
    configuration, a non-default ``rms_norm_eps`` and the dtype reach the
    served module; the generation settings come from the task's block."""
    from semantic_router_tpu.config import load_config
    from semantic_router_tpu.runtime.bootstrap import build_engine

    config = dict(CONFIG, model=dict(MODEL, rms_norm_eps=3e-4),
                  generation=dict(CONFIG["generation"], mask_token_id=200))
    ckpt = str(tmp_path / "ckpt")
    family.write_checkpoints(ckpt, config, 5)
    engine = build_engine(load_config(_router_yaml(tmp_path, ckpt, """
    generation:
      block_length: 4
      denoising_steps: 2
      confidence_threshold: 0.8
      mask_token_id: 200
      gen_length: 8
""")))
    try:
        gen = engine._tasks["jailbreak"].generator
        assert isinstance(gen, BlockDiffusionGenerator)
        assert gen.config.rms_norm_eps == 3e-4
        assert gen.config.dtype == jnp.float32
        assert (gen.config.num_experts, gen.config.num_experts_per_tok,
                gen.config.moe_intermediate_size,
                gen.config.norm_topk_prob) == (8, 2, 32, True)
        assert (gen.denoising_steps, gen.confidence_threshold,
                gen.mask_token_id, gen.gen_length) == (2, 0.8, 200, 8)
        assert gen.transfer_schedule() == [2, 2]
        verdict = engine.guard_classify("jailbreak", "w5 w6 w7")
        assert verdict.safety in ("Safe", "Unsafe", "Controversial")
    finally:
        engine.shutdown()


def test_bootstrap_refuses_what_the_architecture_cannot_express(tmp_path):
    from semantic_router_tpu.runtime.bootstrap import build_generator

    hf = dict(MODEL, attention_bias=True)
    with pytest.raises(ValueError, match="attention_bias"):
        build_generator({"generation": {"mask_token_id": 1}}, hf, "", None,
                        None)
    with pytest.raises(ValueError, match="mask_token_id is required"):
        build_generator({"generation": {"block_length": 4}}, dict(MODEL),
                        "", None, None)


def test_the_dense_generators_config_comes_through_from_hf():
    """What the hand-copied subset dropped: ``rms_norm_eps``,
    ``attention_bias``, ``max_position_embeddings`` and the dtype reach the
    decoder's module."""
    from semantic_router_tpu.runtime.bootstrap import build_generator

    hf = {"model_type": "qwen3", "vocab_size": 64, "hidden_size": 32,
          "intermediate_size": 64, "num_hidden_layers": 1,
          "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
          "rms_norm_eps": 2e-3, "attention_bias": True,
          "max_position_embeddings": 77, "tie_word_embeddings": True,
          "torch_dtype": "bfloat16", "eos_token_id": [3, 4]}
    rng = np.random.default_rng(0)
    state = {"model.embed_tokens.weight":
             rng.standard_normal((64, 32)).astype(np.float32),
             "model.norm.weight": np.ones(32, np.float32)}
    gen, adapters = build_generator({}, hf, "", WordTokenizer(),
                                    lambda path: state)
    cfg = gen.module.config
    assert (cfg.rms_norm_eps, cfg.attention_bias,
            cfg.max_position_embeddings) == (2e-3, True, 77)
    assert cfg.dtype == jnp.bfloat16 and adapters == {}
    assert gen.eos_token_ids == {3, 4}
