"""The joyai_llm_flash family at a toy size on the CPU (hidden 64; 3 layers
of 4 latent heads, the first dense; 16 experts top-2 beside a shared one, 8
of them held; an MTP module): the program's prefill, its two-position steps
through both latent caches and its drafter against the plain reference
(``chipbench/reference/joyai_llm_flash.py``) on seeded float32 weights;
self-drafting against the same program a token at a time; the mechanisms
one by one; the shares; the loop through the engine's batcher.

Tolerances: both sides compute in float32 here and differ only in the order
of their sums, so logits of size 1-10 agree to 2e-4 (``ATOL``); bfloat16
would miss that by two orders of magnitude, which ``chipbench``'s limits
hold on the chip.  A dropped or altered mechanism has to miss it by 50
times (``FAULT``), or it is not being tested.  Served tokens are compared
exactly: drafting may change the number of steps and nothing else."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import per_step_loop
from chipbench import cells
from test_dots3_note import WordTokenizer, prompts, words
from semantic_router_tpu.models import generate as G
from semantic_router_tpu.models import joyai_llm_flash as M
from semantic_router_tpu.models import checkpoints, decoder_parts, dots3_note
from semantic_router_tpu.models import experts as expert_layer
from semantic_router_tpu.models.generate import GreedyGenerator

MODEL = {
    "model_type": "joyai_llm_flash", "attention_bias": False, "ep_size": 1,
    "first_k_dense_replace": 1, "head_dim": 8, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 16,
    "max_position_embeddings": 512, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 8,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 1,
    "q_lora_rank": 32, "qk_head_dim": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 8, "vocab_size": 256, "torch_dtype": "float32"}
EXPERTS = (8, 8)  # the second half
CONFIG = {
    "family": "latent_mtp_ar_guard", "model": MODEL,
    "published": {"n_routed_experts": 16}, "held": {"experts": list(EXPERTS)},
    # the embedding a few times the blocks' sum and W_eh's embedding half
    # near the identity: the module agrees with the model about half the
    # time (the test of the mixed drafter reads it)
    "weights": {"std": 0.1, "q_b_gain": 3.0, "o_gain": 1.0,
                "embed_std": 6.0,
                "head_std": 0.3, "router_std": 0.3, "router_row_log_std": 0.3,
                "expert_bias_std": 0.05, "eh_std": 0.05,
                "eh_embed_gain": 6.0, "norm_std": 0.3,
                "writer_threads": 2},
    "tasks": {"jailbreak": {"kind": "generative"}},
    "route_margin": 0.01, "route_sample": 8, "accept_margin": 0.01}
ATOL = 2e-4
FAULT = 50 * ATOL

family = cells.load_family(CONFIG)
ref = cells.load_module("reference", "joyai_llm_flash")


def variant(experts=EXPERTS, **changes):
    """(published numbers, state, config, params) of the toy with
    ``changes``, holding ``experts``."""
    config = dict(CONFIG, model=dict(
        MODEL, n_routed_experts=experts[1], **changes),
        held={"experts": list(experts)})
    state = family.generate_state(config, 7)
    hf = family.published_model(config)
    cfg = M.JoyaiLlmFlashConfig.from_hf(hf, experts_held=experts)
    return hf, state, cfg, M.params_from_state(state.__getitem__, cfg)


@pytest.fixture(scope="module")
def toy():
    return variant()


def reference(hf, state, ids, rows=None, **kw):
    kw.setdefault("experts_held", EXPERTS)
    return ref.forward(hf, state, ids, rows, **kw)


def padded(rows, bucket: int):
    ids = np.zeros((len(rows), bucket), np.int32)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
    return jnp.asarray(ids), jnp.asarray([len(r) for r in rows], jnp.int32)


def same_sets(got, want) -> bool:
    return (np.sort(np.asarray(got).astype(np.int64), -1)
            == np.sort(np.asarray(want), -1)).all()


# -- the program's stages against the reference, on fixed inputs ---------------------

LENGTHS = (11, 7, 0, 16)  # a padding row among them
BUCKET, CACHE = 16, 64


def stages(cfg, params, fixed=None):
    """A prefill, the first draft, one two-position step and its draft, as
    ONE program.  ``fixed`` = the tokens a sound run chose at each stage
    (``tokens, draft, chosen, accepted``): a faulty program is run on the
    sound one's inputs, so that only its arithmetic differs."""
    ids, lengths = padded(prompts(3, LENGTHS), BUCKET)

    def run(params):
        cache, logits, aux = M.prefill(cfg, params, ids, lengths, CACHE)
        tokens = jnp.argmax(logits, -1).astype(jnp.int32) if fixed is None \
            else fixed["tokens"]
        cache, first, aux = M.first_draft(cfg, params, cache, ids, lengths,
                                          tokens, aux)
        draft = jnp.argmax(first, -1).astype(jnp.int32) if fixed is None \
            else fixed["draft"]
        cache, both, hidden, aux2 = M.verify(
            cfg, params, cache, jnp.stack([tokens, draft], 1), lengths)
        chosen = jnp.argmax(both, -1).astype(jnp.int32) if fixed is None \
            else fixed["chosen"]
        accepted = chosen[:, 0] == draft if fixed is None \
            else fixed["accepted"]
        cache, after, aux2 = M.draft(cfg, params, cache, hidden, chosen,
                                     lengths, accepted, aux2)
        return dict(logits=logits, first=first, both=both, after=after,
                    tokens=tokens, draft=draft, chosen=chosen,
                    accepted=accepted, experts=aux["experts"],
                    step_experts=aux2["experts"])
    return jax.tree.map(np.asarray, jax.jit(run)(params))


@pytest.fixture(scope="module")
def sound(toy):
    """The sound program's stages, and the reference's logits for the same
    sequences: per real row ``(row, n, prompt's, step's)``."""
    hf, state, cfg, params = toy
    got = stages(cfg, params)
    want = []
    for i, n in enumerate(LENGTHS):
        if not n:
            continue
        row = prompts(3, LENGTHS)[i]
        a = reference(hf, state, row, next_token=int(got["tokens"][i]))
        # the step's inputs: the token the prefill chose, then the DRAFT at
        # the second position; the module reads what the step chose
        seq = np.concatenate([row, [got["tokens"][i], got["draft"][i]]])
        b = reference(hf, state, seq, next_token=int(got["chosen"][i, 1]))
        seq[-1] = got["chosen"][i, 0]
        c = reference(hf, state, seq, next_token=int(got["chosen"][i, 1]))
        want.append((i, n, a, b, c))
    return got, want


def errors(got, want):
    """The largest difference from the reference's logits, by stage."""
    err = dict(logits=0.0, first=0.0, both0=0.0, both1=0.0, after=0.0)

    def worst(key, a, b):
        err[key] = max(err[key], float(np.abs(a - b).max()))

    for i, n, a, b, c in want:
        worst("logits", got["logits"][i], a["logits"][n - 1])
        worst("first", got["first"][i], a["draft_logits"][n - 1])
        worst("both0", got["both"][i, 0], b["logits"][n])
        worst("both1", got["both"][i, 1], b["logits"][n + 1])
        # the module at p (rejected: its second position is masked out and
        # the first reads the token chosen), or at p + 1
        src = b if got["accepted"][i] else c
        worst("after", got["after"][i],
              src["draft_logits"][n + int(got["accepted"][i])])
    return err


def test_every_stage_equals_the_reference(sound):
    got, want = sound
    assert max(errors(got, want).values()) < ATOL
    for i, n, a, b, c in want:
        # the experts chosen, the module's block as the last layer
        assert got["experts"].shape == (3, 4, BUCKET, 2)
        assert same_sets(got["experts"][:, i, :n], a["top_e"][:, :n])
        assert same_sets(got["step_experts"][:2, i], b["top_e"][:2, n:n + 2])
        assert same_sets(got["step_experts"][2, i, 0], c["top_e"][2, n])
    assert got["tokens"][2] == got["logits"][2].argmax()  # a padding row runs


SEEN = M._seen


def blind_second(pos, Mx):
    """``_seen`` without the first position's column for the second."""
    seen = SEEN(pos, Mx)
    hide = (jnp.arange(Mx)[None, None, :] == pos[:, :1, None]) \
        & (jnp.arange(pos.shape[1])[None, :, None] == 1)
    return seen & ~hide


def mtp_input_without(which: str):
    def fn(cfg, m, embed_next, hidden):
        norm = lambda x, w: decoder_parts.rms_norm(  # noqa: E731
            x, w, cfg.rms_norm_eps, cfg.dtype)
        return jnp.concatenate(
            [embed_next if which == "enorm" else norm(embed_next, m["enorm"]),
             hidden if which == "hnorm" else norm(hidden, m["hnorm"])],
            -1) @ m["eh_proj"]
    return fn


def with_layers(params, change):
    """``params`` with ``change(layer)`` on every expert layer, the
    module's block among them."""
    out = dict(params, layers=[change(p) if "router" in p else p
                               for p in params["layers"]])
    out["mtp"] = dict(params["mtp"], block=change(params["mtp"]["block"]))
    return out


FAULTS = {
    # name: (config changes, params changes, module patches, the stages
    # that have to miss the tolerance)
    "rotate_half_for_interleaved": (
        dict(rope_interleave=False), None, {}, ("logits", "both0", "first")),
    "no_2.5_scale": (
        dict(routed_scaling_factor=1.0), None, {}, ("logits", "both0")),
    "no_selection_bias": (
        {}, lambda p: with_layers(p, lambda l: dict(
            l, expert_bias=l["expert_bias"] * 0)), {}, ("logits",)),
    "no_shared_expert": (
        {}, lambda p: with_layers(p, lambda l: dict(l, shared=dict(
            l["shared"], down=l["shared"]["down"] * 0))), {},
        ("logits", "both0", "first")),
    "w_eh_halves_swapped": (
        {}, lambda p: dict(p, mtp=dict(p["mtp"], eh_proj=jnp.roll(
            p["mtp"]["eh_proj"], 64, axis=0))), {}, ("first", "after")),
    "no_enorm": ({}, None, {"_mtp_input": mtp_input_without("enorm")},
                 ("first", "after")),
    "no_hnorm": ({}, None, {"_mtp_input": mtp_input_without("hnorm")},
                 ("first", "after")),
    "second_position_blind_to_the_first": (
        {}, None, {"_seen": blind_second}, ("both1",)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_dropped_or_altered_mechanism_misses_the_tolerance(
        toy, sound, fault, monkeypatch):
    """Each on the sound run's own inputs: the stages it touches are off by
    more than ``FAULT``, and those it cannot touch stay within ``ATOL``
    (the fault is the mechanism, not a broken run)."""
    _, _, cfg, params = toy
    got, want = sound
    changes, alter, patches, off = FAULTS[fault]
    for name, fn in patches.items():
        monkeypatch.setattr(M, name, fn)
    bad = stages(dataclasses.replace(cfg, **changes),
                 alter(params) if alter else params,
                 fixed={k: jnp.asarray(got[k]) for k in (
                     "tokens", "draft", "chosen", "accepted")})
    err = errors(bad, want)
    for stage in off:
        assert err[stage] > FAULT, (fault, err)
    if fault in ("w_eh_halves_swapped", "no_enorm", "no_hnorm"):
        assert max(err["logits"], err["both0"], err["both1"]) < ATOL
    if fault == "second_position_blind_to_the_first":
        assert max(err["logits"], err["both0"], err["first"]) < ATOL


# -- self-drafting against the same program a token at a time -----------------------


class Oracle(M.CachedModel):
    """A drafter built to agree with the model (``sign`` +1: its draft is
    what the model itself says one position on) or never to (-1: what the
    model likes LEAST there), row by row; the module still runs, for its
    cache and its experts."""

    def __init__(self, config, sign) -> None:
        super().__init__(config)
        self.sign = jnp.asarray(sign, jnp.float32)

    def ahead(self, params, cache, tokens, positions):
        _, logits, _, _ = M.verify(self.config, params, cache,
                                   tokens[:, None], positions)
        return logits[:, 0] * self.sign[:, None]

    def first_draft(self, params, cache, ids, lengths, tokens, aux):
        cache, _, aux = super().first_draft(params, cache, ids, lengths,
                                            tokens, aux)
        return cache, self.ahead(params, cache, tokens, lengths), aux

    def draft(self, params, cache, hidden, chosen, positions, accepted, aux):
        cache, _, aux = super().draft(params, cache, hidden, chosen,
                                      positions, accepted, aux)
        last = jnp.where(accepted, chosen[:, 1], chosen[:, 0])
        return cache, self.ahead(params, cache, last,
                                 positions + 1 + accepted), aux


ROWS = (30, 12, 21, 5)
NEW = 9


GENERATORS = {}  # a drafter's compiled programs serve every test of it


def generator(toy, model=None) -> GreedyGenerator:
    _, _, cfg, params = toy
    key = (cfg, None if model is None else tuple(np.asarray(model.sign)))
    if key not in GENERATORS:
        GENERATORS[key] = GreedyGenerator(
            cfg, params, WordTokenizer(), model=model or M.CachedModel(cfg),
            gen_length=NEW, top_logits=4)
    return GENERATORS[key]


def texts():
    return [words(r) for r in prompts(8, ROWS)]


def serve(toy, model=None, new=NEW, eos=()):
    gen, steps = generator(toy, model), per_step_loop.Steps()
    gen.eos_token_ids = set(eos)
    out = gen.generate(texts(), new, observer=steps)
    return out, steps


@pytest.fixture(scope="module")
def one_at_a_time(toy):
    """The same main model from a checkpoint without the module: the loop's
    token-at-a-time path."""
    hf, state, cfg, params = toy
    plain = dataclasses.replace(cfg, num_nextn_predict_layers=0)
    assert not M.CachedModel(plain).drafts
    out, steps = serve((hf, state, plain, params))
    (loop,) = steps.decodes()  # the prefill's token, then a token a step
    assert loop["forwards"] == NEW - 1 and "drafted" not in loop
    return out


DRAFTERS = {
    "its_own_module": None,
    "never_agrees": [-1, -1, -1, -1],
    "always_agrees": [1, 1, 1, 1],
    "rows_differ": [1, -1, 1, -1],
}


@pytest.mark.parametrize("drafter", sorted(DRAFTERS))
def test_drafting_serves_the_tokens_of_one_at_a_time(toy, one_at_a_time,
                                                     drafter):
    sign = DRAFTERS[drafter]
    model = None if sign is None else Oracle(toy[2], sign)
    out, steps = serve(toy, model)
    for res, base in zip(out, one_at_a_time):
        assert res.token_ids == base.token_ids
        assert [e["position"] for e in res.trajectory] \
            == [e["position"] for e in base.trajectory]
        for e, b in zip(res.trajectory, base.trajectory):
            np.testing.assert_allclose(e["top_logits"], b["top_logits"],
                                       atol=ATOL)
    (loop,) = steps.decodes()  # one program, and the steps it ran
    accepted = [[e["accepted"] for e in res.trajectory if "accepted" in e]
                for res in out]
    # a row takes NEW - 1 tokens after the prefill's, one or two a step
    want_steps = {"never_agrees": NEW - 1, "always_agrees": NEW // 2}
    if drafter in want_steps:
        assert loop["forwards"] == want_steps[drafter]
        assert all(all(a) == (drafter == "always_agrees") and
                   any(a) == (drafter == "always_agrees") for a in accepted)
    elif drafter == "rows_differ":
        assert loop["forwards"] == NEW - 1  # the slowest row's
        assert [len(a) for a in accepted] == [NEW // 2, NEW - 1] * 2
    else:
        rate = np.mean([a for row in accepted for a in row])
        assert 0.2 < rate < 0.9, rate
        assert NEW // 2 < loop["forwards"] <= NEW - 1
    # what the observer learns: one draft a live row and step, the true count
    assert loop["committed_tokens"] == (NEW - 1) * len(ROWS)
    assert loop["drafted"] == sum(len(a) for a in accepted)
    assert loop["accepted"] == sum(sum(a) for a in accepted)
    assert loop["load"].shape == (3 * loop["forwards"], 4)
    assert steps.opened[1]["tokens_real"] == 2 * len(ROWS)


def drafting(toy, drafter):
    sign = DRAFTERS[drafter]
    return generator(toy, None if sign is None else Oracle(toy[2], sign))


@pytest.mark.parametrize("drafter, case", [
    (d, c) for d in sorted(DRAFTERS) for c in per_step_loop.CASES
    if d == "its_own_module" or c != "a_padding_row"])
def test_the_loop_gives_what_the_hosts_loop_gave(toy, drafter, case):
    """The decode loop on the device against a program a step
    (``tests/per_step_loop.py``), two positions a row: both latent caches
    carried through the loop, a row's count of committed tokens and
    ``finished`` on the device as the host mirrored them."""
    seen = per_step_loop.check_case(case, drafting(toy, drafter), texts(),
                                    NEW, atol=ATOL)
    if seen["done"] is not None:
        steps = seen["steps"]
        assert seen["done"]["drafted"] >= len(steps)
        assert seen["done"]["load"].shape == (3 * len(steps), 4)
        if drafter == "always_agrees" and case == "whole_budget":
            assert len(steps) == NEW // 2
        if drafter == "never_agrees" and case == "whole_budget":
            assert len(steps) == NEW - 1 and seen["done"]["accepted"] == 0


@pytest.mark.parametrize("ends", ["budget_on_a_steps_first_token",
                                  "eos_a_steps_first_token",
                                  "eos_a_steps_second_token"])
def test_a_row_ends_inside_a_pair_on_the_device_as_on_the_host(
        toy, one_at_a_time, ends):
    """Every draft accepted: an even budget ends on a step's first token
    with the second one chosen and dropped; an end-of-sequence id as a
    step's first token drops its accepted second, and one as its second
    is kept and ends the row."""
    gen = drafting(toy, "always_agrees")
    base = one_at_a_time[0].token_ids
    at = {"budget_on_a_steps_first_token": None,
          "eos_a_steps_first_token": 1, "eos_a_steps_second_token": 2}[ends]
    gen.eos_token_ids = set() if at is None else {base[at]}
    seen = per_step_loop.assert_the_loop_gives_what_the_hosts_loop_gave(
        gen, texts(), NEW - (at is None), atol=ATOL)
    gen.eos_token_ids = set()
    row = seen["out"][0]
    if at is None:
        assert row.token_ids == base[:NEW - 1]
        # the last step's second token was accepted and not committed
        assert seen["done"]["committed_tokens"] \
            < seen["done"]["drafted"] + seen["done"]["accepted"]
    else:
        assert row.finished and row.token_ids == base[:base.index(base[at])]


def test_a_rejected_column_left_counted_changes_what_is_served(
        toy, one_at_a_time, monkeypatch):
    """Positions that advance by two whatever was accepted leave the
    rejected draft's column counted among the real ones."""
    monkeypatch.setattr(G, "advance", lambda positions, accepted, last:
                        jnp.minimum(positions + 2, last))
    monkeypatch.setattr(sys.modules[__name__], "GENERATORS", {})  # its own
    out, _ = serve(toy, Oracle(toy[2], [-1, -1, -1, -1]))
    assert any(res.token_ids != base.token_ids
               for res, base in zip(out, one_at_a_time))


def test_a_row_ends_inside_a_pair(toy, one_at_a_time):
    """An even budget ends on a step's first token where every draft is
    accepted; an end-of-sequence token as a step's first ends the row
    there, and one as its second is kept and ends it."""
    always = Oracle(toy[2], [1, 1, 1, 1])
    out, steps = serve(toy, always, new=NEW - 1)
    for res, base in zip(out, one_at_a_time):
        assert res.token_ids == base.token_ids[:NEW - 1]
    base = one_at_a_time[0].token_ids
    for at in (1, 2):  # a step's first token, then a step's second
        out, _ = serve(toy, always, eos=[base[at]])
        first = base.index(base[at])
        assert out[0].finished and out[0].token_ids == base[:first]
        assert len(out[0].trajectory) == first + 1


def test_prefill_then_steps_through_both_caches_equal_the_full_forward(toy):
    """The loop's trajectory against ONE causal forward of the reference
    over the prompt and the served tokens, and the reference's module over
    the same: main logits at every committed position, the drafter's
    behind every draft, the experts, and the walk the reference derives."""
    hf, state, cfg, _ = toy
    out, steps = serve(toy)
    for row, res in zip(prompts(8, ROWS), out):
        traj, n = res.trajectory, len(row)
        served = [e["token"] for e in traj]
        assert res.token_ids == served and len(served) == NEW
        assert [e["position"] for e in traj] == list(range(n - 1, n - 1 + NEW))
        ids = np.concatenate([row, served[:-1]]).astype(np.int32)
        want = reference(hf, state, ids, next_token=served[-1])
        for e in traj:
            z = want["logits"][e["position"]]
            assert int(z.argmax()) == e["token"]
            np.testing.assert_allclose(e["top_logits"], z[e["top_ids"]],
                                       atol=ATOL)
            assert same_sets(e["experts"][:, -1:] if e["kind"] == "decode"
                             else e["experts"],
                             want["top_e"][:, e["position"]:e["position"] + 1]
                             if e["kind"] == "decode"
                             else want["top_e"][:, :n])
            if "draft" in e and e["draft"]["position"] < len(ids):
                d = e["draft"]
                zd = want["draft_logits"][d["position"]]
                assert int(zd.argmax()) == d["token"]
                np.testing.assert_allclose(d["top_logits"],
                                           zd[d["top_ids"]], atol=ATOL)
        drafts = {i: int(z.argmax())
                  for i, z in enumerate(want["draft_logits"])}
        walk, n_steps = ref.accept_walk(list(row) + served, drafts, n)
        mine = [(e["position"], e["accepted"]) for e in traj
                if "accepted" in e]
        assert mine == walk and n_steps == len(mine)
        assert all(e["drafted"] == drafts[e["position"] - 1]
                   for e in traj if "accepted" in e)


def test_the_accept_walk():
    tokens = [5, 6, 7, 8, 9, 10, 11]  # a prompt of 2, five generated
    right = {i: tokens[i + 2] for i in range(5)}
    assert ref.accept_walk(tokens, right, 2) == (
        [(2, True), (4, True)], 2)
    assert ref.accept_walk(tokens, {i: 0 for i in range(5)}, 2) == (
        [(2, False), (3, False), (4, False), (5, False)], 4)
    # the last step's second token would be one too many: still a step
    assert ref.accept_walk(tokens[:6], right, 2) == (
        [(2, True), (4, True)], 2)


# -- the shares ------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["ragged_dot", "megablox"])
def test_the_two_chips_shares_add_up_to_the_uncut_layer(impl, monkeypatch):
    """The routed parts of both chips' shares, plus the shared expert
    counted ONCE, are the uncut reference layer (the 2.5 among them)."""
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
    top_e, top_w = dots3_note.route(cfg, p, x)
    np.testing.assert_allclose(np.asarray(top_w).sum(-1), 2.5, rtol=1e-6)
    for first in (0, 8):
        part = dict(p, gate_up=p["gate_up"][first:first + 8],
                    down=p["down"][first:first + 8])
        y, _ = expert_layer.routed_experts(part, x, valid, top_e, top_w,
                                       (first, 8), cfg.dtype)
        total = total + y
        routed, _, _ = ref.moe(hf, {**w, **{
            k: w[k][first:first + 8] for k in ("gate", "up", "down")}}, x,
            (first, 8), shared=False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(routed),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_a_share_is_not_the_uncut_model(toy):
    hf, state, cfg, params = toy
    (row,) = prompts(4, (13,))
    logits = M.prefill(cfg, params, *padded([row], 16), 32)[1]
    want = reference(hf, state, row, [12], next_token=0)
    np.testing.assert_allclose(np.asarray(logits), want["logits"], atol=ATOL)
    uncut = reference(hf, dict(state), row, [12], next_token=0,
                      experts_held=(8, 8))["logits"]
    np.testing.assert_array_equal(uncut, want["logits"])


# -- the loader and the configuration ------------------------------------------------


def test_params_hold_only_what_is_held_and_the_head_once(tmp_path):
    dirs = family.write_checkpoints(str(tmp_path), CONFIG, 11)
    with open(os.path.join(dirs["jailbreak"], "config.json")) as f:
        hf = json.load(f)
    assert hf["n_routed_experts"] == 16 and hf["num_hidden_layers"] == 3
    cfg = M.JoyaiLlmFlashConfig.from_hf(hf, experts_held=EXPERTS)
    asked = []
    with checkpoints.checkpoint_reader(dirs["jailbreak"]) as get:
        def spy(name):
            asked.append(name)
            return get(name)
        params = M.params_from_state(spy, cfg)
    experts = {int(n.split("experts.")[1].split(".")[0]) for n in asked
               if ".experts." in n}
    assert experts == set(range(8, 16))
    assert asked.count("model.embed_tokens.weight") == 1 \
        and asked.count("lm_head.weight") == 1
    assert len(params["layers"]) == 3 and "router" not in params["layers"][0]
    block = params["mtp"]["block"]
    assert block["gate_up"].shape == (8, 64, 64)
    assert block["router"].shape == (64, 16)
    assert block["expert_bias"].dtype == jnp.float32
    assert params["mtp"]["eh_proj"].shape == (128, 64)
    assert {n.split(".")[3] for n in asked if n.startswith("model.layers.3.")
            } >= {"enorm", "hnorm", "eh_proj", "shared_head", "self_attn"}


def test_every_model_number_comes_from_the_checkpoints_config():
    hf = family.published_model(CONFIG)
    cfg = M.JoyaiLlmFlashConfig.from_hf(hf)
    for key, value in hf.items():
        if hasattr(cfg, key):
            assert getattr(cfg, key) == value, key
    assert cfg.dtype == jnp.float32 and cfg.held == (0, 16)
    g = cfg.geometry
    assert (g.heads, g.r_q, g.r_kv, g.nope, g.rope, g.v) == (4, 32, 16, 8, 8,
                                                             8)
    assert g.interleave and not g.rescale_to and g.theta == 32e6
    real = M.JoyaiLlmFlashConfig()
    assert (real.geometry.r_q, real.geometry.heads, real.n_routed_experts,
            real.routed_scaling_factor) == (1536, 32, 256, 2.5)


@pytest.mark.parametrize("changes, says", [
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "n_group"),
    ({"scoring_func": "softmax"}, "sigmoid"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"num_nextn_predict_layers": 2}, "one drafted token"),
    ({"first_k_dense_replace": 3}, "expert layer"),
    ({"qk_head_dim": 24}, "qk_head_dim"),
    ({"hidden_act": "gelu"}, "silu")])
def test_what_the_architecture_cannot_express_is_refused(changes, says):
    with pytest.raises(ValueError, match=says):
        M.JoyaiLlmFlashConfig.from_hf(dict(family.published_model(CONFIG),
                                           **changes))


def test_both_families_import_one_latent_attention():
    from semantic_router_tpu.models import latent_attention

    for module in (M, dots3_note):
        assert module.absorbed is latent_attention.absorbed
        assert module.keys_values is latent_attention.keys_values
        assert module.Geometry is latent_attention.Geometry


def test_the_interleaved_pairing_is_the_references(toy):
    """``rotate_back`` under ``interleave`` gives the reference's rotation
    in the order evens-then-odds: the same scores."""
    from semantic_router_tpu.models import latent_attention as L

    g = toy[2].geometry
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
            for _ in range(2))
    at = jnp.arange(6) * 37
    cos, sin = L.tables(g, at, 256)
    mine = [L.rotate_back(x, cos, sin, 0, True) for x in (q, k)]
    want = [ref.rope(x, at, g.theta) for x in (q, k)]
    for a, b in zip(mine, want):
        np.testing.assert_allclose(
            np.asarray(a), np.concatenate([b[:, 0::2], b[:, 1::2]], -1),
            atol=1e-5)
    np.testing.assert_allclose(np.asarray(mine[0] @ mine[1].T),
                               np.asarray(want[0] @ want[1].T), atol=1e-5)


# -- the counters, the marker and the engine's batcher ---------------------------------


def test_the_marker_and_the_counters_carry_the_drafts():
    import unittest.mock as mock

    from semantic_router_tpu.observability import batchtrace
    from semantic_router_tpu.observability.metrics import MetricsRegistry
    from semantic_router_tpu.observability.runtimestats import RuntimeStats

    facts = {}

    def span(name, **kw):
        facts.update(kw, name=name)
        return contextlib.nullcontext()

    load = np.ones((3, 4), np.float32)
    with mock.patch.object(batchtrace, "trace_span", span):
        batchtrace.gen_forward("gen:t", "gen.decode", load, drafts=(4, 3, 7))
    assert (facts["drafted"], facts["accepted"], facts["committed_tokens"]) \
        == (4, 3, 7) and facts["layers"] == 3
    with mock.patch.object(batchtrace, "trace_span", span):
        facts.clear()
        batchtrace.gen_forward("gen:t", "gen.decode", load)
    assert "drafted" not in facts and "committed_tokens" not in facts
    stats = RuntimeStats(MetricsRegistry())
    stats.record_generation("t", "gen.decode", committed_tokens=7,
                            drafted=4, accepted=3)
    stats.record_generation("t", "gen.decode", committed_tokens=4)
    assert stats.gen_drafts.get(task="t") == 4
    assert stats.gen_drafts_accepted.get(task="t") == 3
    assert stats.gen_tokens.get(task="t") == 11


@pytest.fixture
def engine(tmp_path):
    """A toy ``joyai_llm_flash`` checkpoint on disk, loaded the way
    ``build_engine`` loads a ``kind: generative`` task holding its share."""
    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine
    from semantic_router_tpu.runtime.bootstrap import build_generator

    dirs = family.write_checkpoints(str(tmp_path), CONFIG, 11)
    with open(os.path.join(dirs["jailbreak"], "config.json")) as f:
        hf = json.load(f)
    gen, adapters = build_generator(
        {"generation": {"gen_length": 6}, "experts_held": list(EXPERTS)},
        hf, dirs["jailbreak"], WordTokenizer(), None)
    assert isinstance(gen, GreedyGenerator) and adapters == {}
    assert gen.drafts and gen.config.held == EXPERTS
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=4, max_wait_ms=50.0, seq_len_buckets=[64]))
    eng.register_generative("guard", gen)
    yield eng
    eng.shutdown()


def test_guard_classify_goes_through_the_batcher(engine):
    from concurrent.futures import ThreadPoolExecutor

    stats = engine._runtime_stats  # the process's: read what this test adds
    counters = (stats.gen_drafts, stats.gen_drafts_accepted, stats.gen_tokens)
    before = [c.get(task="guard") for c in counters]
    rows = prompts(12, (33, 9, 21))
    with ThreadPoolExecutor(3) as pool:
        together = list(pool.map(
            lambda r: engine.generate("guard", [words(r)], 6)[0], rows))
    alone = [engine.generate("guard", [words(r)], 6)[0] for r in rows]
    for a, b in zip(together, alone):
        assert a.token_ids == b.token_ids and len(a.token_ids) == 6
    verdict = engine.guard_classify("guard", words(rows[0]))
    assert verdict.safety in ("Safe", "Unsafe", "Controversial")
    drafted, accepted, tokens = (
        c.get(task="guard") - was for c, was in zip(counters, before))
    assert 0 <= accepted <= drafted and drafted >= 7 * 3
    # a row's 6 tokens: the prefill's and the steps', one or two each
    assert tokens == 7 * 6


def test_the_loop_is_one_program_of_a_generation_through_the_engine(
        engine, seen):
    """``engine.guard_classify`` behind the batcher: ONE ``gen.decode``
    step, marker and program a generation, whose ``forwards`` is the steps
    the device ran and whose drafts and tokens are the sums a program a
    step gave for the same prompt; ``engine.gen.done`` totals them."""
    from semantic_router_tpu.models.generate import build_guard_prompt

    rs = engine._runtime_stats
    flavours = ("gen.prefill", "gen.decode", "gen.denoise", "gen.commit")

    def counts():
        by_flavour = {(c, v): getattr(rs, c).get(task="guard", flavour=v)
                      for c in ("gen_forwards", "gen_programs")
                      for v in flavours}
        return dict(by_flavour, **{c: getattr(rs, c).get(task="guard")
                                   for c in ("gen_drafts", "gen_tokens",
                                             "gen_drafts_accepted")})

    text = words(prompts(12, (9,))[0])
    before = counts()
    engine.guard_classify("guard", text)
    after = counts()
    moved = {k: after[k] - before[k] for k in after}
    # the same prompt, a program a step
    gen = engine._tasks["guard"].generator
    enc = gen.tokenizer.encode(build_guard_prompt(text))
    assert len(enc) <= 64
    ref = per_step_loop.Steps()
    per_step_loop.per_step_twin(gen).generate(
        [], gen.gen_length, encodings=[enc], bucket=64, padded_rows=1,
        observer=ref)
    want = ref.decodes()
    assert 3 <= len(want) <= 5  # 6 tokens: the prefill's, then 1 or 2 a step

    steps = [f for n, f in seen if n == "engine.step"]
    assert [s["flavour"] for s in steps] == ["gen.prefill", "gen.decode"]
    assert steps[1]["tokens_real"] == 2 and "block" not in steps[1]
    marks = [f for n, f in seen if n == "engine.gen.forward"]
    assert [m["flavour"] for m in marks] == ["gen.prefill", "gen.decode"]
    loop = marks[1]
    assert loop["forwards"] == len(want)
    assert loop["layers"] == 3 * len(want)  # two expert layers, the module
    for fact in ("drafted", "accepted", "committed_tokens"):
        assert loop[fact] == sum(w[fact] for w in want), fact
    assert loop["committed_tokens"] == 5 and loop["drafted"] == len(want)
    assert loop["pairs"] == sum(int(w["load"][:, 1].sum()) for w in want)
    (done,) = [f for n, f in seen if n == "engine.gen.done"]
    assert done["forwards"] == 1 + len(want) and done["tokens"] == 6
    assert [f["after"] for n, f in seen if n == "engine.gen.turn"] == \
        ["gen.prefill", "gen.decode"]
    for counter, decode in (("gen_forwards", len(want)),
                            ("gen_programs", 1)):
        assert {v: moved[counter, v] for v in flavours} == {
            "gen.prefill": 1, "gen.decode": decode, "gen.denoise": 0,
            "gen.commit": 0}, counter
    assert moved["gen_drafts"] == loop["drafted"]
    assert moved["gen_drafts_accepted"] == loop["accepted"]
    assert moved["gen_tokens"] == 6

