"""What the generative decoders share, tested where it lives and not through
the first model that needed it: ``models/experts.py`` (``feed_forward``,
``expert_ids``), ``models/mapped_prefill.py`` (the driver of a prefill in
groups), ``models/cached_model.py`` (the one cached-model class) and
``models/checkpoints.py``.  The expert layer itself
(``routed_experts``, the grouped matmul) is held by ``test_sdar_moe.py``,
the group rule's table by ``test_lfm2_moe.py``."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from semantic_router_tpu.models import (
    cached_model,
    checkpoints,
    experts,
    mapped_prefill,
)

# the four token-at-a-time decoders: (their toy's test module, where their
# ``moe`` lives: joyai_llm_flash's expert half is dots3_note's)
DECODERS = {"lfm2_moe": "lfm2_moe", "dots3_note": "dots3_note",
            "laguna": "laguna", "joyai_llm_flash": "dots3_note"}
TOYS = {}


def toy(name):
    """``(cfg, params, moe)`` of a decoder's toy test configuration."""
    if name not in TOYS:
        built = importlib.import_module("test_" + name).variant()
        moe = importlib.import_module(
            "semantic_router_tpu.models." + DECODERS[name]).moe
        TOYS[name] = built[-2], built[-1], moe
    return TOYS[name]


# -- the second half of a layer ------------------------------------------------------


@pytest.mark.parametrize("sparse", [False, True], ids=["mlp", "moe"])
@pytest.mark.parametrize("name", sorted(DECODERS))
def test_a_group_as_one_row_is_its_rows_feed_forward(name, sparse):
    """``feed_forward(..., as_one_row=True)`` of a prefill's group ``[G, S,
    H]`` is the same layer on the same tokens as the rows' shape gives — a
    token's feed-forward does not know its row — with the router's choice
    back ``[G, S, k]``, a padding token routed nowhere, and a dense layer
    reporting no experts."""
    cfg, params, moe = toy(name)
    i, p = next((i, p) for i, p in enumerate(params["layers"])
                if cfg.is_sparse(i) == sparse)
    G, S, H = 3, 8, p["norm2"].shape[0]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((G, S, H)),
                    jnp.float32)
    valid = jnp.arange(S)[None, :] < jnp.asarray([8, 5, 0])[:, None]
    rows = experts.feed_forward(cfg, sparse, p, x, valid, moe)
    one = experts.feed_forward(cfg, sparse, p, x, valid, moe,
                               as_one_row=True)
    assert one[0].shape == (G, S, H)
    np.testing.assert_allclose(np.asarray(one[0]), np.asarray(rows[0]),
                               rtol=1e-5, atol=1e-5)
    if not sparse:
        assert rows[1:] == (None, None) and one[1:] == (None, None)
        return
    assert one[1].shape == (G, S, one[1].shape[-1])
    assert (np.asarray(one[1]) == np.asarray(rows[1])).all()
    np.testing.assert_array_equal(np.asarray(one[2]), np.asarray(rows[2]))
    # load[1]: the pairs computed are the valid tokens' alone
    held = (np.asarray(one[1]) >= cfg.held[0]) \
        & (np.asarray(one[1]) < sum(cfg.held))
    assert float(one[2][1]) == (held & np.asarray(valid)[..., None]).sum()


@pytest.mark.parametrize("n, dtype", [(8, jnp.uint8), (256, jnp.uint8),
                                      (257, jnp.int32)])
def test_expert_ids_are_bytes_where_they_fit(n, dtype):
    ids = experts.expert_ids(jnp.asarray([[0, n - 1]], jnp.int32), n)
    assert ids.dtype == dtype and int(ids[0, 1]) == n - 1


# -- a prefill in groups -------------------------------------------------------------

NAMES = ("state", "pairs", "logits", "experts", "load", "marks")


def rows_fn(ids, lengths):
    """A stand-in for a model's ``_prefill_rows``: a cache leaf and a list of
    pairs of them by row, logits by row, a layer-major aux entry, a load a
    call and a row-major aux entry — each a function of its own row."""
    G, S = ids.shape
    x = ids.astype(jnp.float32) * (jnp.arange(S) < lengths[:, None])
    state = x[:, :, None] * jnp.ones(3)
    pairs = [(x + 1, x + 2), (x * 2, x * 3)]
    logits = x.sum(1, keepdims=True) * jnp.arange(5.0)
    layer_major = jnp.stack([ids, ids * 2])[..., None]  # [2, G, S, 1]
    total = lengths.sum().astype(jnp.float32)
    load = jnp.stack([jnp.stack([total, total, total, total * 0 + G]),
                      jnp.stack([total * 2, total, total * 0 + 1,
                                 total * 0 + 1])])
    return state, pairs, logits, layer_major, load, lengths * 7


@pytest.mark.parametrize("rows, group", [(4, 1), (4, 2), (4, 4), (6, 2),
                                         (6, 3)])
def test_the_driver_maps_groups_and_puts_the_batch_back(rows, group):
    ids = jnp.asarray(np.random.default_rng(1).integers(1, 9, (rows, 6)),
                      jnp.int32)
    lengths = jnp.asarray([6, 3, 0, 5, 1, 2][:rows], jnp.int32)
    cache, logits, aux = jax.jit(
        lambda i, n: mapped_prefill.prefill_in_groups(
            rows_fn, NAMES, ("experts",), group, i, n))(ids, lengths)
    want = rows_fn(ids, lengths)
    assert sorted(cache) == ["lengths", "pairs", "state"]
    assert cache["lengths"].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(cache["lengths"]),
                                  np.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(cache["state"]),
                                  np.asarray(want[0]))
    for got, pair in zip(cache["pairs"], want[1]):
        for a, b in zip(got, pair):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want[2]))
    assert sorted(aux) == ["experts", "load", "marks"]
    np.testing.assert_array_equal(np.asarray(aux["experts"]),
                                  np.asarray(want[3]))  # layers first again
    np.testing.assert_array_equal(np.asarray(aux["marks"]),
                                  np.asarray(want[5]))
    # the groups' loads: busiest the max, pairs and touched summed, the
    # ratio their mean (``experts.sum_loads``)
    per_group = np.asarray(lengths).reshape(-1, group).sum(1)
    np.testing.assert_allclose(
        np.asarray(aux["load"]),
        [[per_group.max(), per_group.sum(), per_group.sum(), group],
         [2 * per_group.max(), per_group.sum(), len(per_group), 1]])


# -- the one cached-model class ------------------------------------------------------


def test_a_cached_decoder_hands_its_config_to_the_models_functions():
    calls = []

    def fn(name):
        return lambda *a: calls.append((name,) + a) or name

    model = cached_model.CachedDecoder(
        "cfg", fn("prefill"), fn("decode"), cache_kinds=("a", "b"),
        drafter=(fn("first_draft"), fn("verify"), fn("draft")))
    assert model.drafts and model.config == "cfg"
    assert model.prefill("p", "ids", "n", 64, "task") == "prefill"
    assert model.decode("p", "c", "t", "pos", "task") == "decode"
    assert model.first_draft("p", "c", "ids", "n", "t", "aux") \
        == "first_draft"
    assert model.verify("p", "c", "t", "pos", "task") == "verify"
    assert model.draft("p", "c", "h", "ch", "pos", "acc", "aux") == "draft"
    assert calls == [
        ("prefill", "cfg", "p", "ids", "n", 64),
        ("decode", "cfg", "p", "c", "t", "pos"),
        ("first_draft", "cfg", "p", "c", "ids", "n", "t", "aux"),
        ("verify", "cfg", "p", "c", "t", "pos"),
        ("draft", "cfg", "p", "c", "h", "ch", "pos", "acc", "aux")]


def test_a_cached_decoder_answers_none_for_what_it_has_not():
    """No sizes: the rows are not mapped.  No attention layers: the flash
    call is handed no lengths.  No drafter.  And ``cache_bytes`` reports the
    kinds the cache at hand holds."""
    model = cached_model.CachedDecoder("cfg", None, None,
                                       cache_kinds=("latent", "draft"))
    assert not model.drafts
    assert model.rows_per_group({}, 8, 512, 576) is None
    assert model.attn_tiles(np.asarray([3, 5]), 512) is None
    cache = {"latent": [jnp.zeros((2, 4, 8), jnp.bfloat16)] * 3,
             "lengths": jnp.zeros(2, jnp.int32)}
    assert model.cache_bytes(cache) == {"latent": 3 * 2 * 4 * 8 * 2}


@pytest.mark.parametrize("name", ["lfm2_moe", "dots3_note", "laguna"])
def test_a_mapped_models_group_is_the_rule_at_its_sizes(name, monkeypatch):
    """``CachedModel(cfg).rows_per_group`` is ``rows_per_group`` at the
    model's own ``_row_bytes`` / ``_cache_bytes``: every row where the
    backend reports no limit, one where the device holds the weights and
    little more, and the tiles are counted over the model's layers."""
    module = importlib.import_module("semantic_router_tpu.models." + name)
    cfg, params, _ = toy(name)
    model = module.CachedModel(cfg)
    monkeypatch.setattr(mapped_prefill, "device_bytes", lambda: None)
    assert model.rows_per_group(params, 4, 16, 64) == 4
    resident = mapped_prefill.tree_bytes(params) \
        + module._cache_bytes(cfg, 4, 64)
    monkeypatch.setattr(
        mapped_prefill, "device_bytes",
        lambda: resident + mapped_prefill.SPARE_BYTES
        + 2 * module._row_bytes(cfg, 16))
    assert model.rows_per_group(params, 4, 16, 64) == 2
    monkeypatch.setattr(mapped_prefill, "device_bytes", lambda: resident)
    assert model.rows_per_group(params, 4, 16, 64) == 1
    visited, grid = model.attn_tiles(np.asarray([16, 3, 0, 9]), 16)
    assert 0 < visited <= grid


# -- reading a checkpoint -------------------------------------------------------------


@pytest.mark.parametrize("name, want", [
    ("bfloat16", jnp.bfloat16), ("torch.bfloat16", jnp.bfloat16),
    ("float16", jnp.float16), ("float32", jnp.float32),
    ("float64", jnp.float32)])
def test_torch_dtype_of(name, want):
    assert checkpoints.torch_dtype_of(name) == want


class Config:
    dtype = jnp.float32


def tensors():
    rng = np.random.default_rng(3)
    out = {f"ff.{n}.weight": rng.standard_normal((6, 4)).astype(np.float32)
           for n in ("gate_proj", "up_proj")}
    out["ff.down_proj.weight"] = rng.standard_normal((4, 6)).astype(
        np.float32)
    for e in range(5):
        for n, shape in (("w1", (6, 4)), ("w3", (6, 4)), ("w2", (4, 6))):
            out[f"ex.{e}.{n}.weight"] = rng.standard_normal(shape).astype(
                np.float32)
    return out


def test_a_swiglus_matrices_are_gate_up_and_down_transposed():
    state = tensors()
    got = checkpoints.swiglu_matrices(state.__getitem__, Config, "ff.")
    np.testing.assert_array_equal(
        np.asarray(got["gate_up"]),
        np.concatenate([state["ff.gate_proj.weight"].T,
                        state["ff.up_proj.weight"].T], -1))
    np.testing.assert_array_equal(np.asarray(got["down"]),
                                  state["ff.down_proj.weight"].T)


def test_only_the_experts_held_are_read():
    state, asked = tensors(), []

    def get(name):
        asked.append(name)
        return state[name]

    got = checkpoints.swiglu_matrices(get, Config, "ex.",
                                      ("w1", "w3", "w2"), experts=(1, 3))
    assert got["gate_up"].shape == (3, 4, 12) and got["down"].shape == (
        3, 6, 4)
    assert sorted({int(n.split(".")[1]) for n in asked}) == [1, 2, 3]
    np.testing.assert_array_equal(np.asarray(got["gate_up"][2, :, 6:]),
                                  state["ex.3.w3.weight"].T)
    np.testing.assert_array_equal(np.asarray(got["down"][0]),
                                  state["ex.1.w2.weight"].T)


@pytest.mark.parametrize("sliced", [False, True])
def test_tensor_rows_reads_a_slice_where_the_reader_can(sliced):
    table = np.arange(40.0).reshape(10, 4)
    whole = []

    def get(name):
        whole.append(name)
        return table

    if sliced:
        get.rows = lambda name, first, count: table[first:first + count]
    got = checkpoints.tensor_rows(get, "embed", 3, 4)
    np.testing.assert_array_equal(got, table[3:7])
    assert whole == ([] if sliced else ["embed"])
