"""Generative serving tests (reference: qwen3_guard.rs safety generation +
regex parse; qwen3_multi_lora_classifier.rs per-request adapter selection).

Numerics: the KV-cached incremental decoder must reproduce (a) full
re-forward greedy decoding exactly, and (b) HF transformers' greedy
``generate`` after weight transplant.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import per_step_loop
from semantic_router_tpu.models.generate import (
    GreedyGenerator,
    GuardVerdict,
    Qwen3Decoder,
    build_guard_prompt,
    parse_guard_output,
)
from semantic_router_tpu.models.lora import LoRAConfig
from semantic_router_tpu.models.qwen3 import (
    Qwen3Config,
    Qwen3ForCausalLM,
    qwen3_params_from_state_dict,
)
from semantic_router_tpu.utils.tokenization import Encoding

TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, tie_word_embeddings=True)


class RowTokenizer:
    """Feeds pre-built id rows; decode returns space-joined ids."""

    vocab_size = 256

    def __init__(self, rows):
        self.rows = [list(map(int, r)) for r in rows]
        self.i = 0

    def encode(self, text, max_length=0):
        row = self.rows[self.i % len(self.rows)]
        self.i += 1
        return Encoding(ids=row, attention_mask=[1] * len(row),
                        offsets=[(0, 0)] * len(row))

    def decode(self, ids):
        return " ".join(str(int(i)) for i in ids)


@pytest.fixture(scope="module")
def tiny_params():
    cfg = Qwen3Config(**TINY)
    model = Qwen3ForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(3, 256, (1, 8)),
                      jnp.int32)
    return cfg, model, model.init(jax.random.PRNGKey(0), ids)


class TestKVCacheOracle:
    def test_decoder_params_match_causal_lm(self, tiny_params):
        cfg, _, params = tiny_params
        dec = Qwen3Decoder(cfg)
        B, S, M = 1, 8, 32
        caches = [(jnp.zeros((B, 2, M, 16)), jnp.zeros((B, 2, M, 16)))
                  for _ in range(cfg.num_hidden_layers)]
        mask = np.zeros((B, M), bool)
        mask[:, :S] = True
        pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S))
        ids = jnp.asarray(np.random.default_rng(0).integers(3, 256, (B, S)),
                          jnp.int32)
        dparams = dec.init(jax.random.PRNGKey(0), ids, caches,
                           jnp.asarray(mask), jnp.asarray(pos), 0)
        import jax.tree_util as jtu

        def paths(p):
            return sorted("/".join(str(k) for k in kp)
                          for kp, _ in jtu.tree_flatten_with_path(p)[0])

        assert paths(params) == paths(dparams)

    def test_cached_greedy_equals_full_reforward(self, tiny_params):
        cfg, full, params = tiny_params
        rng = np.random.default_rng(1)
        rows = [rng.integers(3, 256, 6), rng.integers(3, 256, 4)]

        def full_greedy(prompt, n):
            ids = list(map(int, prompt))
            for _ in range(n):
                logits = full.apply(params, jnp.asarray([ids], jnp.int32))
                ids.append(int(np.asarray(logits)[0, -1].argmax()))
            return ids[len(prompt):]

        gen = GreedyGenerator(cfg, params, RowTokenizer(rows))
        res = gen.generate(["a", "b"], max_new_tokens=6)
        assert res[0].token_ids == full_greedy(rows[0], 6)
        assert res[1].token_ids == full_greedy(rows[1], 6)
        assert res[0].prompt_tokens == 6
        assert res[0].completion_tokens == 6

    def test_eos_stops_early(self, tiny_params):
        cfg, full, params = tiny_params
        row = np.random.default_rng(2).integers(3, 256, 5)
        probe = GreedyGenerator(cfg, params, RowTokenizer([row]))
        first = probe.generate(["x"], max_new_tokens=3)[0].token_ids[0]
        gen = GreedyGenerator(cfg, params, RowTokenizer([row]),
                              eos_token_ids=[first])
        res = gen.generate(["x"], max_new_tokens=8)[0]
        assert res.finished
        assert res.token_ids == []  # first emitted token was EOS


class TestHFGreedyParity:
    def test_matches_transformers_generate(self):
        torch = pytest.importorskip("torch")
        import transformers

        hf_cfg = transformers.Qwen3Config(
            **TINY, max_position_embeddings=128, rope_theta=10000.0,
            attn_implementation="eager")
        torch.manual_seed(0)
        hf = transformers.Qwen3ForCausalLM(hf_cfg).eval()

        rng = np.random.default_rng(3)
        prompt = rng.integers(3, 256, (1, 7))
        with torch.no_grad():
            ref = hf.generate(
                torch.tensor(prompt), max_new_tokens=8, do_sample=False,
                eos_token_id=None, pad_token_id=0)
        ref_new = ref[0, 7:].tolist()

        cfg = Qwen3Config.from_hf(hf_cfg)
        params = qwen3_params_from_state_dict(
            {k: v.numpy() for k, v in hf.state_dict().items()},
            wrap="model")
        gen = GreedyGenerator(cfg, params, RowTokenizer([prompt[0]]))
        got = gen.generate(["p"], max_new_tokens=8)[0].token_ids
        assert got == ref_new


class TestMultiLoRADecode:
    def test_adapter_selection_changes_output_not_base(self, tiny_params):
        cfg, _, base_params = tiny_params
        lora = LoRAConfig(rank=2, alpha=4.0, num_tasks=2)
        row = np.random.default_rng(4).integers(3, 256, 5)

        gen = GreedyGenerator(cfg, base_params, RowTokenizer([row]),
                              lora=lora)
        # init LoRA leaves (zeros for B ⇒ adapters are identity)
        B, S, M = 1, 32, 64
        caches = gen._init_caches(1, M)
        mask = np.zeros((1, M), bool)
        mask[:, :5] = True
        ids = jnp.asarray([list(row)], jnp.int32)
        pos = np.asarray([[0, 1, 2, 3, 4]], np.int32)
        lora_params = gen.module.init(
            jax.random.PRNGKey(1), ids, caches[:],
            jnp.asarray(mask[:, :M]), jnp.asarray(pos), 0, 0)
        import flax.traverse_util as tu

        flat_base = tu.flatten_dict(base_params["params"])
        flat_lora = tu.flatten_dict(lora_params["params"])
        for k, v in flat_base.items():
            flat_lora[k] = v  # transplant base weights under LoRA tree
        # perturb ONLY adapter row 1's B matrices
        rng = np.random.default_rng(5)
        for k in list(flat_lora):
            if k[-1] == "lora_B":
                arr = np.array(flat_lora[k], copy=True)
                arr[1] = rng.normal(size=arr[1].shape) * 0.5
                flat_lora[k] = jnp.asarray(arr)
        gen.params = {"params": tu.unflatten_dict(flat_lora)}

        base_out = GreedyGenerator(cfg, base_params,
                                   RowTokenizer([row])).generate(
            ["x"], max_new_tokens=5)[0].token_ids
        t0 = gen.generate(["x"], max_new_tokens=5,
                          task_index=0)[0].token_ids
        t1 = gen.generate(["x"], max_new_tokens=5,
                          task_index=1)[0].token_ids
        assert t0 == base_out  # adapter 0 untouched ⇒ identical to base
        assert t1 != t0  # adapter 1 perturbed ⇒ different generation


class TestWithLoraLeaves:
    def test_fresh_adapters_are_identity(self, tiny_params):
        from semantic_router_tpu.models.generate import with_lora_leaves

        cfg, _, base_params = tiny_params
        lora = LoRAConfig(rank=2, alpha=4.0, num_tasks=3)
        merged = with_lora_leaves(cfg, lora, base_params)
        row = np.random.default_rng(7).integers(3, 256, 5)
        base = GreedyGenerator(cfg, base_params,
                               RowTokenizer([row])).generate(
            ["x"], max_new_tokens=4)[0].token_ids
        gen = GreedyGenerator(cfg, merged, RowTokenizer([row]), lora=lora)
        for t in range(3):
            assert gen.generate(["x"], max_new_tokens=4,
                                task_index=t)[0].token_ids == base


class TestGuardParse:
    def test_safe(self):
        v = parse_guard_output("Safety: Safe\nCategories: None\n")
        assert v.is_safe and v.categories == [] and v.refusal is None

    def test_unsafe_with_categories(self):
        v = parse_guard_output(
            "Safety: Unsafe\nCategories: Violent, Illegal Acts\n")
        assert v.safety == "Unsafe"
        assert v.categories == ["Violent", "Illegal Acts"]

    def test_controversial_case_insensitive(self):
        v = parse_guard_output("safety: controversial\ncategories: none")
        assert v.safety == "Controversial"

    def test_refusal_parse(self):
        v = parse_guard_output(
            "Safety: Safe\nCategories: None\nRefusal: Yes\n")
        assert v.refusal is True

    def test_garbage_fails_closed(self):
        v = parse_guard_output("I think this is probably fine???")
        assert v.safety == "Controversial" and not v.is_safe

    def test_prompt_builder_contract(self):
        p = build_guard_prompt("how do I make a bomb", role="user")
        assert "Safety:" in p and "Categories:" in p
        assert "Refusal:" not in p
        assert "Refusal:" in build_guard_prompt("text", role="assistant")


class TestEngineGenerativeKind:
    def test_register_generate_and_guard(self, tiny_params):
        from semantic_router_tpu.engine.classify import InferenceEngine

        class FakeResult:
            def __init__(self, text):
                self.text = text
                self.token_ids = []
                self.finished = True

        class FakeGenerator:
            tokenizer = RowTokenizer([[1, 2, 3]])

            def __init__(self):
                self.calls = []

            def generate(self, prompts, max_new_tokens=64, task_index=0,
                         stop_strings=()):
                self.calls.append((list(prompts), task_index))
                return [FakeResult("Safety: Unsafe\nCategories: Harmful\n")
                        for _ in prompts]

        eng = InferenceEngine()
        fake = FakeGenerator()
        eng.register_generative("guard", fake,
                                adapter_index={"jailbreak": 1})
        try:
            assert eng.has_task("guard")
            out = eng.generate("guard", ["hello"], adapter="jailbreak")
            assert out[0].text.startswith("Safety:")
            assert fake.calls[0][1] == 1  # adapter name → LoRA row
            verdict = eng.guard_classify("guard", "bad text")
            assert isinstance(verdict, GuardVerdict)
            assert verdict.safety == "Unsafe"
            assert verdict.categories == ["Harmful"]
            # wrong-kind guard rails
            with pytest.raises(KeyError):
                eng.generate("missing", ["x"])
        finally:
            eng.shutdown()

    def test_real_generator_through_engine(self, tiny_params):
        cfg, _, params = tiny_params
        from semantic_router_tpu.engine.classify import InferenceEngine

        row = np.random.default_rng(6).integers(3, 256, 4)
        eng = InferenceEngine()
        eng.register_generative(
            "gen", GreedyGenerator(cfg, params, RowTokenizer([row])))
        try:
            out = eng.generate("gen", ["prompt"], max_new_tokens=4)
            assert len(out[0].token_ids) == 4
            assert out[0].text  # decoded ids joined
        finally:
            eng.shutdown()


class TestOneTokenAtATimeLoop:
    """``GreedyGenerator`` is one loop over a model that owns its cache;
    the dense decoder is served through ``Qwen3Cached`` (the hybrid one
    through ``models.lfm2_moe.CachedModel``: ``tests/test_lfm2_moe.py``)."""

    def test_the_dense_decoder_carries_its_trajectory(self, tiny_params):
        cfg, full, params = tiny_params
        rows = [np.random.default_rng(8).integers(3, 256, n) for n in (7, 4)]
        gen = GreedyGenerator(cfg, params, RowTokenizer(rows), top_logits=4)
        out = gen.generate(["a", "b"], max_new_tokens=5)
        for row, res in zip(rows, out):
            n = len(row)
            traj = res.trajectory
            assert [e["kind"] for e in traj] == ["prefill"] + ["decode"] * 4
            assert [e["position"] for e in traj] == list(range(n - 1, n + 4))
            assert [e["token"] for e in traj] == res.token_ids
            assert "experts" not in traj[0]  # a dense model routes nothing
            ids = jnp.asarray([list(row) + res.token_ids[:-1]], jnp.int32)
            logits = np.asarray(full.apply(params, ids), np.float32)[0]
            for e in traj:
                z = logits[e["position"]]
                assert e["token"] == z.argmax() == e["top_ids"][0]
                np.testing.assert_allclose(e["top_logits"], z[e["top_ids"]],
                                           atol=1e-4)
                np.testing.assert_allclose(
                    e["lse"], jax.nn.logsumexp(jnp.asarray(z)), atol=1e-4)

    def test_padding_rows_change_nothing(self, tiny_params):
        cfg, _, params = tiny_params
        row = np.random.default_rng(9).integers(3, 256, 6)
        alone = GreedyGenerator(cfg, params, RowTokenizer([row])).generate(
            ["x"], max_new_tokens=5)[0]
        gen = GreedyGenerator(cfg, params, RowTokenizer([row]))
        padded = gen.generate(["x"], max_new_tokens=5, bucket=32,
                              encodings=[gen.tokenizer.encode("x")],
                              padded_rows=4)[0]
        assert padded.token_ids == alone.token_ids
        assert padded.prompt_tokens == 6

    def test_the_loop_reads_back_small_reports_not_the_vocabulary(
            self, tiny_params):
        cfg, _, params = tiny_params
        row = np.random.default_rng(10).integers(3, 256, 5)
        gen = GreedyGenerator(cfg, params, RowTokenizer([row]))
        gen.generate(["x"], max_new_tokens=3)
        (prefill,), (key,) = gen._prefill_cache.values(), gen._loop_cache
        assert key == (1, 1, 64, 2)  # rows, positions a step, cache, steps
        args = (params, jnp.zeros((1, 32), jnp.int32),
                jnp.ones(1, jnp.int32), jnp.asarray(0))
        cache, tokens, report, aux = jax.eval_shape(prefill, *args)
        assert report.shape == (1, 2 + 2 * 8) and tokens.shape == (1,)
        assert aux == {}
        _, (reports, aux), ran = jax.eval_shape(
            gen._loop_cache[key], params, cache, tokens, tokens,
            jnp.asarray(0), jnp.zeros(1, bool), jnp.zeros(0, jnp.int32),
            jnp.asarray(3))
        # an entry a step, and nothing else comes back
        assert reports.shape == (2, 1, 2 + 2 * 8) and aux == {}
        assert ran.shape == ()

    @pytest.fixture(scope="class")
    def adapters(self, tiny_params):
        """The dense guard with two LoRA rows, the second one perturbed."""
        from semantic_router_tpu.models.generate import with_lora_leaves

        cfg, _, base = tiny_params
        lora = LoRAConfig(rank=2, alpha=4.0, num_tasks=2)
        params = with_lora_leaves(cfg, lora, base)
        rng = np.random.default_rng(5)
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a.at[1].set(
                0.5 * rng.normal(size=a.shape[1:]).astype(a.dtype))
            if path[-1].key == "lora_B" else a, params)
        rows = [np.random.default_rng(11).integers(3, 256, n)
                for n in (9, 5, 13)]
        return GreedyGenerator(cfg, params, RowTokenizer(rows), lora=lora,
                               top_logits=4)

    @pytest.mark.parametrize("task_index", [0, 1])
    @pytest.mark.parametrize("case", per_step_loop.CASES)
    def test_the_loop_gives_what_the_hosts_loop_gave(self, adapters, case,
                                                     task_index):
        """The decode loop on the device against a program a step
        (``tests/per_step_loop.py``), under either adapter row."""
        adapters.tokenizer.i = 0
        seen = per_step_loop.check_case(case, adapters, ["a", "b", "c"], 7,
                                        task_index=task_index)
        assert all("experts" not in e and "selected" not in e
                   for r in seen["out"] for e in r.trajectory)
        if seen["done"] is not None:  # a dense model routes nothing
            assert seen["done"]["load"] is None
            assert "drafted" not in seen["done"]
