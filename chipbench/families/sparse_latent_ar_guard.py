"""The generative guard served by a sparse-attention latent decoder
(``model_type: dots3_note``: latent attention whose full layers see the keys
a learned indexer selects and whose sliding layers see a window, over one
latent cache; sparse experts behind a sigmoid router with a selection bias,
beside a shared expert; a chip's share of the experts and of the
vocabulary): the jailbreak family answered by ``engine.guard_classify``,
whose wrapped call is ``generate``.  Everything the benchmark knows of this
family is here; the plain reference is ``chipbench/reference/dots3_note.py``.
The guard template, the tokenizer's rules and the quantile draw of the
weights are ``families/blockdiff_guard.py``'s, the comparison's helpers
``families/hybrid_ar_guard.py``'s, loaded by name.

The configuration's file gives the counts HELD here (``n_routed_experts``,
``vocab_size``: both ``reduced``) and, under ``published``, the model's own;
``held`` says which (``{"experts": [first, count], "vocab": [first,
count]}``).  A checkpoint's ``config.json`` carries the published counts
(the router keeps its width), its files the held experts under their
published indices and the vocabulary's rows up to the end of the held slice;
ids, logits and the choice are over the slice.

What is compared is what the program computed on the way to its tokens.  A
served result carries its trajectory (``models/generate.py``): per forward
that chose a token the chosen id, the top logits, the log-sum-exp, the
experts chosen per layer, and the keys selected per full layer — of a
decode at the token decoded, of a prefill at a few prompt positions past
``index_topk`` that the program draws from the row's length.  The reference
runs ONE causal forward over the prompt and the served tokens: prefill and
then decoding through the latent cache against the full forward pass.

``sa_logit_rel_sq_err``, ``sa_transfer_gap_max``,
``sa_route_disagreement_share``
    as ``hybrid_ar_guard``'s ``ar_*`` three.
``sa_select_disagreement_share``
    over the decoded positions and the prefill's sampled positions, per full
    layer: the share of the program's selected keys that are not among the
    reference's at that query.
"""

from __future__ import annotations

import json
import os
import types
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from chipbench import cells

base = cells.load_module("families", "blockdiff_guard")
hybrid = cells.load_module("families", "hybrid_ar_guard")

MODEL_KEYS = (
    "model_type", "apply_mla_qkv_lora_rescale", "attention_bias",
    "attention_gate_type", "first_k_dense_replace", "hidden_act",
    "hidden_size", "index_head_dim", "index_n_heads", "index_topk",
    "intermediate_size", "kv_lora_rank", "layer_types",
    "max_position_embeddings", "moe_intermediate_size", "moe_layer_freq",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "rms_norm_eps", "rope_scaling", "rope_theta",
    "routed_scaling_factor", "scoring_func", "sliding_window_size",
    "swa_attention_gate_type", "swa_kv_lora_rank", "swa_num_attention_heads",
    "swa_num_key_value_heads", "swa_q_lora_rank", "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim", "swa_rope_theta", "swa_v_head_dim",
    "tie_word_embeddings", "topk_method", "v_head_dim", "vocab_size",
    "torch_dtype")

prompt_ids = base.prompt_ids


def published_model(config: Dict[str, Any]) -> Dict[str, Any]:
    """The model's numbers with the PUBLISHED counts of experts and of the
    vocabulary: a checkpoint's ``config.json``, and what the reference is
    given beside the shares."""
    pub = config.get("published") or {}
    return dict(config["model"], **{k: pub[k] for k in (
        "n_routed_experts", "vocab_size") if k in pub})


def shares(config: Dict[str, Any]) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """``(experts_held, vocab_held)``, each ``(first, count)``."""
    m, held = config["model"], config.get("held") or {}
    experts = tuple(held.get("experts", (0, m["n_routed_experts"])))
    vocab = tuple(held.get("vocab", (0, m["vocab_size"])))
    if experts[1] != m["n_routed_experts"] or vocab[1] != m["vocab_size"]:
        raise SystemExit("chipbench: the configuration's held counts are "
                         "not its n_routed_experts / vocab_size")
    return experts, vocab


# -- checkpoints from the seed ---------------------------------------------------


def _geometry(m: Dict[str, Any], kind: str) -> Dict[str, int]:
    p = "swa_" if kind == "sliding_attention" else ""
    return {"heads": m[p + "num_attention_heads"], "r_q": m[p + "q_lora_rank"],
            "r_kv": m[p + "kv_lora_rank"], "nope": m[p + "qk_nope_head_dim"],
            "rope": m[p + "qk_rope_head_dim"], "v": m[p + "v_head_dim"]}


def shards(config: Dict[str, Any], seed: int) -> Iterator[Tuple[str, Any]]:
    """(file name, function that draws that file's tensors): one file for
    what stands outside the layers, one per layer; each from its own
    stream of the seed, so they can be drawn side by side.  ``weights`` in
    the configuration's file says how each scale was chosen."""
    m, a = config["model"], config["weights"]
    pub = published_model(config)
    (e_first, e_count), (v_first, v_count) = shares(config)
    dtype = base._to_dtype(config)
    H, I, E = m["hidden_size"], m["moe_intermediate_size"], \
        pub["n_routed_experts"]
    n_files = m["num_hidden_layers"] + 1
    normal = base._normal
    std = a["std"]

    def outside() -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0xd073, 0])
        rows = v_first + v_count  # up to the end of the slice held
        return {"model.embed_tokens.weight": normal(
                    rng, dtype, a["embed_std"], rows, H),
                "lm_head.weight": normal(rng, dtype, a["head_std"], rows, H),
                "model.norm.weight": np.ones(H, dtype)}

    def layer(i: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0xd073, i + 1])
        p = f"model.layers.{i}."
        kind = m["layer_types"][i]
        g = _geometry(m, kind)
        out = {p + "input_layernorm.weight": np.ones(H, dtype),
               p + "post_attention_layernorm.weight": np.ones(H, dtype)}
        s = p + "self_attn."
        out[s + "q_a_layernorm.weight"] = np.ones(g["r_q"], dtype)
        out[s + "kv_a_layernorm.weight"] = np.ones(g["r_kv"], dtype)
        for name, rows, cols in (
                ("q_a_proj", g["r_q"], H),
                ("q_b_proj", g["heads"] * (g["nope"] + g["rope"]), g["r_q"]),
                ("kv_a_proj_with_mqa", g["r_kv"] + g["rope"], H),
                ("kv_b_proj", g["heads"] * (g["nope"] + g["v"]), g["r_kv"]),
                ("o_proj", H, g["heads"] * g["v"]),
                ("gate_proj", g["heads"], H)):
            out[f"{s}{name}.weight"] = normal(rng, dtype, std, rows, cols)
        if kind == "full_attention":
            x = s + "indexer."
            nj, dj = m["index_n_heads"], m["index_head_dim"]
            out[x + "wq_b.weight"] = normal(rng, dtype, std, nj * dj,
                                            g["r_q"])
            out[x + "wk.weight"] = normal(rng, dtype, std, dj, H)
            out[x + "k_norm.weight"] = np.ones(dj, dtype)
            out[x + "k_norm.bias"] = np.zeros(dj, dtype)
            out[x + "weights_proj.weight"] = normal(
                rng, dtype, a["index_weight_std"], nj, H)
        f = p + "mlp."
        if i < m["first_k_dense_replace"]:
            W = m["intermediate_size"]
            for k, rows, cols in (("gate", W, H), ("up", W, H),
                                  ("down", H, W)):
                out[f"{f}{k}_proj.weight"] = normal(rng, dtype, std, rows,
                                                    cols)
            return out
        # as hybrid_ar_guard's router: a row's own scale makes some experts
        # chosen more often; the selection bias is of the order of the gaps
        # between neighbouring scores
        scale = np.exp(a["router_row_log_std"] * rng.standard_normal(E))
        router = normal(rng, np.float32, a["router_std"], E, H)
        out[f + "gate.weight"] = (router * scale[:, None]).astype(dtype)
        out[f + "gate.e_score_correction_bias"] = (
            a["expert_bias_std"] * rng.standard_normal(E)).astype(np.float32)
        Is = I * m["n_shared_experts"]
        for k, rows, cols in (("gate", Is, H), ("up", Is, H),
                              ("down", H, Is)):
            out[f"{f}shared_experts.{k}_proj.weight"] = normal(
                rng, dtype, std, rows, cols)
        experts = normal(rng, dtype, std, e_count, 3, I * H)
        for n in range(e_count):
            q = f"{f}experts.{e_first + n}."
            out[q + "gate_proj.weight"] = experts[n, 0].reshape(I, H)
            out[q + "up_proj.weight"] = experts[n, 1].reshape(I, H)
            out[q + "down_proj.weight"] = experts[n, 2].reshape(H, I)
        return out

    yield f"model-00001-of-{n_files:05d}.safetensors", outside
    for i in range(m["num_hidden_layers"]):
        yield (f"model-{i + 2:05d}-of-{n_files:05d}.safetensors",
               lambda i=i: layer(i))


def generate_state(config: Dict[str, Any], seed: int
                   ) -> Dict[str, np.ndarray]:
    """Every tensor in one dict (toy sizes and tests)."""
    state: Dict[str, np.ndarray] = {}
    for _, draw in shards(config, seed):
        state.update(draw())
    return state


def _needs_the_decoder() -> None:
    """This family serves ``model_type: dots3_note``; a program without
    that decoder cannot run its cell, and says so before anything is
    built."""
    try:
        from semantic_router_tpu.models import dots3_note  # noqa: F401
    except ImportError:
        raise SystemExit(
            "chipbench: families/sparse_latent_ar_guard.py needs a program "
            "that serves model_type dots3_note (semantic_router_tpu.models."
            "dots3_note); this program does not")


def write_checkpoints(root: str, config: Dict[str, Any], seed: int
                      ) -> Dict[str, str]:
    """Sharded safetensors in the model's dtype under the published names,
    ``config.json`` with the published counts, and a WordLevel tokenizer of
    the vocabulary held."""
    _needs_the_decoder()
    from safetensors.numpy import save_file
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    dirs = {t: os.path.join(root, t) for t in config["tasks"]}
    dirs["tokenizer"] = os.path.join(root, "tokenizer")

    def write(task_dir: str, name: str, draw) -> Dict[str, str]:
        tensors = draw()
        save_file(tensors, os.path.join(task_dir, name))
        return {k: name for k in tensors}

    for task in config["tasks"]:
        os.makedirs(dirs[task], exist_ok=True)
        with ThreadPoolExecutor(config["weights"]["writer_threads"]) as pool:
            maps = list(pool.map(lambda s: write(dirs[task], *s),
                                 shards(config, seed)))
        weight_map = {k: v for m in maps for k, v in m.items()}
        with open(os.path.join(dirs[task], "model.safetensors.index.json"),
                  "w") as f:
            json.dump({"metadata": {}, "weight_map": weight_map}, f)
        with open(os.path.join(dirs[task], "config.json"), "w") as f:
            json.dump(published_model(config), f)
    n_vocab = config["model"]["vocab_size"]
    own = base.template_ids(n_vocab)
    taken = set(own.values())
    vocab = {"[PAD]": 0, "[UNK]": base.UNK, **own}
    vocab.update({f"w{i}": i for i in range(2, n_vocab) if i not in taken})
    tok = Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    os.makedirs(dirs["tokenizer"], exist_ok=True)
    tok.save(os.path.join(dirs["tokenizer"], "tokenizer.json"))
    return dirs


# -- the system: warm-up, and the engine's public calls ----------------------------

warm = hybrid.warm
ENGINE_CALLS = base.ENGINE_CALLS


# -- the comparison with the plain reference ---------------------------------------

_lse = hybrid._lse


def _select_rows(traj) -> np.ndarray:
    """The query positions whose selection a trajectory carries: the
    prefill's sampled ones, then the decoded ones."""
    at = np.asarray(traj[0]["selected_at"]).reshape(-1)
    return np.concatenate(
        [at[at >= 0], [e["position"] for e in traj[1:]]]).astype(np.int64)


def _selected_bits(traj) -> List[np.ndarray]:
    """A trajectory's selected sets, ``[layers, bytes]`` a query, in
    ``_select_rows``'s order."""
    at = np.asarray(traj[0]["selected_at"]).reshape(-1)
    first = np.asarray(traj[0]["selected"])  # [layers, n, bytes]
    return [first[:, j] for j in np.flatnonzero(at >= 0)] \
        + [np.asarray(e["selected"]) for e in traj[1:]]


def _free_the_device() -> None:
    """Delete what the closed system left on the device.  Its parameters
    are still there when the reference begins (the shut-down engine stays
    alive in the process: PERF.md section 7), and beside this
    configuration's 10 GB one layer of the reference in float32 (3.7 GB of
    weights and 3.3 GB of temporaries) does not fit the chip.  Nothing of
    the program runs after its window, and its answers are host arrays."""
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


class Reference:
    def __init__(self, config: Dict[str, Any], states: Dict[str, Any]
                 ) -> None:
        self.config, self.states = config, states
        self.model = published_model(config)
        self.experts, self.vocab = shares(config)
        self.ref = cells.load_module("reference", "dots3_note")

    @classmethod
    def from_checkpoints(cls, config, ckpt_dirs) -> "Reference":
        _free_the_device()
        return cls(config, {t: base._Checkpoint(ckpt_dirs[t])
                            for t in config["tasks"]})

    def outputs(self, request, shapes, answers, precision: str = "highest"
                ) -> Dict[str, Dict[str, Any]]:
        """Per task: one causal forward over the prompt and the served
        tokens but the last.  ``logits [forwards, V]`` at the positions
        that chose a token, ``router_s``/``top_e`` at every position,
        ``selected [full layers, n, T]`` at the trajectory's queries."""
        out = {}
        for task in self.config["tasks"]:
            if task not in answers:
                continue
            traj = answers[task].trajectory
            prompt = prompt_ids(request.text,
                                self.config["model"]["vocab_size"])
            if traj[0]["position"] != len(prompt) - 1:
                raise RuntimeError(
                    f"the program read {traj[0]['position'] + 1} prompt "
                    f"tokens where the reference reads {len(prompt)}")
            served = [e["token"] for e in traj]
            ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            out[task] = self.ref.forward(
                self.model, self.states[task], ids,
                [e["position"] for e in traj], precision,
                experts_held=self.experts, vocab_held=self.vocab,
                select_rows=_select_rows(traj))
        return out

    def answers(self, request, shapes, answers, precision: str
                ) -> Dict[str, Any]:
        """The control: the same trajectory's inputs, with what the LOWER
        precision computes for them in the program's place."""
        out = {}
        raw = self.outputs(request, shapes, answers, precision)
        for task, low in raw.items():
            src = answers[task].trajectory
            n_first = int((np.asarray(src[0]["selected_at"]) >= 0).sum())
            bits = np.packbits(low["selected"], axis=-1)  # [layers, n, B]
            traj = []
            for f, e in enumerate(src):
                z = low["logits"][f].astype(np.float64)
                at = e["position"]
                rows = slice(0, at + 1) if e["kind"] == "prefill" \
                    else slice(at, at + 1)
                new = dict(
                    e, token=int(z.argmax()), lse=_lse(z),
                    top_logits=z[e["top_ids"].astype(np.int64)],
                    experts=low["top_e"][:, rows])
                if e["kind"] == "prefill":
                    keep = np.asarray(e["selected_at"]).reshape(-1)
                    new["selected_at"] = keep[keep >= 0]
                    new["selected"] = bits[:, :n_first]
                else:
                    new["selected"] = bits[:, n_first + f - 1]
                traj.append(new)
            out[task] = types.SimpleNamespace(trajectory=traj)
        return out


def compare(config: Dict[str, Any], request, answers: Dict[str, Any],
            raw: Dict[str, Dict[str, Any]]
            ) -> Dict[str, Tuple[float, float]]:
    parts = {"sa_" + k[3:]: v for k, v in hybrid.compare(
        config, request, answers, raw).items()}
    strangers = chosen = 0
    for task, ref in raw.items():
        traj = answers[task].trajectory
        T = ref["selected"].shape[-1]
        for n, bits in enumerate(_selected_bits(traj)):
            got = np.unpackbits(bits, axis=-1)[:, :T].astype(bool)
            want = ref["selected"][:, n]  # [layers, T]
            chosen += int(got.sum())
            strangers += int((got & ~want).sum())
    if chosen:
        parts["sa_select_disagreement_share"] = (float(strangers),
                                                 float(chosen))
    return parts


def finish(total: Dict[str, Tuple[float, float]]) -> Dict[str, float]:
    numbers = {k: s / w for k, (s, w) in total.items() if w}
    for name in ("route", "select"):
        key = f"sa_{name}_disagreement_share"
        if key in total:
            numbers[f"sa_{name}_counted"] = total[key][1]
    return numbers


def expected_numbers(config: Dict[str, Any]) -> List[str]:
    return ["sa_logit_rel_sq_err", "sa_transfer_gap_max",
            "sa_route_disagreement_share", "sa_select_disagreement_share"]
