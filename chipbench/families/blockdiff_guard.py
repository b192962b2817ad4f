"""The generative guard served by a block-diffusion expert model
(``model_type: sdar_moe``): the jailbreak family answered by
``engine.guard_classify``, whose wrapped call is ``generate``.  Everything
the benchmark knows of this family is here; the plain reference is
``chipbench/reference/sdar_moe.py``.

What is compared is not the tokens (with seeded weights the largest logit
changes on rounding) but what the program computed on the way to them.  A
served generation's result carries its trajectory: per forward the block's
state that went in, the positions filled, and at every masked position the
top logits and the log-sum-exp.  The reference replays that trajectory —
every forward's input is fixed by what was served — as ONE sequence under a
visibility matrix (``reference.replay_plan``) and three numbers come out:

``gen_logit_rel_sq_err``
    at every masked position of every denoise forward, the program's top
    logits and log-sum-exp against the reference's at the same ids:
    sum of squared differences over the sum of the reference's squares.
``gen_transfer_gap_max``
    at every position a denoise forward filled, how far the reference's
    logit of the served token lies below the reference's best there; the
    widest of a request, averaged over the requests compared.
``moe_route_disagreement_share``
    the share of (token, layer) pairs of every block forward whose set of
    chosen experts is not the reference's, counted where the reference's
    last chosen probability exceeds the first unchosen by more than
    ``route_margin`` of itself.  (``correctness.judge`` holds a number
    under its limit, so the share counted is the disagreeing one.)
"""

from __future__ import annotations

import json
import os
import re
import types
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

MODEL_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "hidden_act", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "decoder_sparse_step",
    "mlp_only_layers", "attention_bias", "rms_norm_eps", "rope_theta",
    "rope_scaling", "max_position_embeddings", "max_window_layers",
    "sliding_window", "use_sliding_window", "tie_word_embeddings",
    "torch_dtype")

UNK = 1
# the program's guard template (models/generate.py build_guard_prompt, role
# "user"), copied: the reference tokenizes it without the program
BEFORE = ("You are a safety classifier. Classify the user message below.\n"
          "Respond in EXACTLY this format:\n"
          "Safety: Safe, Unsafe, or Controversial\n"
          "Categories: comma-separated categories, or None\n"
          "\nuser message:\n")
AFTER = "\n\nClassification:\n"
PIECES = re.compile(r"\w+|[^\w\s]+")  # the tokenizer's Whitespace pre-tokenizer


def template_ids(vocab_size: int) -> Dict[str, int]:
    """The guard template's own words and marks as tokens of their own,
    spread evenly over the vocabulary (a word ``w<id>`` of that id gives
    its place up and is [UNK]).  As one [UNK] they were a third of every
    prompt with one and the same key and value, and every position of
    every row attended mostly to that."""
    words = sorted(set(PIECES.findall(BEFORE + AFTER)))
    stride = (vocab_size - 2) // (len(words) + 1)
    return {w: 2 + (k + 1) * stride for k, w in enumerate(words)}


def prompt_ids(text: str, vocab_size: int) -> np.ndarray:
    """Token ids of the guard prompt around ``text``: the template's pieces
    by ``template_ids``, ``w<id>`` is token ``id``, anything else [UNK]."""
    own = template_ids(vocab_size)
    taken = set(own.values())

    def one(piece: str) -> int:
        if piece in own:
            return own[piece]
        if re.fullmatch(r"w\d+", piece) and int(piece[1:]) not in taken:
            return int(piece[1:])
        return UNK

    return np.asarray([one(p) for p in PIECES.findall(BEFORE + text + AFTER)],
                      np.int32)


def request_text(prompt: str) -> str:
    if prompt.startswith(BEFORE) and prompt.endswith(AFTER):
        return prompt[len(BEFORE):-len(AFTER)]
    return prompt


# -- checkpoints from the seed ---------------------------------------------------


def _to_dtype(config: Dict[str, Any]):
    import ml_dtypes

    return {"bfloat16": ml_dtypes.bfloat16,
            "float32": np.float32}[config["model"]["torch_dtype"]]


def _normal(rng: np.random.Generator, dtype, std: float, *shape: int
            ) -> np.ndarray:
    """N(0, std) at 65536 evenly spaced quantiles, drawn in float32 and
    rounded to ``dtype`` once (the table), then looked up by 16 random
    bits an entry: 4.4e9 parameters in seconds, not minutes."""
    from scipy.special import ndtri

    table = (std * ndtri((np.arange(65536) + 0.5) / 65536)) \
        .astype(np.float32).astype(dtype)
    n = int(np.prod(shape))
    bits = np.frombuffer(rng.bytes(2 * n), np.uint16)
    return table[bits].reshape(shape)


def shards(config: Dict[str, Any], seed: int
           ) -> Iterator[Tuple[str, Any]]:
    """(file name, function that draws that file's tensors): one file for
    what stands outside the layers, one per layer; each from its own
    stream of the seed, so they can be drawn side by side."""
    m, a = config["model"], config["weights"]
    dtype = _to_dtype(config)
    H, D, I = m["hidden_size"], m["head_dim"], m["moe_intermediate_size"]
    nh, nkv, E = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["num_experts"])
    n_files = m["num_hidden_layers"] + 1
    # the weight of the per-head norms of q and k scales the attention
    # scores (qk^2 times): 1 leaves them at unit variance, where every
    # query averages its keys and all positions' states collapse onto one
    # direction within two layers (and then choose the same experts)
    qk = a.get("qk_norm", 1.0)

    def outside() -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x5da7, 0])
        embed = _normal(rng, dtype, a["embed_std"], m["vocab_size"], H)
        if "mask_embed_std" in a:
            embed[config["generation"]["mask_token_id"]] = _normal(
                rng, dtype, a["mask_embed_std"], H)
        return {
            "model.embed_tokens.weight": embed,
            "model.norm.weight": np.ones(H, dtype),
            "lm_head.weight": _normal(rng, dtype, a["std"],
                                      m["vocab_size"], H)}

    def layer(i: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x5da7, i + 1])
        p = f"model.layers.{i}."
        out = {p + "input_layernorm.weight": np.ones(H, dtype),
               p + "post_attention_layernorm.weight": np.ones(H, dtype),
               p + "self_attn.q_norm.weight": np.full(D, qk, dtype),
               p + "self_attn.k_norm.weight": np.full(D, qk, dtype)}
        for name, rows, cols in (("q", nh * D, H), ("k", nkv * D, H),
                                 ("v", nkv * D, H), ("o", H, nh * D)):
            out[f"{p}self_attn.{name}_proj.weight"] = _normal(
                rng, dtype, a["std"], rows, cols)
        # a row's own scale makes some experts' logits wider than others':
        # they are chosen more often, as in a trained router
        scale = np.exp(a["router_row_log_std"] * rng.standard_normal(E))
        router = _normal(rng, np.float32, a["router_std"], E, H)
        out[p + "mlp.gate.weight"] = (router * scale[:, None]).astype(dtype)
        experts = _normal(rng, dtype, a["std"], E, 3, I * H)
        for e in range(E):
            q = f"{p}mlp.experts.{e}."
            out[q + "gate_proj.weight"] = experts[e, 0].reshape(I, H)
            out[q + "up_proj.weight"] = experts[e, 1].reshape(I, H)
            out[q + "down_proj.weight"] = experts[e, 2].reshape(H, I)
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


def _needs_trajectory() -> None:
    """This family compares what ``generate`` computed forward by forward;
    a program whose result carries no trajectory cannot be held to it."""
    try:
        from semantic_router_tpu.models.generate import GenerationResult

        ok = "trajectory" in getattr(GenerationResult,
                                     "__dataclass_fields__", {})
    except ImportError:
        ok = False
    if not ok:
        raise SystemExit(
            "chipbench: families/blockdiff_guard.py needs a program whose "
            "generate() returns per-forward trajectories "
            "(models.generate.GenerationResult.trajectory) and serves "
            "model_type sdar_moe; this program does neither")


def write_checkpoints(root: str, config: Dict[str, Any], seed: int
                      ) -> Dict[str, str]:
    """Sharded safetensors in the model's dtype under the published names,
    ``config.json``, and a WordLevel tokenizer of the whole vocabulary."""
    _needs_trajectory()
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
            json.dump(config["model"], f)
    own = template_ids(config["model"]["vocab_size"])
    taken = set(own.values())
    assert config["generation"]["mask_token_id"] not in taken
    vocab = {"[PAD]": 0, "[UNK]": UNK, **own}
    vocab.update({f"w{i}": i for i in range(2, config["model"]["vocab_size"])
                  if i not in taken})
    tok = Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    os.makedirs(dirs["tokenizer"], exist_ok=True)
    tok.save(os.path.join(dirs["tokenizer"], "tokenizer.json"))
    return dirs


# -- the system: warm-up, and the engine's public calls ----------------------------


def warm(engine, config: Dict[str, Any], shapes: Dict[str, Any]) -> None:
    """The generative programs (prefill, denoise, commit) of every prompt
    bucket and row count of the cell, through ``engine.warmup``."""
    engine.warmup(tasks=list(config["tasks"]), buckets=shapes["buckets"],
                  batch_sizes=shapes["rows"])
    for row in engine.warmup_report():
        print(f"warmup {row['target']} bucket={row['bucket']} "
              f"rows={row['rows']} {row['seconds']:.2f} s", flush=True)


def _generate_arguments(task, prompts, *_, **__):
    return [task], [request_text(p) for p in prompts]


def _generate_answers(tasks, texts, out):
    for text, res in zip(texts, out):
        if getattr(res, "trajectory", None) is None:
            raise RuntimeError("generate() returned no trajectory: this "
                               "family cannot compare such a program")
        yield text, tasks[0], res


ENGINE_CALLS = {"generate": (_generate_arguments, _generate_answers)}


# -- the comparison with the plain reference ---------------------------------------


class _Checkpoint:
    """A checkpoint directory as a mapping name -> tensor, one tensor
    loaded per access (the reference holds a layer at a time)."""

    def __init__(self, path: str) -> None:
        from safetensors import safe_open

        with open(os.path.join(path, "model.safetensors.index.json")) as f:
            self._where = json.load(f)["weight_map"]
        self._files = {name: safe_open(os.path.join(path, name), "np")
                       for name in set(self._where.values())}

    def __getitem__(self, name: str) -> np.ndarray:
        return self._files[self._where[name]].get_tensor(name)


class Reference:
    def __init__(self, config: Dict[str, Any], states: Dict[str, Any]
                 ) -> None:
        from chipbench import cells

        self.config, self.states = config, states
        self.ref = cells.load_module("reference", "sdar_moe")

    @classmethod
    def from_checkpoints(cls, config, ckpt_dirs) -> "Reference":
        return cls(config, {t: _Checkpoint(ckpt_dirs[t])
                            for t in config["tasks"]})

    def outputs(self, request, shapes, answers, precision: str = "highest"
                ) -> Dict[str, Dict[str, Any]]:
        """Per task: the reference over the served trajectory.  ``logits
        [forwards, L, V]`` of the denoise forwards in order, and of every
        block forward (denoise and commit) ``router_p [layers, forwards, L,
        E]`` and ``top_e [layers, forwards, L, k]``."""
        out = {}
        L = self.config["generation"]["block_length"]
        for task in self.config["tasks"]:
            if task not in answers:
                continue
            traj = answers[task].trajectory
            plan = self.ref.replay_plan(
                prompt_ids(request.text, self.config["model"]["vocab_size"]),
                traj, L)
            denoise = [f for f, e in enumerate(traj)
                       if e["kind"] == "denoise"]
            want = [r for f in denoise for r in plan["rows"][f]]
            raw = self.ref.forward(
                self.config["model"], self.states[task], plan["ids"],
                plan["positions"], plan["visible"], want, precision)
            rows = np.asarray(plan["rows"])  # [forwards, L]
            out[task] = {
                "logits": raw["logits"].reshape(len(denoise), L, -1),
                "router_p": raw["router_p"][:, rows],
                "top_e": raw["top_e"][:, rows]}
        return out

    def answers(self, request, shapes, answers, precision: str
                ) -> Dict[str, Any]:
        """The control: the same trajectory's inputs, with what the LOWER
        precision computes for them in the program's place — its logits at
        the served ids, its log-sum-exp, its best token at every filled
        position, its choice of experts."""
        out = {}
        raw = self.outputs(request, shapes, answers, precision)
        for task, low in raw.items():
            traj, d = [], 0
            for f, e in enumerate(answers[task].trajectory):
                e = dict(e, experts=low["top_e"][:, f])
                if e["kind"] == "denoise":
                    z = low["logits"][d].astype(np.float64)
                    d += 1
                    e["top_logits"] = np.take_along_axis(
                        z, e["top_ids"].astype(np.int64), -1)
                    e["lse"] = np.log(np.exp(
                        z - z.max(-1, keepdims=True)).sum(-1)) + z.max(-1)
                    e["tokens_after"] = np.where(
                        e["filled"], z.argmax(-1), e["tokens_after"])
                traj.append(e)
            out[task] = types.SimpleNamespace(trajectory=traj)
        return out


def compare(config: Dict[str, Any], request, answers: Dict[str, Any],
            raw: Dict[str, Dict[str, Any]]
            ) -> Dict[str, Tuple[float, float]]:
    num = den = 0.0
    gap = 0.0
    differ = counted = 0
    margin = config["route_margin"]
    k = config["model"]["num_experts_per_tok"]
    for task, ref in raw.items():
        d = 0
        for f, e in enumerate(answers[task].trajectory):
            # the router: every token of every block forward
            p = np.sort(ref["router_p"][:, f], -1)  # [layers, L, E]
            sure = (p[..., -k] - p[..., -k - 1]) > margin * p[..., -k]
            same = (np.sort(ref["top_e"][:, f], -1)
                    == np.sort(np.asarray(e["experts"]), -1)).all(-1)
            counted += int(sure.sum())
            differ += int((sure & ~same).sum())
            if e["kind"] != "denoise":
                continue
            z = ref["logits"][d].astype(np.float64)  # [L, V]
            d += 1
            lse = np.log(np.exp(z - z.max(-1, keepdims=True)).sum(-1)) \
                + z.max(-1)
            m = np.asarray(e["masked"], bool)
            at_ids = np.take_along_axis(z, e["top_ids"].astype(np.int64), -1)
            got = np.concatenate([np.asarray(e["top_logits"], np.float64),
                                  np.asarray(e["lse"], np.float64)[:, None]],
                                 -1)[m]
            want = np.concatenate([at_ids, lse[:, None]], -1)[m]
            num += float(((got - want) ** 2).sum())
            den += float((want ** 2).sum())
            filled = np.asarray(e["filled"], bool)
            served = np.asarray(e["tokens_after"], np.int64)
            gaps = z.max(-1) - np.take_along_axis(z, served[:, None], -1)[:, 0]
            if filled.any():
                gap = max(gap, float(gaps[filled].max()))
    parts: Dict[str, Tuple[float, float]] = {}
    if den:
        parts["gen_logit_rel_sq_err"] = (num, den)
        parts["gen_transfer_gap_max"] = (gap, 1.0)
    if counted:
        parts["moe_route_disagreement_share"] = (float(differ),
                                                 float(counted))
    return parts


def finish(total: Dict[str, Tuple[float, float]]) -> Dict[str, float]:
    numbers = {k: s / w for k, (s, w) in total.items() if w}
    if "moe_route_disagreement_share" in total:
        numbers["moe_route_pairs_counted"] = \
            total["moe_route_disagreement_share"][1]
    return numbers


def expected_numbers(config: Dict[str, Any]) -> List[str]:
    return ["gen_logit_rel_sq_err", "gen_transfer_gap_max",
            "moe_route_disagreement_share"]
