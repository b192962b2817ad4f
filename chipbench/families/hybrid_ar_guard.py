"""The generative guard served by a hybrid token-at-a-time decoder
(``model_type: lfm2_moe``: short-convolution and attention layers over one
cache, sparse experts behind a sigmoid router with a selection bias): the
jailbreak family answered by ``engine.guard_classify``, whose wrapped call
is ``generate``.  Everything the benchmark knows of this family is here;
the plain reference is ``chipbench/reference/lfm2_moe.py``.  The guard
template, the WordLevel tokenizer's rules and the quantile draw of the
weights are ``families/blockdiff_guard.py``'s, loaded by name.

What is compared is not the tokens (with seeded weights the largest logit
changes on rounding) but what the program computed on the way to them.  A
served result carries its trajectory: per forward that chose a token (one
prefill, then a decode a token) the chosen id, the top logits and the
log-sum-exp at that position, and the experts chosen per layer — of a
prefill at every prompt position, of a decode at the one it decoded.  The
reference runs ONE causal forward over the prompt and the served tokens
(every forward's input is fixed by what was served): prefill and then
decoding through the hybrid cache against the full forward pass.

``ar_logit_rel_sq_err``
    at every position that chose a token, the program's top logits and
    log-sum-exp against the reference's at the same ids: sum of squared
    differences over the sum of the reference's squares.
``ar_transfer_gap_max``
    how far the reference's logit of the served token lies below the
    reference's best there; the widest of a request, averaged over the
    requests compared.
``ar_route_disagreement_share``
    the share of (token, expert layer) pairs whose set of chosen experts is
    not the reference's, counted where the reference's last chosen BIASED
    score exceeds the first unchosen by more than ``route_margin``, over
    the generated positions and ``route_sample`` prompt positions drawn
    from the request.  (``correctness.judge`` holds a number under its
    limit, so the share counted is the disagreeing one.)
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

MODEL_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
    "layer_types", "num_attention_heads", "num_key_value_heads",
    "conv_L_cache", "conv_bias", "norm_eps", "rope_parameters",
    "max_position_embeddings", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
    "tie_word_embeddings", "torch_dtype")

prompt_ids = base.prompt_ids


# -- checkpoints from the seed ---------------------------------------------------


def shards(config: Dict[str, Any], seed: int) -> Iterator[Tuple[str, Any]]:
    """(file name, function that draws that file's tensors): one file for
    what stands outside the layers, one per layer; each from its own
    stream of the seed, so they can be drawn side by side."""
    m, a = config["model"], config["weights"]
    dtype = base._to_dtype(config)
    H, I, E = m["hidden_size"], m["moe_intermediate_size"], m["num_experts"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    D = H // nh
    n_files = m["num_hidden_layers"] + 1
    normal = base._normal

    def outside() -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x1f32, 0])
        # the head is this matrix too: its scale is the logits' scale
        return {"model.embed_tokens.weight": normal(
                    rng, dtype, a["embed_std"], m["vocab_size"], H),
                "model.embedding_norm.weight": np.ones(H, dtype)}

    def layer(i: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(seed), 0x1f32, i + 1])
        p = f"model.layers.{i}."
        out = {p + "operator_norm.weight": np.ones(H, dtype),
               p + "ffn_norm.weight": np.ones(H, dtype)}
        if m["layer_types"][i] == "conv":
            out[p + "conv.in_proj.weight"] = normal(rng, dtype, a["std"],
                                                    3 * H, H)
            # taps of order one, each its own: with N(0, 0.02) taps the
            # operator's output is numerically nothing beside the
            # residual, and a wrong tap would pass
            out[p + "conv.conv.weight"] = normal(
                rng, dtype, a["conv_std"], H, 1, m["conv_L_cache"])
            out[p + "conv.out_proj.weight"] = normal(rng, dtype, a["std"],
                                                     H, H)
        else:
            # 1 leaves the attention scores at unit variance, where every
            # query averages its keys (blockdiff_guard.py has the sweep)
            qk = a["qk_norm"]
            out[p + "self_attn.q_layernorm.weight"] = np.full(D, qk, dtype)
            out[p + "self_attn.k_layernorm.weight"] = np.full(D, qk, dtype)
            for name, rows, cols in (("q", nh * D, H), ("k", nkv * D, H),
                                     ("v", nkv * D, H), ("out", H, nh * D)):
                out[f"{p}self_attn.{name}_proj.weight"] = normal(
                    rng, dtype, a["std"], rows, cols)
        f = p + "feed_forward."
        if i < m["num_dense_layers"]:
            W = m["intermediate_size"]
            for k, rows, cols in (("w1", W, H), ("w3", W, H), ("w2", H, W)):
                out[f"{f}{k}.weight"] = normal(rng, dtype, a["std"], rows,
                                               cols)
            return out
        # a row's own scale makes some experts' sigmoids wider than
        # others': they are chosen more often, as in a trained router; the
        # selection bias is of the order of the gaps between neighbouring
        # scores, so it changes some of the choices and not most
        scale = np.exp(a["router_row_log_std"] * rng.standard_normal(E))
        router = normal(rng, np.float32, a["router_std"], E, H)
        out[f + "gate.weight"] = (router * scale[:, None]).astype(dtype)
        out[f + "expert_bias"] = (a["expert_bias_std"]
                                  * rng.standard_normal(E)).astype(np.float32)
        experts = normal(rng, dtype, a["std"], E, 3, I * H)
        for e in range(E):
            q = f"{f}experts.{e}."
            out[q + "w1.weight"] = experts[e, 0].reshape(I, H)
            out[q + "w3.weight"] = experts[e, 1].reshape(I, H)
            out[q + "w2.weight"] = experts[e, 2].reshape(H, I)
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
    """This family serves ``model_type: lfm2_moe`` through a generator
    whose results carry trajectories; a program without either cannot run
    its cell, and says so before anything is built."""
    try:
        from semantic_router_tpu.models import lfm2_moe  # noqa: F401
    except ImportError:
        raise SystemExit(
            "chipbench: families/hybrid_ar_guard.py needs a program that "
            "serves model_type lfm2_moe (semantic_router_tpu.models."
            "lfm2_moe); this program does not")


def write_checkpoints(root: str, config: Dict[str, Any], seed: int
                      ) -> Dict[str, str]:
    """Sharded safetensors in the model's dtype under the published names,
    ``config.json``, and a WordLevel tokenizer of the whole vocabulary."""
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
            json.dump(config["model"], f)
    own = base.template_ids(config["model"]["vocab_size"])
    taken = set(own.values())
    vocab = {"[PAD]": 0, "[UNK]": base.UNK, **own}
    vocab.update({f"w{i}": i for i in range(2, config["model"]["vocab_size"])
                  if i not in taken})
    tok = Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    os.makedirs(dirs["tokenizer"], exist_ok=True)
    tok.save(os.path.join(dirs["tokenizer"], "tokenizer.json"))
    return dirs


# -- the system: warm-up, and the engine's public calls ----------------------------


def warm(engine, config: Dict[str, Any], shapes: Dict[str, Any]) -> None:
    """The two programs (prefill, decode) of every prompt bucket and row
    count of the cell, through ``engine.warmup``; first what
    ``build_engine`` loaded, counted where it lies."""
    import jax

    held = [a for a in jax.live_arrays() if a.ndim]
    print(f"setup loaded: {sum(a.size for a in held) / 1e6:.2f} M parameters"
          f" = {sum(a.nbytes for a in held) / 1e9:.3f} GB on the device",
          flush=True)
    engine.warmup(tasks=list(config["tasks"]), buckets=shapes["buckets"],
                  batch_sizes=shapes["rows"])
    for row in engine.warmup_report():
        print(f"warmup {row['target']} bucket={row['bucket']} "
              f"rows={row['rows']} {row['seconds']:.2f} s", flush=True)


ENGINE_CALLS = base.ENGINE_CALLS


# -- the comparison with the plain reference ---------------------------------------


def _lse(z: np.ndarray) -> np.ndarray:
    return np.log(np.exp(z - z.max(-1, keepdims=True)).sum(-1)) + z.max(-1)


def _sampled_positions(request, n_prompt: int, k: int) -> np.ndarray:
    """``k`` prompt positions of a request, drawn from the request."""
    rng = np.random.default_rng([int(request.index), n_prompt, 0x5a3])
    return np.sort(rng.permutation(n_prompt)[:k])


class Reference:
    def __init__(self, config: Dict[str, Any], states: Dict[str, Any]
                 ) -> None:
        self.config, self.states = config, states
        self.ref = cells.load_module("reference", "lfm2_moe")

    @classmethod
    def from_checkpoints(cls, config, ckpt_dirs) -> "Reference":
        return cls(config, {t: base._Checkpoint(ckpt_dirs[t])
                            for t in config["tasks"]})

    def outputs(self, request, shapes, answers, precision: str = "highest"
                ) -> Dict[str, Dict[str, Any]]:
        """Per task: one causal forward over the prompt and the served
        tokens but the last.  ``logits [forwards, V]`` at the positions
        that chose a token, ``router_s [layers, T, E]`` and ``top_e
        [layers, T, k]`` at every position."""
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
                self.config["model"], self.states[task], ids,
                [e["position"] for e in traj], precision)
        return out

    def answers(self, request, shapes, answers, precision: str
                ) -> Dict[str, Any]:
        """The control: the same trajectory's inputs, with what the LOWER
        precision computes for them in the program's place — its logits at
        the served ids, its log-sum-exp, its best token, its choice of
        experts."""
        out = {}
        raw = self.outputs(request, shapes, answers, precision)
        for task, low in raw.items():
            traj = []
            for f, e in enumerate(answers[task].trajectory):
                z = low["logits"][f].astype(np.float64)
                at = e["position"]
                rows = slice(0, at + 1) if e["kind"] == "prefill" \
                    else slice(at, at + 1)
                traj.append(dict(
                    e, token=int(z.argmax()), lse=_lse(z),
                    top_logits=z[e["top_ids"].astype(np.int64)],
                    experts=low["top_e"][:, rows]))
            out[task] = types.SimpleNamespace(trajectory=traj)
        return out


def compare(config: Dict[str, Any], request, answers: Dict[str, Any],
            raw: Dict[str, Dict[str, Any]]
            ) -> Dict[str, Tuple[float, float]]:
    num = den = gap = 0.0
    differ = counted = 0
    margin = config["route_margin"]
    k = config["model"]["num_experts_per_tok"]
    for task, ref in raw.items():
        traj = answers[task].trajectory
        n_prompt = traj[0]["position"] + 1
        # the router: the generated positions, and a sample of the prompt's
        at = _sampled_positions(request, n_prompt, config["route_sample"])
        got = [np.asarray(traj[0]["experts"])[:, at]] + [
            np.asarray(e["experts"]) for e in traj[1:]]
        at = np.concatenate([at, [e["position"] for e in traj[1:]]]) \
            .astype(np.int64)
        s = np.sort(ref["router_s"][:, at], -1)  # [layers, n, E]
        sure = (s[..., -k] - s[..., -k - 1]) > margin
        same = (np.sort(ref["top_e"][:, at], -1)
                == np.sort(np.concatenate(got, 1).astype(np.int64), -1)
                ).all(-1)
        counted += int(sure.sum())
        differ += int((sure & ~same).sum())
        for f, e in enumerate(traj):
            z = ref["logits"][f].astype(np.float64)  # [V]
            have = np.append(np.asarray(e["top_logits"], np.float64),
                             float(e["lse"]))
            want = np.append(z[e["top_ids"].astype(np.int64)], _lse(z))
            num += float(((have - want) ** 2).sum())
            den += float((want ** 2).sum())
            gap = max(gap, float(z.max() - z[int(e["token"])]))
    parts: Dict[str, Tuple[float, float]] = {}
    if den:
        parts["ar_logit_rel_sq_err"] = (num, den)
        parts["ar_transfer_gap_max"] = (gap, 1.0)
    if counted:
        parts["ar_route_disagreement_share"] = (float(differ),
                                                float(counted))
    return parts


def finish(total: Dict[str, Tuple[float, float]]) -> Dict[str, float]:
    numbers = {k: s / w for k, (s, w) in total.items() if w}
    if "ar_route_disagreement_share" in total:
        numbers["ar_route_pairs_counted"] = \
            total["ar_route_disagreement_share"][1]
    return numbers


def expected_numbers(config: Dict[str, Any]) -> List[str]:
    return ["ar_logit_rel_sq_err", "ar_transfer_gap_max",
            "ar_route_disagreement_share"]
