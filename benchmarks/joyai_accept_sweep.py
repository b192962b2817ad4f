"""Which seeded weights make the ``joyai-flash-guard`` drafter worth
measuring: the acceptance of the MTP module's drafts, swept over the
embedding's deviation (the blocks' share ``rho`` of what the head reads) and
``W_eh``'s embedding gain, at the configuration's own widths on one chip.

    chiprun -- python benchmarks/joyai_accept_sweep.py \\
        --pairs 8:8,16:16,32:32 --rows 16 --batches 2

With independent random weights a draft is right once in 129,280.  The
recipe (``chipbench/configs/joyai-flash-guard/model.json``,
``assumed.weights``): an embedding of large deviation, so that what the head
reads at position ``i + 1`` is ``Emb(t_{i+1})`` plus the blocks' sum at a
small ratio ``rho``, and ``W_eh``'s embedding half ``gain * I + N(0,
eh_std)``: main model and drafter both read "the current token, perturbed by
context", and ``rho`` sets how often they agree.

The weights are drawn on the device with the configuration's deviations
(not the family's quantile draw: the same distributions), through
``models/joyai_llm_flash.py`` and ``GreedyGenerator`` directly, no engine:
16 rows in lock step, prompts of the cell's lengths between a common
beginning and a common end, as the guard template's.  Prints one JSON line a
setting: the blocks' ratio as measured, the acceptance, the steps a
generation took, whether the rows of a batch say different things
(``rows_apart_share``: an embedding that drowns the context makes every row
follow ONE chain of tokens from the template's last token on, and a step
then touches a handful of experts), and the generations' seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from semantic_router_tpu.models import joyai_llm_flash as M  # noqa: E402
from semantic_router_tpu.models.generate import GreedyGenerator  # noqa: E402
from semantic_router_tpu.utils.tokenization import Encoding  # noqa: E402


def draw(cfg, a, key):
    """The tree ``params_from_state`` builds, drawn in place."""
    H, I = cfg.hidden_size, cfg.moe_intermediate_size
    g = cfg.geometry
    count = cfg.held[1]
    keys = iter(jax.random.split(key, 4096))

    def n(std, *shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(cfg.dtype)

    def gains(size):
        return (1 + a["norm_std"] * jax.random.normal(
            next(keys), (size,), jnp.float32)).astype(cfg.dtype)

    def block(i):
        s = a["std"]
        layer = {"norm1": gains(H), "norm2": gains(H),
                 "q_a": n(s, H, g.r_q), "q_a_norm": gains(g.r_q),
                 "q_b": n(s * a["q_b_gain"], g.r_q,
                          g.heads * (g.nope + g.rope)),
                 "kv_a": n(s, H, g.r_kv + g.rope),
                 "kv_a_norm": gains(g.r_kv),
                 "kv_b": n(s, g.r_kv, g.heads * (g.nope + g.v)),
                 "o_proj": n(s * a["o_gain"], g.heads * g.v, H)}
        if not cfg.is_sparse(i):
            W = cfg.intermediate_size
            layer.update(gate_up=n(s, H, 2 * W), down=n(s, W, H))
            return layer
        scale = jnp.exp(a["router_row_log_std"] * jax.random.normal(
            next(keys), (cfg.n_routed_experts,), jnp.float32))
        layer.update(
            router=(jax.random.normal(
                next(keys), (H, cfg.n_routed_experts), jnp.float32)
                * a["router_std"] * scale[None]).astype(cfg.dtype),
            expert_bias=a["expert_bias_std"] * jax.random.normal(
                next(keys), (cfg.n_routed_experts,), jnp.float32),
            gate_up=n(s, count, H, 2 * I), down=n(s, count, I, H),
            shared={"gate_up": n(s, H, 2 * I), "down": n(s, I, H)})
        return layer

    L = cfg.num_hidden_layers
    return {"embed_unit": jax.random.normal(
                next(keys), (cfg.vocab_size, H), jnp.float32
            ).astype(cfg.dtype),
            "layers": [block(i) for i in range(L)], "norm": gains(H),
            "lm_head": n(a["head_std"], cfg.vocab_size, H),
            "mtp": {"enorm": gains(H), "hnorm": gains(H), "norm": gains(H),
                    "eh_noise": n(a["eh_std"], 2 * H, H), "block": block(L)}}


def at(params, cfg, embed_std: float, gain: float):
    """``params`` at one setting of the two knobs."""
    H = cfg.hidden_size
    m = params["mtp"]
    eh = m["eh_noise"].astype(jnp.float32).at[:H].add(
        gain * jnp.eye(H, dtype=jnp.float32)).astype(cfg.dtype)
    out = {k: v for k, v in params.items() if k != "embed_unit"}
    out["embed"] = (params["embed_unit"].astype(jnp.float32)
                    * embed_std).astype(cfg.dtype)
    out["mtp"] = {k: v for k, v in m.items() if k != "eh_noise"}
    out["mtp"]["eh_proj"] = eh
    return out


def blocks_ratio(cfg, params, ids):
    """``rho``: the RMS of what the layers add to the residual stream over
    the RMS of the embedding it started as, on ``ids [1, S]``."""
    B, S = ids.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x0 = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    x = x0.astype(cfg.dtype)
    for i, p in enumerate(params["layers"]):
        x, _, _, _ = M._prompt_layer(cfg, i, p, x, positions,
                                     jnp.ones((B, S), bool))
    rms = lambda a: jnp.sqrt(jnp.mean(a * a))  # noqa: E731
    return rms(x.astype(jnp.float32) - x0) / rms(x0)


class Ids:
    """Prompts are ids already."""

    def encode(self, text):
        raise NotImplementedError

    def decode(self, ids):
        return " ".join(map(str, ids))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pairs", default="8:8,16:16,32:32",
                    help="embed_std:eh_embed_gain, ...")
    ap.add_argument("--norm-std", type=float, default=None,
                    help="the norms' gains' deviation, if not the file's")
    ap.add_argument("--o-gain", type=float, default=None,
                    help="o_proj's deviation over std, if not the file's")
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--batches", type=int, default=2)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--held", type=int, default=128)
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--config", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "chipbench", "configs", "joyai-flash-guard", "model.json"))
    args = ap.parse_args()
    with open(args.config) as f:
        a = json.load(f)["weights"]
    if args.norm_std is not None:
        a["norm_std"] = args.norm_std
    if args.o_gain is not None:
        a["o_gain"] = args.o_gain
    cfg = M.JoyaiLlmFlashConfig(num_hidden_layers=args.layers,
                                experts_held=(0, args.held))
    print(f"device {jax.devices()[0].device_kind}; weights {a}", flush=True)
    params = draw(cfg, a, jax.random.PRNGKey(args.seed % (2 ** 31)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"drawn {n_params / 1e6:.1f} M parameters", flush=True)
    rng = np.random.default_rng(args.seed)
    bucket, new = 512, 32
    lengths = np.clip(np.exp(rng.normal(np.log(96), 1.0, (
        args.batches, args.rows))).astype(int), 8, 400)
    # as the guard template: every prompt begins with the same 40 tokens
    # and ENDS with the same two, so every row's first token is chosen at
    # the same token (what random prompts hide: rows that say one thing)
    before, after = rng.integers(2, cfg.vocab_size, 40), \
        rng.integers(2, cfg.vocab_size, 2)
    # one generator: the weights are arguments of its programs
    gen = GreedyGenerator(cfg, None, Ids(), model=M.CachedModel(cfg),
                          gen_length=new)
    ratio = jax.jit(lambda p, i: blocks_ratio(cfg, p, i))
    for pair in args.pairs.split(","):
        for embed_std, gain in [map(float, pair.split(":"))]:
            p = gen.params = at(params, cfg, embed_std, gain)
            gen.warm(args.rows, bucket)
            ids = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, bucket)),
                              jnp.int32)
            rho = float(ratio(p, ids))
            accepted, steps, secs, distinct, apart = [], [], [], [], []
            for b in range(args.batches):
                encs = [Encoding(
                    ids=[*before, *rng.integers(2, cfg.vocab_size, n),
                         *after], attention_mask=[1] * (n + 42),
                    offsets=[(0, 0)] * (n + 42)) for n in lengths[b]]
                t = time.perf_counter()
                out = gen.generate([], new, encodings=encs, bucket=bucket,
                                   padded_rows=args.rows)
                secs.append(time.perf_counter() - t)
                for r in out:
                    bits = [e["accepted"] for e in r.trajectory
                            if "accepted" in e]
                    accepted += bits
                    steps.append(len(bits))
                    distinct.append(len(set(r.token_ids)))
                # do the rows say different things?  distinct tokens at
                # the same index over the rows, as a share of the rows
                apart += [len(set(col)) / len(col) for col in zip(
                    *(r.token_ids for r in out))]
            print(json.dumps({
                "embed_std": embed_std, "eh_embed_gain": gain, "rho": rho,
                "accept_rate": float(np.mean(accepted)),
                "steps_mean": float(np.mean(steps)),
                "steps_max_mean": float(np.mean(
                    np.asarray(steps).reshape(args.batches, -1).max(1))),
                "distinct_tokens_mean": float(np.mean(distinct)),
                "rows_apart_share": float(np.mean(apart)),
                "generation_s": [round(s, 4) for s in secs]}), flush=True)
            gen.params = p = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
