"""On-device probe of the lfm2_moe guard's two ways to bound a prefill in
tokens, at the cell's own widths (``chipbench/configs/lfm2-24b-a2b-guard``:
9 layers, 10.36 GB of bfloat16): rows mapped INSIDE one program
(``models/lfm2_moe.py`` ``prefill``: ``jax.lax.map``) against a program a
row (the one-row prefill called once a row, its cache written into the
batch's).  Also one decode step of all rows.  The table behind the choice
in ``models/lfm2_moe.py`` (PERF.md section 6, PR 32).

    python benchmarks/lfm2_prefill_probe.py [--out chiprun_out/lfm2_prefill_probe.json]

Needs one TPU chip; weights are drawn on the device (N(0, 0.02); the
router's spread is not the cell's: experts touched a decode step are
printed beside the time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/lfm2_prefill_probe.json")
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.models import lfm2_moe as M

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"lfm2_prefill_probe: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(__file__), "..", "chipbench",
                           "configs", "lfm2-24b-a2b-guard",
                           "model.json")) as f:
        cfg = M.Lfm2MoeConfig.from_hf(json.load(f))
    S, cache_len, B = 8192, 8256, args.rows

    def draw(key, shape, std=0.02, dtype=jnp.bfloat16):
        return (jax.random.normal(key, shape, jnp.float32) * std) \
            .astype(dtype)

    draw = jax.jit(draw, static_argnums=(1, 2, 3))
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 200))
    H, I, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    W, V = cfg.intermediate_size, cfg.vocab_size
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        p = {"norm1": jnp.ones(H, jnp.bfloat16),
             "norm2": jnp.ones(H, jnp.bfloat16)}
        if kind == "conv":
            p.update(in_proj=draw(next(keys), (H, 3 * H)),
                     conv_w=draw(next(keys), (3, H), 0.5),
                     out_proj=draw(next(keys), (H, H)))
        else:
            p.update(q_proj=draw(next(keys), (H, 2048)),
                     k_proj=draw(next(keys), (H, 512)),
                     v_proj=draw(next(keys), (H, 512)),
                     o_proj=draw(next(keys), (2048, H)),
                     q_norm=jnp.full(64, 1.5, jnp.bfloat16),
                     k_norm=jnp.full(64, 1.5, jnp.bfloat16))
        if cfg.is_sparse(i):
            p.update(router=draw(next(keys), (H, E)),
                     expert_bias=draw(next(keys), (E,), 0.05, jnp.float32),
                     gate_up=draw(next(keys), (E, H, 2 * I)),
                     down=draw(next(keys), (E, I, H)))
        else:
            p.update(gate_up=draw(next(keys), (H, 2 * W)),
                     down=draw(next(keys), (W, H)))
        layers.append(p)
    params = {"embed": draw(next(keys), (V, H), 0.07), "layers": layers,
              "norm": jnp.ones(H, jnp.bfloat16)}
    n_params = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    print(f"parameters on the device: {n_params / 1e6:.1f} M", flush=True)

    rng = np.random.default_rng(0)
    lengths = np.exp(rng.uniform(np.log(2090), np.log(8041), B)) \
        .astype(np.int32)
    ids = rng.integers(2, V, (B, S)).astype(np.int32)
    ids_dev, len_dev = jnp.asarray(ids), jnp.asarray(lengths)

    mapped = jax.jit(lambda p, i, n: M.prefill(cfg, p, i, n, cache_len))

    def one_row(p, i, n):
        kv, conv, logits, experts, load = M._prefill_rows(
            cfg, p, i, n, cache_len)
        return {"kv": kv, "conv": conv}, logits, experts, load

    one_row = jax.jit(one_row)

    def put_row(cache, row, at):
        return jax.tree_util.tree_map(
            lambda c, r: jax.lax.dynamic_update_slice(
                c, r, (at,) + (0,) * (c.ndim - 1)), cache, row)

    put_row = jax.jit(put_row, donate_argnums=(0,))

    def a_program_a_row(cache):
        outs = []
        for b in range(B):
            row, logits, experts, load = one_row(
                params, ids_dev[b:b + 1], len_dev[b:b + 1])
            cache = put_row(cache, row, b)
            outs.append((logits, load))
        return cache, outs

    def timed(fn, sync):
        fn()  # compile
        times = []
        for _ in range(args.iters):
            t = time.perf_counter()
            out = fn()
            jax.block_until_ready(sync(out))
            times.append(time.perf_counter() - t)
        return times, out

    results = {"rows": B, "lengths": lengths.tolist(),
               "parameters": n_params}
    t_map, out = timed(lambda: mapped(params, ids_dev, len_dev),
                       lambda o: o[1])
    cache, _, aux = out
    results["mapped_s"] = t_map
    print(f"rows mapped inside one program: {t_map}", flush=True)
    empty = jax.tree_util.tree_map(
        jnp.zeros_like, {"kv": cache["kv"], "conv": cache["conv"]})
    state = {"cache": empty}

    def per_row():
        state["cache"], outs = a_program_a_row(state["cache"])
        return outs

    t_row, _ = timed(per_row, lambda o: o[-1][0])
    results["program_a_row_s"] = t_row
    print(f"a program a row: {t_row}", flush=True)

    step = jax.jit(lambda p, c, t, q: M.decode(cfg, p, c, t, q),
                   donate_argnums=(1,))
    tok = jnp.asarray(rng.integers(2, V, B).astype(np.int32))
    pos = len_dev
    cache, logits, aux = step(params, cache, tok, pos)
    times = []
    for k in range(10):
        t = time.perf_counter()
        cache, logits, aux = step(params, cache, tok, pos + 1 + k)
        load = np.asarray(aux["load"])
        times.append(time.perf_counter() - t)
    results["decode_step_s"] = times
    results["decode_experts_touched_per_layer"] = float(load[:, 2].mean())
    print(f"decode step of {B} rows (readback of load included): {times}; "
          f"experts touched a layer {load[:, 2].mean():.1f}", flush=True)
    stats = dev.memory_stats() or {}
    results["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
    print(f"peak bytes in use {results['peak_bytes_in_use']}", flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
