"""On-device probe of the lfm2_moe guard's prefill at the cell's own widths
(``chipbench/configs/lfm2-24b-a2b-guard``: 9 layers, 10.36 GB of bfloat16).

``--groups 1,2,4,8``: rows mapped inside one program a GROUP at a time
(``models/lfm2_moe.py`` ``_prefill_groups``), for each group size the
whole prefill's ms, the ``moe/gmm`` scope's device ms a row-layer (one
traced call, read as the benchmark's ``ar_moe_gmm_roofline`` reads it),
the compiler's temporaries and the allocator's peak: the table behind
``mapped_prefill.rows_per_group`` (PERF.md section 6, PR 37;
``benchmarks/results/lfm2_prefill_groups.json``).  A group that does not
fit the device is recorded as such.  Then the two ways to bound a prefill
in tokens that PR 32 chose between: rows mapped INSIDE one program
(``prefill``, at the rule's group) against a program a row (the one-row
prefill called once a row, its cache written into the batch's), and one
decode step of all rows.

    python benchmarks/lfm2_prefill_probe.py [--groups 1,2,4,8] [--out FILE]

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


SCOPES = ("embed_tokens", "conv", "attn", "mlp", "moe/router", "moe/sort",
          "moe/gmm", "moe/combine", "lm_head")


def device_ms_by_scope(log_dir: str):
    """One traced call's device ms under each of ``SCOPES`` (an op's scope
    is the ``tf_op`` path the benchmark's readers take from the trace
    file), everything else under ``other``, and the ten longest ops."""
    from chipbench import reduce_trace
    from chipbench.layer_metrics import _gen_spans

    path = reduce_trace.find_xplane(log_dir)
    reduced = reduce_trace.reduce(path)
    tf_ops = _gen_spans._tf_ops(path)
    # a mapped prefill's ``while`` op covers its body's ops: not work of
    # its own (busy time is the union)
    ms = dict.fromkeys(SCOPES + ("other",), 0.0)
    for name, secs in reduced["op_seconds"].items():
        if reduce_trace.short_name(name).startswith("while"):
            continue
        tf_op = f"/{tf_ops.get(name, '')}/"
        scope = next((s for s in SCOPES if f"/{s}/" in tf_op), "other")
        ms[scope] += secs * 1e3
    return reduced["busy_s"] * 1e3, ms, [
        [n, s * 1e3] for n, s in reduce_trace.top_ops(reduced, 12)]


def measure_group(G, cfg, params, ids, lengths, cache_len, layers, timed,
                  dev):
    """One group size's row of the table."""
    import tempfile

    import jax

    from semantic_router_tpu.models import lfm2_moe as M

    row = {"rows_per_group": G}
    fn = jax.jit(lambda p, i, n: M._prefill_groups(cfg, p, i, n, cache_len,
                                                   G))
    try:
        compiled = fn.lower(params, ids, lengths).compile()
        row["temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
        times, _ = timed(lambda: compiled(params, ids, lengths),
                         lambda o: o[1])
        with tempfile.TemporaryDirectory() as log_dir:
            jax.profiler.start_trace(log_dir)
            try:
                jax.block_until_ready(compiled(params, ids, lengths)[1])
            finally:
                jax.profiler.stop_trace()
            busy_ms, by_scope, top = device_ms_by_scope(log_dir)
    except Exception as exc:  # a group the device cannot hold
        if "RESOURCE_EXHAUSTED" not in str(exc):
            raise
        row["does_not_fit"] = str(exc).splitlines()[0][:200]
        return row
    row["prefill_ms"] = [t * 1e3 for t in times]
    row["device_busy_ms"] = busy_ms
    row["device_ms_by_scope"] = by_scope
    row["gmm_ms_per_row_layer"] = by_scope["moe/gmm"] \
        / (ids.shape[0] * layers)
    row["top_ops_ms"] = top
    # the allocator's peak so far: it is this group's while the groups
    # are measured in ascending order
    row["peak_bytes_in_use"] = int(
        (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    return row


def cell_inputs(B: int, S: int = 8192):
    """The cell's configuration, its parameters drawn on the device and
    ``B`` rows of 2090-8041 tokens: ``(cfg, params, ids [B, S], lengths
    [B] on the device, lengths)``."""
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.models import lfm2_moe as M

    with open(os.path.join(os.path.dirname(__file__), "..", "chipbench",
                           "configs", "lfm2-24b-a2b-guard",
                           "model.json")) as f:
        cfg = M.Lfm2MoeConfig.from_hf(json.load(f))

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
    rng = np.random.default_rng(0)
    lengths = np.exp(rng.uniform(np.log(2090), np.log(8041), B)) \
        .astype(np.int32)
    ids = rng.integers(2, V, (B, S)).astype(np.int32)
    return cfg, params, jnp.asarray(ids), jnp.asarray(lengths), lengths


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/lfm2_prefill_probe.json")
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--groups", default="1,2,4,8",
                    help="rows a group to measure, ascending")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.models import lfm2_moe as M

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"lfm2_prefill_probe: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    cfg, params, ids_dev, len_dev, lengths = cell_inputs(args.rows)
    S, cache_len, B, V = 8192, 8256, args.rows, cfg.vocab_size
    n_params = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    print(f"parameters on the device: {n_params / 1e6:.1f} M", flush=True)
    rng = np.random.default_rng(1)

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
               "parameters": n_params, "device_kind": dev.device_kind,
               "bytes_limit": (dev.memory_stats() or {}).get("bytes_limit"),
               "rule_rows_per_group": M.CachedModel(cfg).rows_per_group(
                   params, B, S, cache_len),
               "groups": []}
    print(f"{dev.device_kind}: bytes_limit {results['bytes_limit']}, the "
          f"rule gives {results['rule_rows_per_group']} rows a group",
          flush=True)
    layers = sum(cfg.is_sparse(i) for i in range(cfg.num_hidden_layers))
    for G in [int(g) for g in args.groups.split(",")]:
        results["groups"].append(
            measure_group(G, cfg, params, ids_dev, len_dev, cache_len,
                          layers, timed, dev))
        print(results["groups"][-1], flush=True)
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
