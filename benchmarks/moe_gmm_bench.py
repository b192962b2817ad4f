"""On-device sweep of the sparse expert layer's grouped matmul
(``models/sdar_moe.py`` ``moe``) at the published SDAR-30B-A3B widths: hidden
2048, 128 experts of width 768, top-8, bfloat16.

Two shapes, the two the guard cell runs: a block forward of 16 rows x 4
tokens (512 routed pairs over 128 experts: every touched expert's three
matrices are read for a handful of rows, memory-bound) and a prefill of
16 x 512 tokens of which a third is real (about 22,000 pairs, compute-
bound).  Two implementations: XLA's own ``jax.lax.ragged_dot`` and the
Pallas ``megablox`` kernel at a few tilings.  The table behind
``models/experts.py`` ``_grouped_matmul``'s choice (PERF.md section 6, PR 28).

    python benchmarks/moe_gmm_bench.py [--out chiprun_out/moe_gmm_bench.json]

Needs one TPU chip; results go to --out and, in short, to stdout.
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
    ap.add_argument("--out", default="chiprun_out/moe_gmm_bench.json")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.models import experts
    from semantic_router_tpu.models import sdar_moe as M

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"moe_gmm_bench: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    cfg = M.SdarMoeConfig(num_hidden_layers=1)
    H, I, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # a row's own scale, as the benchmark's checkpoints draw the router
    scale = jnp.exp(0.5 * jax.random.normal(k4, (E,)))
    p = {"norm2": jnp.ones(H, jnp.bfloat16),
         "router": (jax.random.normal(k1, (H, E)) * 0.02 * scale
                    ).astype(jnp.bfloat16),
         "gate_up": (jax.random.normal(k2, (E, H, 2 * I), jnp.bfloat16)
                     * 0.02),
         "down": (jax.random.normal(k3, (E, I, H), jnp.bfloat16) * 0.02)}
    shapes = {"block_16x4": (64, 64), "block_1x4": (4, 4),
              "prefill_16x512_third_real": (8192, 2731),
              "prefill_16x512_all_real": (8192, 8192),
              "prefill_1x512_third_real": (512, 171)}
    impls = [("ragged_dot", None)] + [
        ("megablox", t) for t in ((128, 1024, 768), (256, 1024, 768),
                                  (512, 1024, 1024), (512, 2048, 1536))]
    rows = []
    for shape_name, (tokens, real) in shapes.items():
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, H),
                              jnp.bfloat16)
        valid = jnp.arange(tokens) < real
        want = None
        for impl, tiling in impls:
            if tiling is None:
                gmm = jax.lax.ragged_dot
            else:
                if (tokens * cfg.num_experts_per_tok) % min(
                        tiling[0], tokens * cfg.num_experts_per_tok):
                    continue

                def gmm(lhs, rhs, sizes, _t=tiling):
                    from jax.experimental.pallas.ops.tpu.megablox import \
                        gmm as megablox

                    t = (min(_t[0], lhs.shape[0]), min(_t[1], lhs.shape[1]),
                         min(_t[2], rhs.shape[-1]))
                    return megablox(lhs, rhs, sizes,
                                    preferred_element_type=lhs.dtype,
                                    tiling=t)
            fn = jax.jit(lambda p, x, v: M.moe(cfg, p, x, v))
            real_gmm, experts._grouped_matmul = experts._grouped_matmul, gmm
            try:
                t0 = time.perf_counter()
                y, _, load = jax.block_until_ready(fn(p, x, valid))
                compile_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = fn(p, x, valid)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) / args.iters * 1e3
                err = None
                if want is None:
                    want = np.asarray(y.astype(jnp.float32))
                else:
                    err = float(np.abs(np.asarray(y.astype(jnp.float32))
                                       - want).max())
                row = {"shape": shape_name, "tokens": tokens, "real": real,
                       "impl": impl, "tiling": tiling, "ms": ms,
                       "compile_s": compile_s,
                       "pairs": float(load[1]), "touched": float(load[2]),
                       "busiest_over_mean": float(load[3]),
                       "max_abs_diff_to_ragged_dot": err}
            except Exception as exc:  # a tiling the compiler refuses
                row = {"shape": shape_name, "impl": impl, "tiling": tiling,
                       "error": f"{type(exc).__name__}: {exc}"[:300]}
            finally:
                experts._grouped_matmul = real_gmm
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
