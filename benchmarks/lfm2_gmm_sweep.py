"""On-device sweep of the megablox tiling at the lfm2_moe guard's expert
matrices (64 experts, ``[2048, 3072]`` gate+up and ``[1536, 2048]`` down,
bfloat16), at the two regimes the cell runs: a prefill row (8192 tokens
top-4 = 32768 sorted pairs of which about 17,600 are real) and a decode
forward of 8 rows (32 pairs).  The table behind ``models/experts.py``
``_megablox``'s tiling for these widths (PERF.md section 6, PR 32);
``benchmarks/moe_gmm_bench.py`` is the same for the sdar_moe widths.

    python benchmarks/lfm2_gmm_sweep.py [--out chiprun_out/lfm2_gmm_sweep.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/lfm2_gmm_sweep.json")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if jax.devices()[0].platform != "tpu":
        print("lfm2_gmm_sweep: needs a TPU", file=sys.stderr)
        return 2
    E, H, I = 64, 2048, 1536
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    weights = {"gate_up": (jax.random.normal(key, (E, H, 2 * I), jnp.bfloat16)
                           * 0.02),
               "down": (jax.random.normal(key, (E, I, H), jnp.bfloat16)
                        * 0.02)}
    # group sizes as a router with uneven load gives them
    load = np.exp(0.5 * rng.standard_normal(E))
    load /= load.sum()
    regimes = {"prefill_row": (32768, 17600), "decode_8_rows": (32, 32)}
    tilings = [(128, 1024, 768), (256, 1024, 768), (512, 1024, 768),
               (256, 1024, 1024), (512, 1024, 1024), (512, 512, 1024),
               (256, 2048, 1024), (512, 2048, 512), (512, 768, 1024),
               (256, 1536, 1024), (512, 1536, 512), (512, 1024, 1536),
               (1024, 512, 1024), (1024, 1024, 512)]
    rows = []
    for regime, (m, real) in regimes.items():
        sizes = rng.multinomial(real, load).astype(np.int32)
        gs = jnp.asarray(sizes)
        for which, (k, n) in (("gate_up", (H, 2 * I)), ("down", (I, H))):
            lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
            for t in tilings:
                tiling = (min(t[0], m), min(t[1], k), min(t[2], n))
                if m % tiling[0]:
                    continue
                fn = jax.jit(lambda a, b, g, _t=tiling: gmm(
                    a, b, g, preferred_element_type=a.dtype, tiling=_t))
                try:
                    jax.block_until_ready(fn(lhs, weights[which], gs))
                except Exception as exc:  # refused by the compiler: noted
                    rows.append({"regime": regime, "matmul": which,
                                 "tiling": tiling,
                                 "error": str(exc)[:120]})
                    continue
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = fn(lhs, weights[which], gs)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) / args.iters * 1e3
                tf = real * 2 * k * n / ms / 1e9
                rows.append({"regime": regime, "matmul": which,
                             "tiling": tiling, "ms": ms, "tflops": tf})
                print(f"{regime} {which} {tiling}: {ms:.3f} ms "
                      f"({tf:.1f} TFLOP/s)", flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
