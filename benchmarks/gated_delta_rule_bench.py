"""The gated delta rule (``ops/gated_delta_rule.py``) on the chip, at the
shape the ``olmo_hybrid`` guard serves: ``[rows, 30, 8192, 96 / 192]``
bfloat16, right-padded rows of 2k-8k real tokens, and 8 rows of one token.

  1. numerics: the chunked op (the Pallas kernel) against the token-by-token
     recurrence (``gated_delta_step`` under ``lax.scan``, float32) — the
     outputs at the real positions and the final state, which has to be the
     state at each row's TRUE length —, against the plain ``jax.numpy``
     pass over the same chunks, and for two heads of the longest row
     against the recurrence in float64 on the host;
  2. ms a call of the whole op by real length (the stage that no state
     decides and the sequential pass together, as the decoder calls it), of
     the plain pass in its place, and of the one-token step at 8 rows;
  3. the same by chunk size.

Results -> chiprun_out/gated_delta_rule_bench.json (the chip tool brings it
back); nothing in the program reads it.  ``--small`` runs a toy shape (the
CPU rehearsal).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _ms(fn, *args, iters=3):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _inputs(rng, B, H, S, dk, dv, dtype):
    import jax.numpy as jnp

    q = rng.standard_normal((B, H, S, dk), np.float32)
    k = rng.standard_normal((B, H, S, dk), np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((B, H, S, dv), np.float32)
    # a head's decay a token log-uniform over 1e-4 .. 0.1, beta in (0, 2)
    rate = np.exp(rng.uniform(np.log(1e-4), np.log(0.1), (1, H, 1)))
    g = -rate * rng.uniform(0.5, 1.5, (B, H, S))
    beta = rng.uniform(0.05, 1.95, (B, H, S))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--out", default="chiprun_out/gated_delta_rule_bench.json")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.ops import gated_delta_rule as G

    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind}}
    H, S, dk, dv = (3, 256, 12, 24) if args.small else (30, 8192, 96, 192)
    lengths = [S // 4, S // 2, S - 7] if args.small else [2049, 4400, 8000]
    rng = np.random.default_rng(47)

    def recurrence(q, k, v, g, beta, lengths):
        real = (jnp.arange(q.shape[2])[None] < lengths[:, None])[:, None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)

        def token(s, t):
            o, s = G.gated_delta_step(s, *t)
            return s, o

        xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
        s0 = jnp.zeros(q.shape[:2] + (q.shape[3], v.shape[3]), jnp.float32)
        s, o = jax.lax.scan(token, s0, xs)
        return jnp.moveaxis(o, 0, 2), s

    op = jax.jit(lambda *a: G.chunk_gated_delta_rule(*a[:5], lengths=a[5]))
    plain = jax.jit(lambda *a: G.chunk_gated_delta_rule(
        *a[:5], lengths=a[5], kernel="jnp"))
    ref = jax.jit(recurrence)
    report["numerics"], report["ms_by_length"] = [], []
    for n in lengths:
        x = _inputs(rng, 1, H, S, dk, dv, jnp.bfloat16)
        ln = jnp.asarray([n], jnp.int32)
        o, s = op(*x, ln)
        o_ref, s_ref = ref(*x, ln)
        o_pl, s_pl = plain(*x, ln)
        f = lambda a: np.asarray(a, np.float32)  # noqa: E731
        scale_o = float(np.abs(f(o_ref)[:, :, :n]).max())
        scale_s = float(np.abs(f(s_ref)).max())
        row = {"real_tokens": n,
               "o_max_abs_err_vs_recurrence": float(np.abs(
                   f(o)[:, :, :n] - f(o_ref)[:, :, :n]).max()),
               "o_max_abs": scale_o,
               "state_max_abs_err_vs_recurrence": float(np.abs(
                   f(s) - f(s_ref)).max()),
               "state_max_abs": scale_s,
               "o_max_abs_err_vs_plain_pass": float(np.abs(
                   f(o)[:, :, :n] - f(o_pl)[:, :, :n]).max()),
               "state_max_abs_err_vs_plain_pass": float(np.abs(
                   f(s) - f(s_pl)).max())}
        if n == lengths[-1]:
            # the ground truth, float64 on the host, for the head that
            # forgets slowest and the one that forgets fastest
            rate = -np.asarray(x[3], np.float64)[0].mean(-1)
            for h in (int(rate.argmin()), int(rate.argmax())):
                qh, kh, vh, gh, bh = (np.asarray(
                    a[0, h].astype(jnp.float32), np.float64) for a in x)
                want = np.zeros((dk, dv))
                for t in range(n):
                    want *= np.exp(gh[t])
                    want += np.outer(kh[t], bh[t] * (vh[t] - want.T @ kh[t]))
                row[f"head_{h}"] = {
                    "mean_decay_a_token": float(rate[h]),
                    "state_max_abs": float(np.abs(want).max()),
                    "op_state_err_vs_float64": float(np.abs(
                        f(s)[0, h] - want).max()),
                    "device_recurrence_state_err_vs_float64": float(np.abs(
                        f(s_ref)[0, h] - want).max())}
        report["numerics"].append(row)
        print("numerics", json.dumps(row), flush=True)
        row = {"real_tokens": n, "op_ms": _ms(op, *x, ln),
               "plain_pass_ms": _ms(plain, *x, ln)}
        report["ms_by_length"].append(row)
        print("ms", json.dumps(row), flush=True)
    # the stage no state decides, alone
    x = _inputs(rng, 1, H, S, dk, dv, jnp.bfloat16)
    stage1 = jax.jit(lambda q, k, v, g, b: G._chunk_operands(
        q, k, v, g, b, G.CHUNK))
    report["chunk_operands_ms"] = _ms(stage1, *x)
    print("chunk operands alone ms", report["chunk_operands_ms"], flush=True)
    report["ms_by_chunk"] = []
    for chunk in (32, 64, 128):
        fn = jax.jit(lambda *a, c=chunk: G.chunk_gated_delta_rule(
            *a, chunk=c))
        row = {"chunk": chunk, "op_ms": _ms(fn, *x)}
        report["ms_by_chunk"].append(row)
        print("ms", json.dumps(row), flush=True)
    # 8 rows of one token
    B = 8
    x1 = _inputs(rng, B, H, 1, dk, dv, jnp.bfloat16)
    s0 = jnp.asarray(rng.standard_normal((B, H, dk, dv)), jnp.float32)
    step = jax.jit(lambda s, q, k, v, g, b: G.gated_delta_step(
        s, q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0], b[:, :, 0]))
    report["step_8_rows_ms"] = _ms(step, s0, *x1, iters=20)
    print("step of 8 rows ms", report["step_8_rows_ms"], flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
