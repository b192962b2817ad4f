"""On-device Pallas flash-attention benchmark + numerics validation.

The evidence the kernel owes (SURVEY.md N8/N12; reference numbers
paper/sections/evaluation.tex:83-121):
  1. numerics: Pallas kernel vs the dense/chunked JAX oracle, on the real
     chip (not interpret mode) — global, sliding-window, causal, padded.
  2. latency: flash vs XLA dense SDPA at 512..32K (3-classifier batch
     geometry, B=3 H=12 D=64, the reference's "3 concurrent classifiers"
     scenario), expecting dense to OOM/regress at long seq like the
     reference's SDPA did at >=8K (evaluation.tex:92-95).
  3. block sweep: ms per call by (block_q, block_k) at the served
     geometry, f32 and bf16, global and windowed — the table behind
     ``ops/flash_attention.py``'s block rule (PERF.md section 6).
  4. end-to-end classifier sweep: mmBERT-32K-geometry ModernBERT b=1 at
     512..32768 tok vs the MI300X FP16 numbers (evaluation.tex:50-57).
  5. ``--lengths``, alone: ms per call by the row's REAL length in a
     bucket of 8192, with the kernel handed the length and without, at
     the long-prompt guards' calls (causal / windowed / selected, D 64 and
     128), and one shape's time by the precision of its two products'
     operands (PERF.md section 6, PR 43) -> chiprun_out/
     flash_bench_lengths.json.

Results stream into --out (default chiprun_out/flash_bench.json, which the
chip tool brings back) after every section so an interrupted run still
leaves partial evidence.  Diagnostics on stderr; the file is the artifact,
and nothing in the program reads it.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _sync_time(fn, *args, warmup=1, iters=3):
    """Time jitted ``fn(*args)`` -> scalar; device_get is the sync
    primitive (the result bytes have arrived)."""
    import jax

    for _ in range(warmup):
        jax.device_get(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.device_get(out)
    return (time.perf_counter() - t0) / iters


def _flush(report, path):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


def run_numerics(report, out_path):
    """Pallas-on-chip vs dense oracle; max abs error in f32."""
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.ops.attention import (
        chunked_sdpa,
        padding_bias,
        sdpa,
        sliding_window_bias,
    )
    from semantic_router_tpu.ops.flash_attention import flash_attention_pallas

    rng = np.random.default_rng(0)
    B, H, S, D = 2, 4, 512, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
               for _ in range(3))
    lens = jnp.asarray([S, S - 77])
    mask = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.int32)

    cases = {}

    def check(name, flash_out, oracle_out, valid_mask=None):
        err = jnp.abs(flash_out.astype(jnp.float32) -
                      oracle_out.astype(jnp.float32))
        if valid_mask is not None:
            err = err * valid_mask[:, None, :, None]
        cases[name] = float(jnp.max(err))
        sys.stderr.write(f"numerics {name}: max_abs_err={cases[name]:.2e}\n")

    check("global", flash_attention_pallas(q, k, v),
          chunked_sdpa(q, k, v))
    check("global_padded", flash_attention_pallas(q, k, v, mask),
          chunked_sdpa(q, k, v, key_padding_mask=mask), mask)
    check("window128", flash_attention_pallas(q, k, v, window=128),
          chunked_sdpa(q, k, v, window=128))
    check("window128_padded",
          flash_attention_pallas(q, k, v, mask, window=128),
          chunked_sdpa(q, k, v, key_padding_mask=mask, window=128), mask)
    S2 = S
    causal_bias = jnp.triu(jnp.full((S2, S2), -1e30, jnp.float32), k=1)[
        None, None]
    check("causal", flash_attention_pallas(q, k, v, causal=True),
          sdpa(q, k, v, bias=causal_bias))
    # bf16 in/out (the serving dtype)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    err_bf16 = jnp.max(jnp.abs(
        flash_attention_pallas(qb, kb, vb).astype(jnp.float32) -
        chunked_sdpa(q, k, v)))
    cases["global_bf16_vs_f32_oracle"] = float(err_bf16)
    sys.stderr.write(f"numerics bf16: max_abs_err={cases['global_bf16_vs_f32_oracle']:.2e}\n")

    report["numerics"] = {
        "platform": jax.default_backend(),
        "shape": [B, H, S, D],
        "max_abs_err": cases,
        "pass_f32": all(v < 2e-5 for k, v in cases.items()
                        if "bf16" not in k),
        "pass_bf16": cases["global_bf16_vs_f32_oracle"] < 3e-2,
    }
    _flush(report, out_path)


def run_kernel_sweep(report, out_path, seqs):
    """flash vs XLA dense SDPA; B=3 (3 concurrent classifiers), H=12, D=64."""
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.ops.attention import (
        padding_bias,
        sdpa,
        sliding_window_bias,
    )
    from semantic_router_tpu.ops.flash_attention import flash_attention_pallas

    B, H, D = 3, 12, 64
    rows = []
    for S in seqs:
        rng = np.random.default_rng(S)
        q, k, v = (jnp.asarray(
            rng.standard_normal((B, H, S, D)).astype(np.float32),
            jnp.bfloat16) for _ in range(3))
        row = {"seq": S}

        flash_fn = jax.jit(lambda q, k, v: flash_attention_pallas(
            q, k, v).sum())
        try:
            dt = _sync_time(flash_fn, q, k, v)
            row["flash_global_ms"] = round(dt * 1e3, 2)
        except Exception as exc:
            row["flash_global_ms"] = None
            row["flash_global_error"] = f"{type(exc).__name__}"[:80]

        flash_local = jax.jit(lambda q, k, v: flash_attention_pallas(
            q, k, v, window=128).sum())
        try:
            dt = _sync_time(flash_local, q, k, v)
            row["flash_window128_ms"] = round(dt * 1e3, 2)
        except Exception as exc:
            row["flash_window128_ms"] = None
            row["flash_window128_error"] = f"{type(exc).__name__}"[:80]

        dense_fn = jax.jit(lambda q, k, v: sdpa(q, k, v).sum())
        try:
            dt = _sync_time(dense_fn, q, k, v)
            row["dense_sdpa_ms"] = round(dt * 1e3, 2)
        except Exception as exc:
            row["dense_sdpa_ms"] = None
            row["dense_sdpa_error"] = f"{type(exc).__name__}: {exc}"[:120]

        if row.get("flash_global_ms") and row.get("dense_sdpa_ms"):
            row["speedup_vs_dense"] = round(
                row["dense_sdpa_ms"] / row["flash_global_ms"], 2)
        sys.stderr.write(f"kernel sweep {row}\n")
        rows.append(row)
        report["kernel_sweep"] = {
            "geometry": {"batch": B, "heads": H, "head_dim": D,
                         "dtype": "bfloat16"},
            "reference": "MI300X SDPA vs CK-FA, evaluation.tex:83-96 "
                         "(4K: 167->51ms; >=8K SDPA OOM)",
            "rows": rows,
        }
        _flush(report, out_path)


# the served geometry (mmBERT-32K: 12 heads x 64) at every default bucket
# from 512 up, with the padded batch of 8 at the benchmark's bucket
SWEEP_SHAPES = ((1, 512), (1, 2048), (1, 8192), (8, 8192), (1, 32768))
SWEEP_BLOCKS_Q = (128, 256, 512, 1024)
SWEEP_BLOCKS_K = (128, 256, 512, 1024, 2048)


def _time_block_pair(q, k, v, window, bq, bk):
    """{compile_s, ms, calls} of the kernel at one block pair, or {ms:
    None, error}.  A call's time is a ``fori_loop`` of n dependent calls
    inside one program over n, so no host dispatch is in it."""
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.ops.flash_attention import flash_attention_pallas

    def loop(n, q, k, v):
        return jax.lax.fori_loop(
            0, n, lambda _, x: flash_attention_pallas(
                x, k, v, window=window, block_q=bq, block_k=bk), q)

    def run(fn, n):
        t0 = time.perf_counter()
        fn(jnp.int32(n), q, k, v).block_until_ready()
        return time.perf_counter() - t0

    try:
        t0 = time.perf_counter()
        fn = jax.jit(loop).lower(jnp.int32(1), q, k, v).compile()
        compile_s = time.perf_counter() - t0
        run(fn, 1)
        n = int(min(200, max(3, 0.25 / (run(fn, 2) / 2))))
        return {"compile_s": round(compile_s, 2), "calls": n,
                "ms": round(min(run(fn, n), run(fn, n)) / n * 1e3, 4)}
    except Exception as exc:
        return {"ms": None,
                "error": f"{type(exc).__name__}: {exc}"[:160]}


def run_block_sweep(report, out_path, shapes=SWEEP_SHAPES):
    """Device milliseconds per kernel call for every (block_q, block_k)
    pair: the table ``ops/flash_attention.py``'s block rule was read
    from (PERF.md section 6, PR 26).  The program reads nothing this
    writes."""
    import jax.numpy as jnp

    from semantic_router_tpu.ops.flash_attention import blocks_for

    H, D = 12, 64
    rows = []
    for dtype in (jnp.float32, jnp.bfloat16):
        for window in (0, 128):
            for B, S in shapes:
                rng = np.random.default_rng(S + B)
                q, k, v = (jnp.asarray(rng.standard_normal(
                    (B, H, S, D)).astype(np.float32), dtype)
                    for _ in range(3))
                for bq, bk in itertools.product(SWEEP_BLOCKS_Q,
                                                SWEEP_BLOCKS_K):
                    if max(bq, bk) > S:
                        continue
                    row = {"dtype": jnp.dtype(dtype).name, "window": window,
                           "batch": B, "seq": S, "block_q": bq,
                           "block_k": bk,
                           "rule": (bq, bk) == blocks_for(S, window),
                           **_time_block_pair(q, k, v, window, bq, bk)}
                    sys.stderr.write(f"block sweep {row}\n")
                    rows.append(row)
                report["block_sweep"] = {
                    "geometry": {"heads": H, "head_dim": D}, "rows": rows}
                _flush(report, out_path)


def print_block_table(rows):
    """One stdout line per swept shape, pairs in sweep order, the block
    rule's own pair starred."""
    for key in sorted({(r["dtype"], r["window"], r["batch"], r["seq"])
                       for r in rows}):
        cells = [f"{r['block_q']}x{r['block_k']}="
                 f"{r['ms'] if r['ms'] is not None else 'FAIL'}"
                 f"{'*' if r['rule'] else ''}"
                 for r in rows if (r["dtype"], r["window"], r["batch"],
                                   r["seq"]) == key]
        print("SWEEP", *key, " ".join(cells))


def _time_chain(call, q, *operands):
    """ms of ``call(q, *operands)``: a ``fori_loop`` of n dependent calls
    (each one's output is the next one's q) inside one program, over n, so
    no host dispatch is in it; the best of two."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda n, q, *ops: jax.lax.fori_loop(
        0, n, lambda _, x: call(x, *ops), q))

    def run(n):
        t0 = time.perf_counter()
        fn(jnp.int32(n), q, *operands).block_until_ready()
        return time.perf_counter() - t0

    run(1)
    n = int(min(200, max(3, 0.25 / (run(2) / 2))))
    return round(min(run(n), run(n)) / n * 1e3, 4)


LENGTHS_BUCKET = 8192
LENGTHS_REAL = (2048, 3072, 4096, 5120, 6144, 7168, 8192)
# (kind, head size, heads): one row of the guards' prefill calls, bfloat16
# — lfm2's causal call is two rows of 32 heads of 64, laguna's 48 heads of
# 128 whole and 72 under its window of 512 keys, dots3's selected call 128
# heads (q/k 192 there; the output chains into q here, so 128)
LENGTHS_CALLS = (("causal", 64, 64), ("causal", 128, 48),
                 ("window", 64, 32), ("window", 128, 72),
                 ("select", 64, 32), ("select", 128, 128))


def run_lengths_sweep(report, out_path):
    """Device ms per kernel call by the row's real length: the kernel
    handed ``lengths`` against the same call without (which does the
    bucket's work whatever the mask says), beside the share of tiles
    ``tiles_for`` says are left."""
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.ops.flash_attention import (
        flash_attention_pallas,
        tiles_for,
    )

    S = LENGTHS_BUCKET
    rows = []
    for kind, D, H in LENGTHS_CALLS:
        window = 2 * 511 if kind == "window" else 0
        keys = jax.random.split(jax.random.PRNGKey(D + H), 4)
        q, k, v = (jax.random.normal(key, (1, H, S, D), jnp.bfloat16)
                   for key in keys[:3])
        kw = dict(causal=True, window=window)
        if kind == "select":  # every third key and the token itself
            kw["select"] = (jax.random.bernoulli(keys[3], 0.33, (1, S, S))
                            | jnp.eye(S, dtype=bool)[None]).astype(jnp.int8)

        def call(q, k, v, lengths, ragged, kw=kw):
            mask = (jnp.arange(S)[None, :] < lengths[:, None]).astype(
                jnp.int32)
            return flash_attention_pallas(
                q, k, v, mask, lengths=lengths if ragged else None, **kw)

        for real in LENGTHS_REAL:
            n = jnp.asarray([real], jnp.int32)
            visited, grid = tiles_for(S, window, True, [real])
            row = {"kind": kind, "head_dim": D, "heads": H, "real": real,
                   "tiles_share": round(visited / grid, 4)}
            for name, ragged in (("ms_lengths", True), ("ms_plain", False)):
                if not ragged and real != S:
                    continue  # the plain call's work is the bucket's
                row[name] = _time_chain(
                    lambda x, k, v, n, r=ragged: call(x, k, v, n, r),
                    q, k, v, n)
            sys.stderr.write(f"lengths sweep {row}\n")
            rows.append(row)
            report["lengths_sweep"] = {"bucket": S, "dtype": "bfloat16",
                                       "rows": rows}
            _flush(report, out_path)


def run_operand_precision(report, out_path):
    """One shape (causal, one row of 48 heads of 128 over 8192 columns),
    three ways: bfloat16 arrays as the guards hand them (the kernel widens
    them and multiplies float32 operands); the same with the operands of
    both products rounded to bfloat16 first (float32 accumulation; the
    kernel as it is, its two product calls wrapped while it is traced);
    float32 arrays.  The ratio of the first two is what bfloat16 operands
    would win."""
    import unittest.mock as mock

    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.ops.flash_attention import flash_attention_pallas

    S, H, D = LENGTHS_BUCKET, 48, 128
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(key, (1, H, S, D), jnp.bfloat16)
               for key in keys)

    def call(x, k, v):
        return flash_attention_pallas(x, k, v, causal=True)

    def rounded(product):
        def wrapped(a, b, *args, **kwargs):
            return product(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                           *args, **kwargs)
        return wrapped

    out = {"shape": [1, H, S, D], "causal": True,
           "ms_bf16_arrays": _time_chain(call, q, k, v)}
    with mock.patch.object(jax.lax, "dot_general",
                           rounded(jax.lax.dot_general)), \
            mock.patch.object(jnp, "dot", rounded(jnp.dot)):
        out["ms_bf16_operands"] = _time_chain(call, q, k, v)
    out["ms_f32_arrays"] = _time_chain(
        call, *(t.astype(jnp.float32) for t in (q, k, v)))
    out["bf16_operands_over_as_served"] = round(
        out["ms_bf16_operands"] / out["ms_bf16_arrays"], 4)
    sys.stderr.write(f"operand precision {out}\n")
    report["operand_precision"] = out
    _flush(report, out_path)


def print_lengths_table(rows):
    """One stdout line per call: ms with the lengths by real length, the
    plain call's ms last."""
    for key in sorted({(r["kind"], r["head_dim"], r["heads"])
                       for r in rows}):
        mine = [r for r in rows
                if (r["kind"], r["head_dim"], r["heads"]) == key]
        cells = [f"{r['real']}={r['ms_lengths']}({r['tiles_share']})"
                 for r in mine]
        print("LENGTHS", *key, " ".join(cells),
              f"plain={mine[-1].get('ms_plain')}")


def run_classifier_sweep(report, out_path, seqs,
                         impls=("flash", "dense")):
    """End-to-end mmBERT-32K-geometry classify latency, b=1, comparing
    attention impls, vs the MI300X FP16 reference (evaluation.tex:50-57).
    On TPU the pair is flash vs dense; a CPU evidence run passes
    ("chunked", "dense") — interpret-mode flash is a non-number there."""
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.models.modernbert import (
        ModernBertConfig,
        ModernBertForSequenceClassification,
    )

    MI300X_MS = {512: 6.0, 1024: 7.7, 2048: 14.1, 4096: 57.6, 8192: 237.0}
    CPU_REF_MS = {512: 120.0, 1024: 263.0, 2048: 809.0, 4096: 2664.0,
                  8192: 9656.0}
    rows = []
    params_cache = {}
    # bf16 is the MXU-native dtype; CPU XLA has no fast bf16 matmul, so
    # an off-chip evidence run measures f32 (and says so in the label)
    dtype = jnp.bfloat16 if jax.default_backend() != "cpu" \
        else jnp.float32
    for impl in impls:
        cfg = ModernBertConfig(
            num_labels=14, max_position_embeddings=32768,
            rope_scaling={"rope_type": "yarn", "factor": 4.0,
                          "original_max_position_embeddings": 8192},
            attention_impl=impl, dtype=dtype)
        model = ModernBertForSequenceClassification(cfg)
        if "p" not in params_cache:
            rng = np.random.default_rng(0)
            ids0 = jnp.asarray(rng.integers(3, cfg.vocab_size, (1, 8)),
                               jnp.int32)
            p = model.init(jax.random.PRNGKey(0), ids0)
            params_cache["p"] = jax.tree_util.tree_map(
                lambda x: x.astype(dtype)
                if x.dtype == jnp.float32 else x, p)
        params = params_cache["p"]
        fn = jax.jit(lambda p, i, m: model.apply(p, i, m).sum())
        for S in seqs:
            rng = np.random.default_rng(S)
            ids = jnp.asarray(rng.integers(3, cfg.vocab_size, (1, S)),
                              jnp.int32)
            mask = jnp.ones((1, S), jnp.int32)
            row = {"seq": S, "attention_impl": impl}
            try:
                iters = 3 if S <= 8192 else 2
                dt = _sync_time(fn, params, ids, mask, warmup=1, iters=iters)
                row["ms"] = round(dt * 1e3, 2)
                if S in MI300X_MS:
                    row["vs_mi300x_gpu"] = round(MI300X_MS[S] / row["ms"], 2)
                if S in CPU_REF_MS:
                    row["vs_ref_cpu"] = round(CPU_REF_MS[S] / row["ms"], 2)
            except Exception as exc:
                row["ms"] = None
                row["error"] = f"{type(exc).__name__}: {exc}"[:120]
            sys.stderr.write(f"classifier sweep {row}\n")
            rows.append(row)
            report["classifier_sweep"] = {
                "model": f"ModernBERT-base geometry, YaRN 32K, "
                         f"{jnp.dtype(dtype).name}, b=1",
                "reference": "MI300X ORT FP16 SDPA, evaluation.tex:50-57",
                "rows": rows,
            }
            _flush(report, out_path)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/flash_bench.json")
    ap.add_argument("--seqs", default="512,2048,4096,8192,16384,32768")
    ap.add_argument("--cls-seqs", default="512,1024,2048,4096,8192,16384,32768")
    ap.add_argument("--skip", default="",
                    help="comma list: numerics,kernel,blocks,classifier")
    ap.add_argument("--lengths", action="store_true",
                    help="only the sweep by a row's real length and the "
                         "operand-precision timing (section 5 above), "
                         "into chiprun_out/flash_bench_lengths.json "
                         "unless --out says otherwise")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="seconds; on expiry the process flushes partial "
                         "results and os._exit(3)s itself")
    args = ap.parse_args()
    skip = set(args.skip.split(",")) if args.skip else set()
    if args.lengths and args.out == ap.get_default("out"):
        args.out = "chiprun_out/flash_bench_lengths.json"

    if args.deadline > 0:
        import threading

        def _expire():
            sys.stderr.write("flash_bench: deadline hit, exiting with "
                             "partial results\n")
            sys.stderr.flush()
            os._exit(3)

        t = threading.Timer(args.deadline, _expire)
        t.daemon = True
        t.start()

    import jax

    platform = jax.default_backend()
    report = {"platform": platform,
              "device": str(jax.devices()[0]),
              "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime())}
    _flush(report, args.out)
    sys.stderr.write(f"flash_bench: platform={platform}\n")

    if args.lengths:
        run_lengths_sweep(report, args.out)
        run_operand_precision(report, args.out)
        print(json.dumps(report["operand_precision"]))
        print_lengths_table(report["lengths_sweep"]["rows"])
        return 0
    seqs = [int(s) for s in args.seqs.split(",")]
    cls_seqs = [int(s) for s in args.cls_seqs.split(",")]
    if "numerics" not in skip:
        run_numerics(report, args.out)
    if "kernel" not in skip:
        run_kernel_sweep(report, args.out, seqs)
    if "blocks" not in skip:
        run_block_sweep(report, args.out)
    if "classifier" not in skip:
        run_classifier_sweep(report, args.out, cls_seqs)
    sweep = report.pop("block_sweep", None)
    print(json.dumps(report, indent=2))
    if sweep:
        print_block_table(sweep["rows"])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
