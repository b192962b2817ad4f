#!/usr/bin/env python3
"""Proof that the router's main path runs on a TPU: ``python chip_smoke.py``.

One process, one chip.  Drives ``runtime.bootstrap.serve()`` — the
``python -m semantic_router_tpu serve`` path: ``build_engine`` from
``classifier_models`` on disk → ``InferenceEngine`` (batcher, fused trunk
group, token head, embedding task) → ``Router`` → ``RouterServer`` — at the
published mmBERT-32K widths with seeded random weights, sends requests over
HTTP, and checks what comes back against a plain ``jax.numpy`` reference on
the same chip.  Any failed check exits non-zero; nothing is caught and
continued.  Without a TPU it says so and exits non-zero.

``--multichip`` (four chips) runs only the mesh paths: the same tasks under
``engine.mesh {dp: 2, tp: 2}`` against a single-device engine, and the ANN
top-k programs on a bank sharded over the four devices against the
single-device bank.

The LAST stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Everything else the run learned (compile seconds, served dtype, peak
memory, wall seconds per phase) is on earlier lines.

The phases are functions that take sizes, so tests/test_chip_smoke.py
runs them on CPU at toy width.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(HERE, ".chip_smoke")  # git-ignored, fixed

INTENT_LABELS = [
    "business", "law", "psychology", "biology", "chemistry", "history",
    "other", "health", "economics", "math", "physics", "computer science",
    "philosophy", "engineering"]
JAILBREAK_LABELS = ["benign", "jailbreak"]
PII_LABELS = ["O", "B-EMAIL_ADDRESS", "I-EMAIL_ADDRESS", "B-PERSON",
              "I-PERSON", "B-PHONE_NUMBER", "I-PHONE_NUMBER", "B-US_SSN",
              "I-US_SSN"]
# signal families the engine's tasks back: every one of them, in every
# decision record, must have been answered by the engine without error
CORE_FAMILIES = ("domain", "jailbreak", "pii", "embedding")
ENGINE_FAMILIES = CORE_FAMILIES + ("preference", "complexity")
# families whose tasks this smoke does not serve, with every decision
# that reads them, leave the derived config: "no error" is then absolute
UNSERVED_SIGNALS = {"fact_check": "fact_check",
                    "user_feedbacks": "user_feedback",
                    "modality": "modality"}

# max |Δprob| engine vs reference.  The served programs hold float32 and
# run their matmuls at the TPU's default precision (one bf16 MXU pass,
# ~3 significant digits) through 22 layers; the reference runs at
# `highest`.  Seeded N(0, 0.02) weights give logit gaps of ~0.5, so a
# relative error of a few 1e-3 on the logits moves a probability by
# well under 1e-2; 2e-2 leaves room without admitting a wrong layer.
PROB_TOLERANCE = 2e-2
EMBED_MIN_COSINE = 0.999
# the gate of tests/test_kernels.py, kernel and reference both at
# `highest` precision so that the comparison is of float32 math
KERNEL_TOLERANCE = 1e-4


class SmokeFailure(Exception):
    """A check failed; the message says which."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Model geometry + traffic shape of one smoke run."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    max_position_embeddings: int
    original_max_position_embeddings: int
    buckets: Tuple[int, ...]
    # token counts of the single requests, one per bucket to exercise
    request_tokens: Tuple[int, ...]
    burst: int            # concurrent short requests
    parity_tokens: Tuple[int, ...]  # texts checked against the reference

    def geometry(self) -> Dict[str, int]:
        """What the checkpoints depend on (the rest is traffic)."""
        return {k: getattr(self, k) for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "max_position_embeddings", "original_max_position_embeddings")}


# mmBERT-32K as published (__graft_entry__.entry): 22 layers, hidden 768,
# 12 heads, GeGLU 1152, vocab 50368, window 128 with every third layer
# global, YaRN x4 to 32768.  The default engine block's 2048 bucket is
# dropped to bound cold compile time (tests/test_tpu_compile.py covers it).
# The burst is 8, not 16: warming its padded batches cost 175 s cold for
# 2/4/8 and another 157 s for 16 alone (my chip run, PR 21).
FULL = Sizes(vocab_size=50368, hidden_size=768, intermediate_size=1152,
             num_hidden_layers=22, num_attention_heads=12,
             max_position_embeddings=32768,
             original_max_position_embeddings=8192,
             buckets=(128, 512, 8192, 32768),
             request_tokens=(40, 300, 5000, 20000), burst=8,
             parity_tokens=(40, 5000))
# the four-chip run compiles each program for the mesh AND for one
# device, at four times the cost per second: short buckets only
FULL_MULTICHIP = dataclasses.replace(
    FULL, buckets=(128, 512), request_tokens=(40, 300), burst=0,
    parity_tokens=())


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    print(f"phase {name}: {time.perf_counter() - t0:.2f} s wall",
          flush=True)


# -- device gate --------------------------------------------------------------


def device_gate(devices: Sequence[Any], want_count: int) -> Dict[str, Any]:
    """The device block of the result line, or SmokeFailure: this smoke
    proves the chip path and has no other mode."""
    check(len(devices) > 0, "JAX reports no devices")
    platform = devices[0].platform
    check(platform == "tpu",
          f"JAX found platform {platform!r}, not a TPU: chip_smoke.py "
          f"proves the chip path and has no CPU mode")
    check(len(devices) == want_count,
          f"this mode needs {want_count} chip(s), JAX reports "
          f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


# -- checkpoints --------------------------------------------------------------


def _trunk_state(sizes: Sizes, rng: np.random.Generator
                 ) -> Dict[str, np.ndarray]:
    """HF ModernBERT trunk state dict (torch layout: [out, in])."""
    H, I = sizes.hidden_size, sizes.intermediate_size

    def w(*shape: int) -> np.ndarray:
        return (0.02 * rng.standard_normal(shape)).astype(np.float32)

    state = {"model.embeddings.tok_embeddings.weight":
             w(sizes.vocab_size, H),
             "model.embeddings.norm.weight": np.ones(H, np.float32),
             "model.final_norm.weight": np.ones(H, np.float32)}
    for i in range(sizes.num_hidden_layers):
        pfx = f"model.layers.{i}."
        if i > 0:
            state[pfx + "attn_norm.weight"] = np.ones(H, np.float32)
        state[pfx + "attn.Wqkv.weight"] = w(3 * H, H)
        state[pfx + "attn.Wo.weight"] = w(H, H)
        state[pfx + "mlp_norm.weight"] = np.ones(H, np.float32)
        state[pfx + "mlp.Wi.weight"] = w(2 * I, H)
        state[pfx + "mlp.Wo.weight"] = w(H, I)
    return state


def _head_state(sizes: Sizes, rng: np.random.Generator, n_labels: int
                ) -> Dict[str, np.ndarray]:
    H = sizes.hidden_size
    return {"head.dense.weight":
            (0.02 * rng.standard_normal((H, H))).astype(np.float32),
            "head.norm.weight": np.ones(H, np.float32),
            "classifier.weight":
            (0.02 * rng.standard_normal((n_labels, H))).astype(np.float32),
            "classifier.bias": np.zeros(n_labels, np.float32)}


def generate_checkpoints(root: str, sizes: Sizes, seed: int
                         ) -> Dict[str, str]:
    """HF-style checkpoint directories (model.safetensors, config.json)
    for intent / jailbreak / pii / embedding plus one shared
    tokenizer.json, made from ``seed``; reused when already complete.
    The three classifiers carry the SAME trunk weights, so the engine's
    content fingerprint fuses them into one trunk group."""
    from safetensors.numpy import save_file
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    tasks = {"intent": INTENT_LABELS, "jailbreak": JAILBREAK_LABELS,
             "pii": PII_LABELS, "embedding": None}
    dirs = {t: os.path.join(root, t) for t in tasks}
    dirs["tokenizer"] = os.path.join(root, "tokenizer")
    stamp = {"seed": seed, "geometry": sizes.geometry()}
    stamp_path = os.path.join(root, "complete.json")
    try:
        with open(stamp_path) as f:
            if json.load(f) == stamp:
                print(f"checkpoints: reusing {root}", flush=True)
                return dirs
    except (OSError, ValueError):
        pass
    rng = np.random.default_rng(seed)
    trunk = _trunk_state(sizes, rng)
    hf_config = {
        "model_type": "modernbert",
        "vocab_size": sizes.vocab_size, "hidden_size": sizes.hidden_size,
        "intermediate_size": sizes.intermediate_size,
        "num_hidden_layers": sizes.num_hidden_layers,
        "num_attention_heads": sizes.num_attention_heads,
        "max_position_embeddings": sizes.max_position_embeddings,
        "rope_scaling": {
            "rope_type": "yarn",
            "factor": sizes.max_position_embeddings
            / sizes.original_max_position_embeddings,
            "original_max_position_embeddings":
            sizes.original_max_position_embeddings},
        "global_attn_every_n_layers": 3, "local_attention": 128,
        "classifier_pooling": "cls",
    }
    for task, labels in tasks.items():
        os.makedirs(dirs[task], exist_ok=True)
        state = dict(trunk)
        cfg = dict(hf_config)
        if labels is not None:
            state.update(_head_state(sizes, rng, len(labels)))
            cfg["id2label"] = {str(i): l for i, l in enumerate(labels)}
        save_file(state, os.path.join(dirs[task], "model.safetensors"))
        with open(os.path.join(dirs[task], "config.json"), "w") as f:
            json.dump(cfg, f)
    # one token per whitespace word, no specials: a text of n words is
    # n tokens, which is how the requests aim at their buckets
    vocab = {"[PAD]": 0, "[UNK]": 1}
    vocab.update({f"w{i}": i for i in range(2, sizes.vocab_size)})
    tok = Tokenizer(WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    os.makedirs(dirs["tokenizer"], exist_ok=True)
    tok.save(os.path.join(dirs["tokenizer"], "tokenizer.json"))
    with open(stamp_path, "w") as f:
        json.dump(stamp, f)
    print(f"checkpoints: generated under {root} from seed {seed}",
          flush=True)
    return dirs


def seeded_text(rng: np.random.Generator, n_tokens: int, vocab_size: int
                ) -> str:
    return " ".join(f"w{i}" for i in rng.integers(2, vocab_size, n_tokens))


# -- config -------------------------------------------------------------------


def _reads_any(node: Any, signal_types: set) -> bool:
    if isinstance(node, dict):
        return node.get("type") in signal_types \
            or any(_reads_any(v, signal_types) for v in node.values())
    if isinstance(node, list):
        return any(_reads_any(v, signal_types) for v in node)
    return False


def write_config(root: str, ckpts: Dict[str, str], sizes: Sizes,
                 mesh: Optional[Dict[str, Any]] = None) -> str:
    """tests/fixtures/router_config.yaml with the smoke's
    ``classifier_models`` and the schema's DEFAULT engine block (only the
    buckets — and, for --multichip, ``mesh`` — are stated)."""
    import yaml

    with open(os.path.join(HERE, "tests", "fixtures",
                           "router_config.yaml")) as f:
        cfg = yaml.safe_load(f)
    engine: Dict[str, Any] = {"seq_len_buckets": list(sizes.buckets)}
    if mesh is not None:
        engine["mesh"] = mesh
    cfg["engine"] = engine
    specs = {"intent": ("sequence", INTENT_LABELS),
             "jailbreak": ("sequence", JAILBREAK_LABELS),
             "pii": ("token", PII_LABELS), "embedding": ("embedding", [])}
    cfg["classifier_models"] = {
        task: {"checkpoint": ckpts[task], "tokenizer": ckpts["tokenizer"],
               "kind": kind, **({"labels": labels} if labels else {})}
        for task, (kind, labels) in specs.items()}
    routing = cfg["routing"]
    for block in UNSERVED_SIGNALS:
        routing["signals"].pop(block, None)
    unserved = set(UNSERVED_SIGNALS.values())
    routing["decisions"] = [d for d in routing["decisions"]
                            if not _reads_any(d, unserved)]
    # seeded random heads call some texts a jailbreak: the decision must
    # then ROUTE (x-vsr-selected-model), not answer from policy
    for d in routing["decisions"]:
        d["plugins"] = [p for p in d.get("plugins", [])
                        if p.get("type") != "fast_response"]
    # the dispatcher evaluates only families a decision reads, and no
    # fixture decision reads pii: add one so the token head serves
    routing["decisions"].append({
        "name": "pii_route", "priority": 150,
        "rules": {"operator": "OR", "conditions": [
            {"type": "pii", "name": routing["signals"]["pii"][0]["name"]}]},
        "modelRefs": [{"model": cfg["default_model"]}],
        "algorithm": {"type": "static"}})
    # every request leaves a decision record for the checks below
    cfg.setdefault("observability", {}).setdefault(
        "explain", {})["sample_rate"] = 1.0
    path = os.path.join(root, "router_config.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f, sort_keys=False)
    return path


# -- serve --------------------------------------------------------------------


@dataclasses.dataclass
class Running:
    server: Any
    tracker: Any
    backend: Any
    engine: Any

    def stop(self) -> None:
        if self.server.watcher:
            self.server.watcher.stop()
        self.server.stop()
        self.server.router.shutdown()
        self.backend.stop()


def print_warmup(report: List[Dict[str, Any]]) -> None:
    for row in report:
        print(f"warmup {row['target']} bucket={row['bucket']} "
              f"rows={row['rows']} {row['seconds']:.2f} s"
              + (f" ERROR {row['error']}" if row["error"] else ""),
              flush=True)


def warm_burst_shapes(engine, sizes: Sizes) -> None:
    """serve()'s warmup compiles batch 1 only; at full width every other
    padded batch costs 7–16 s per program on first use, which a cold
    burst turns into 30 s classify timeouts answered fail-open.  Warm the
    padded batches the burst can form, through the engine's own warmup
    (any failure raises)."""
    batch_sizes = [n for n in (2, 4, 8, 16, 32) if n <= sizes.burst]
    if not batch_sizes:
        return
    (g,) = engine._groups_by_gid.values()
    # a request's trunk items dedup to one row; it embeds its text twice
    # (signals, semantic cache), so embedding batches reach 2 x burst
    for tasks, sizes_ in ((g.members, batch_sizes),
                          (["embedding"],
                           batch_sizes + [2 * batch_sizes[-1]])):
        engine.warmup(tasks=tasks, buckets=[sizes.buckets[0]],
                      batch_sizes=sizes_)
        print_warmup(engine.warmup_report())


def start_router(config_path: str, warmup_timeout_s: float) -> Running:
    """MockVLLMServer + serve(config, block=False), then wait for the
    warmup thread's terminal event.  Every (task, bucket) must have
    warmed: a failed program fails the smoke with the compiler's
    message."""
    from semantic_router_tpu.router.mock_backend import MockVLLMServer
    from semantic_router_tpu.runtime.bootstrap import serve
    from semantic_router_tpu.runtime.events import (
        ENGINE_FAILED,
        WARMUP_DONE,
        default_bus,
    )

    terminal: List[Any] = []
    done = threading.Event()

    def on_event(ev) -> None:
        if ev.stage in (WARMUP_DONE, ENGINE_FAILED):
            terminal.append(ev)
            done.set()

    unsubscribe = default_bus.subscribe(on_event)
    backend = MockVLLMServer().start()
    try:
        server, tracker = serve(config_path, port=0,
                                default_backend=backend.url,
                                watch_config=False, block=False)
    except BaseException:
        backend.stop()
        unsubscribe()
        raise
    run = Running(server, tracker, backend, server.router.engine)
    try:
        check(run.engine is not None, "serve() built no engine")
        check(done.wait(warmup_timeout_s),
              f"warmup did not finish in {warmup_timeout_s:.0f} s")
        report = run.engine.warmup_report()
        print_warmup(report)
        ev = terminal[0]
        check(ev.stage == WARMUP_DONE,
              f"warmup failed: {ev.public()}; startup status "
              f"{tracker.snapshot()}")
        check(report and not any(r["error"] for r in report),
              "warmup report carries errors")
        snap = tracker.snapshot()
        check(snap["ready"] and not snap["failed"],
              f"startup tracker is not ready: {snap}")
        print(f"warmup: {len(report)} (target, bucket) programs sets, "
              f"{sum(r['seconds'] for r in report):.1f} s compile+run",
              flush=True)
    except BaseException:
        run.stop()
        raise
    finally:
        unsubscribe()
    return run


# -- requests -----------------------------------------------------------------


def _chat(url: str, text: str, timeout: float) -> Tuple[int, Dict[str, str]]:
    req = urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps({"model": "auto", "messages": [
            {"role": "user", "content": text}]}).encode(),
        method="POST")
    req.add_header("content-type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        resp.read()
        return resp.status, {k.lower(): v for k, v in resp.headers.items()}


def send_requests(url: str, sizes: Sizes, seed: int,
                  timeout: float = 300.0) -> List[Dict[str, Any]]:
    """One request per entry of ``sizes.request_tokens``, a burst of
    ``sizes.burst`` concurrent short ones, and one repeat of the first."""
    rng = np.random.default_rng(seed + 1)
    out: List[Dict[str, Any]] = []

    def one(name: str, text: str) -> Dict[str, Any]:
        t0 = time.perf_counter()
        status, headers = _chat(url, text, timeout)
        row = {"name": name, "status": status, "headers": headers,
               "seconds": time.perf_counter() - t0}
        print(f"request {name}: {status} model="
              f"{headers.get('x-vsr-selected-model')} "
              f"{row['seconds']:.2f} s", flush=True)
        return row

    singles = [(f"tokens={n}", seeded_text(rng, n, sizes.vocab_size))
               for n in sizes.request_tokens]
    for name, text in singles:
        out.append(one(name, text))
    if sizes.burst:
        texts = [seeded_text(rng, int(rng.integers(8, 100)),
                             sizes.vocab_size) for _ in range(sizes.burst)]
        with ThreadPoolExecutor(sizes.burst) as pool:
            out.extend(pool.map(lambda it: one(f"burst-{it[0]}", it[1]),
                                enumerate(texts)))
    out.append(one("repeat", singles[0][1]))
    return out


def check_responses(url: str, responses: List[Dict[str, Any]]) -> None:
    """Every response is a 200 with a selected model, and its decision
    record (x-vsr-decision-record) shows each engine-backed family
    answered by the engine.  Only a semantic-cache hit (the repeat) may
    come without a record: no signal ran for it."""
    records = []
    for r in responses:
        headers = r["headers"]
        check(r["status"] == 200, f"{r['name']}: HTTP {r['status']}")
        check(bool(headers.get("x-vsr-selected-model")),
              f"{r['name']}: no x-vsr-selected-model header")
        rid = headers.get("x-vsr-decision-record")
        if rid is None:
            check(r["name"] == "repeat"
                  and headers.get("x-vsr-cache-hit") is not None,
                  f"{r['name']}: no decision record and no cache hit")
            print(f"request {r['name']}: semantic-cache hit, no signals "
                  f"ran", flush=True)
            continue
        with urllib.request.urlopen(
                url + f"/debug/decisions/{rid}", timeout=60) as resp:
            records.append(json.loads(resp.read()))
    check_decision_records(records, expected=len(responses) - 1)
    print(f"decision records: {len(records)} checked, every engine-backed "
          f"family from the engine, none with an error", flush=True)


def check_decision_records(records: List[Dict[str, Any]],
                           expected: int) -> None:
    check(len(records) >= expected,
          f"{len(records)} decision records for {expected} requests")
    for rec in records:
        signals = rec.get("signals") or {}
        rid = rec.get("id") or rec.get("record_id")
        for fam in CORE_FAMILIES:
            check(fam in signals,
                  f"record {rid}: family {fam!r} missing ({sorted(signals)})")
        for fam in ENGINE_FAMILIES:
            row = signals.get(fam)
            if row is None:
                continue
            check(not row.get("error"),
                  f"record {rid}: family {fam!r} carries error "
                  f"{row.get('error')!r} — answered fail-open, not by "
                  f"the engine")
            check(row.get("source") in ("engine", "fused_bank"),
                  f"record {rid}: family {fam!r} source "
                  f"{row.get('source')!r}, not the engine")


# -- engine -------------------------------------------------------------------


def served_seq_program(engine) -> Tuple[Any, tuple]:
    """The one trunk group's fused seq program and batch-1 arguments on
    the smallest bucket, placed the way the runner places them."""
    (g,) = engine._groups_by_gid.values()
    fns = g.fns
    b = engine.cfg.seq_len_buckets[0]
    rows = engine._padded_batch(1, mesh=fns.get("mesh"))
    ids, mask = engine._to_device(np.ones((rows, b), np.int32),
                                  np.ones((rows, b), np.int32),
                                  mesh=fns.get("mesh"))
    return fns["seq"], (fns["trunk_params"], fns["demux"]["bank"], ids,
                        mask)


def device_steps(engine) -> int:
    return sum(p["executes"] + p["compiles"]
               for p in engine._runtime_stats.programs())


def check_engine(engine, steps_before: int, platform: str,
                 want_padded_batch: bool) -> None:
    """The engine really stepped on the device, with the chip's attention
    and — on a TPU — the Pallas kernel inside the served program."""
    import jax
    import jax.numpy as jnp

    steps = device_steps(engine)
    check(steps > steps_before,
          f"device step count did not rise ({steps_before} → {steps})")
    shapes = sorted({s for v in engine.shape_census().values() for s in v})
    print(f"device steps: {steps_before} → {steps}; shapes run "
          f"(padded batch, bucket): {shapes}", flush=True)
    for p in engine._runtime_stats.programs():
        print(f"program {p['group']} bucket={p['bucket']} "
              f"{p['variant']}: compiles={p['compiles']} "
              f"({p.get('compile_s_total', 0.0):.1f} s) "
              f"executes={p['executes']} "
              f"ewma={p.get('execute_ewma_s', 0.0) * 1e3:.1f} ms",
              flush=True)
    if want_padded_batch:
        check(any(s[0] > 1 for s in shapes),
              "no padded batch > 1 ran: the burst never batched")
    want_impl = "flash" if platform == "tpu" else None
    for name in engine.tasks():
        impl = engine.task_info(name).get("attention_impl")
        print(f"task {name}: attention_impl={impl}", flush=True)
        if want_impl:
            check(impl == want_impl,
                  f"task {name}: attention_impl {impl!r}, not {want_impl!r}")
    # weights live on the device: a host (numpy) tree would be uploaded
    # again on every step
    for name in engine.tasks():
        leaves = jax.tree_util.tree_leaves(engine._tasks[name].params)
        check(all(isinstance(x, jax.Array)
                  and {d.platform for d in x.devices()} == {platform}
                  for x in leaves),
              f"task {name}: parameters are not all {platform} arrays")
    # the served fused program itself, on the smallest bucket
    seq_fn, args = served_seq_program(engine)
    out = jax.block_until_ready(seq_fn(*args))
    out_platforms = {d.platform for d in out.devices()}
    check(out_platforms == {platform},
          f"step output lives on {out_platforms}, not {platform!r}")
    check(bool(jnp.all(jnp.isfinite(out))), "step output is not finite")
    n_kernel = seq_fn.lower(*args).compile().as_text().count(
        "tpu_custom_call")
    print(f"served fused program: output {out.shape} {out.dtype} on "
          f"{sorted(out_platforms)}, tpu_custom_call x{n_kernel}",
          flush=True)
    if platform == "tpu":
        check(n_kernel > 0,
              "served program holds no tpu_custom_call: a reference ran "
              "under the kernel's name")


def report_served_dtype(engine, configured: str) -> None:
    """engine.dtype has no reader (bootstrap builds ModernBertConfig
    without it): say what the served programs actually compute in."""
    import jax

    (g,) = engine._groups_by_gid.values()
    leaves = jax.tree_util.tree_leaves(g.fns["trunk_params"])
    print(f"served dtype: engine.dtype={configured!r} is configured but "
          f"unread; params {sorted({str(x.dtype) for x in leaves})}, "
          f"module dtype {np.dtype(g.config.dtype).name}, "
          f"jax_default_matmul_precision="
          f"{jax.config.jax_default_matmul_precision!r} (None = the "
          f"platform default: one bf16 MXU pass per float32 matmul on "
          f"TPU)", flush=True)


# -- numerics -----------------------------------------------------------------


def _softmax(x: np.ndarray) -> np.ndarray:
    e = np.exp(x - x.max())
    return e / e.sum()


def check_parity(engine, sizes: Sizes, seed: int) -> None:
    """Engine results against the plain-jnp float32 reference
    (models/reference.py, `highest` precision) on the same device."""
    import jax

    from semantic_router_tpu.models.reference import (
        reference_head,
        reference_hidden,
    )

    rng = np.random.default_rng(seed + 2)
    # every task shares the trunk's geometry: one jitted reference trunk
    trunk_cfg = engine._tasks["intent"].module.config
    ref_hidden = jax.jit(
        lambda trunk, ids, mask: reference_hidden(trunk_cfg, trunk, ids,
                                                  mask))
    for n in sizes.parity_tokens:
        text = seeded_text(rng, n, sizes.vocab_size)
        hidden_of: Dict[int, Any] = {}
        for name in ("intent", "jailbreak", "embedding"):
            t = engine._tasks[name]
            enc = t.tokenizer.encode(text)
            ids = np.asarray(enc.ids, np.int32)[None]
            mask = np.asarray(enc.attention_mask, np.int32)[None]
            cfg = t.module.config
            trunk = t.params["params"]["model"]
            key = id(jax.tree_util.tree_leaves(trunk)[0])
            if key not in hidden_of:
                hidden_of[key] = ref_hidden(trunk, ids, mask)
            ref = np.asarray(reference_head(
                cfg, t.params, hidden_of[key], mask, t.kind))[0]
            if t.kind == "embedding":
                got = engine.embed(name, [text], timeout=300.0)[0]
                cos = float(got @ ref / (np.linalg.norm(got)
                                         * np.linalg.norm(ref)))
                print(f"parity {name} tokens={n}: cosine {cos:.6f}",
                      flush=True)
                check(cos >= EMBED_MIN_COSINE,
                      f"{name} tokens={n}: cosine {cos} < "
                      f"{EMBED_MIN_COSINE}")
                continue
            res = engine.classify(name, text, timeout=300.0)
            ref_p = _softmax(ref[:len(t.labels)].astype(np.float64))
            got_p = np.asarray([res.probs[l] for l in t.labels])
            diff = float(np.max(np.abs(got_p - ref_p)))
            top2 = np.sort(ref_p)[-2:]
            print(f"parity {name} tokens={n}: label {res.label!r} vs "
                  f"{t.labels[int(ref_p.argmax())]!r}, max|dprob| "
                  f"{diff:.2e} (reference margin {top2[1] - top2[0]:.2e})",
                  flush=True)
            check(diff <= PROB_TOLERANCE,
                  f"{name} tokens={n}: max|dprob| {diff} > "
                  f"{PROB_TOLERANCE}")
            # a label can only be held to the reference where the
            # reference's own margin exceeds the tolerance
            if top2[1] - top2[0] > 2 * PROB_TOLERANCE:
                check(res.label == t.labels[int(ref_p.argmax())],
                      f"{name} tokens={n}: label {res.label!r} differs "
                      f"from the reference")


def check_kernels(width: int, task_counts: Sequence[int] = (6, 64),
                  interpret: Optional[bool] = None) -> None:
    """head_epilogue_pallas and bgmv_pallas at D = H = ``width``, and the
    flash kernel at the trunk's head geometry, compiled, against their
    references."""
    import jax
    import jax.numpy as jnp

    from semantic_router_tpu.models.modernbert import activation
    from semantic_router_tpu.ops.bgmv import bgmv_pallas, bgmv_reference
    from semantic_router_tpu.ops.epilogue import (
        head_epilogue_pallas,
        head_epilogue_reference,
    )

    from semantic_router_tpu.ops.attention import (
        padding_bias,
        sdpa,
        sliding_window_bias,
    )
    from semantic_router_tpu.ops.flash_attention import (
        flash_attention_pallas,
    )

    act = activation("gelu")
    rng = np.random.default_rng(0)
    rows = 32
    with jax.default_matmul_precision("highest"):
        S, real = 1024, 900
        q, k, v = (jnp.asarray(rng.standard_normal((2, 12, S, 64)),
                               jnp.float32) for _ in range(3))
        mask = jnp.asarray(np.arange(S)[None] < real, jnp.int32) \
            * jnp.ones((2, 1), jnp.int32)
        for window in (0, 128):
            bias = padding_bias(mask)
            if window:
                bias = bias + sliding_window_bias(S, window)
            f = float(jnp.max(jnp.abs(
                flash_attention_pallas(q, k, v, mask, window=window,
                                       interpret=interpret)
                - sdpa(q, k, v, bias=bias))[:, :, :real]))
            print(f"kernels flash window={window} B=2 H=12 S={S} D=64: "
                  f"max|d| {f:.2e}", flush=True)
            # tests/test_flash_attention.py's atol
            check(f <= 1e-5, f"flash window={window}: {f} > 1e-5")
        for T in task_counts:
            x = jnp.asarray(rng.standard_normal((rows, width)), jnp.float32)
            K = jnp.asarray(0.02 * rng.standard_normal((T, width, width)),
                            jnp.float32)
            b = jnp.asarray(0.1 * rng.standard_normal((T, width)),
                            jnp.float32)
            d = jnp.asarray(0.1 * rng.standard_normal((rows, T, width)),
                            jnp.float32)
            idx = jnp.asarray(rng.integers(0, T, rows), jnp.int32)
            e = float(jnp.max(jnp.abs(
                head_epilogue_pallas(x, K, b, d, act, interpret=interpret)
                - head_epilogue_reference(x, K, b, d, act))))
            g = float(jnp.max(jnp.abs(
                bgmv_pallas(x, K, idx, interpret=interpret)
                - bgmv_reference(x, K, idx))))
            print(f"kernels T={T} D=H={width}: epilogue max|d| {e:.2e}, "
                  f"bgmv max|d| {g:.2e}", flush=True)
            check(e <= KERNEL_TOLERANCE,
                  f"epilogue T={T}: {e} > {KERNEL_TOLERANCE}")
            check(g <= KERNEL_TOLERANCE,
                  f"bgmv T={T}: {g} > {KERNEL_TOLERANCE}")


# -- modes --------------------------------------------------------------------


def _cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def _native_library() -> str:
    """The lexical library is an ignored build output: build it from
    native/ when absent, run without it (pure Python) when that fails."""
    from semantic_router_tpu import native

    if native.available():
        return "present"
    try:
        from semantic_router_tpu.native.build import build

        build(verbose=False)
        native._LOAD_FAILED = False
        return "built from native/" if native.available() else \
            "absent (pure-Python fallback)"
    except Exception as exc:
        return f"absent (pure-Python fallback; build failed: {exc})"


def run_single(sizes: Sizes, seed: int, root: str) -> None:
    import jax

    with phase("checkpoints"):
        ckpts = generate_checkpoints(root, sizes, seed)
        config_path = write_config(root, ckpts, sizes)
    with phase("serve+warmup"):
        run = start_router(config_path, warmup_timeout_s=1000.0)
    try:
        platform = jax.devices()[0].platform
        with phase("burst-shape warmup"):
            warm_burst_shapes(run.engine, sizes)
        steps0 = device_steps(run.engine)
        with phase("requests"):
            responses = send_requests(run.server.url, sizes, seed)
        with phase("response checks"):
            check_responses(run.server.url, responses)
            check_engine(run.engine, steps0, platform,
                         want_padded_batch=sizes.burst > 1)
            report_served_dtype(run.engine, run.server.cfg.engine.dtype)
        with phase("reference parity"):
            check_parity(run.engine, sizes, seed)
    finally:
        run.stop()
    with phase("kernels"):
        check_kernels(sizes.hidden_size)


def run_multichip(sizes: Sizes, seed: int, root: str,
                  ann_rows: int = 65536) -> None:
    """engine.mesh dp=2 x tp=2 against a single-device engine, and the
    sharded ANN bank against the single-device bank."""
    import jax

    from semantic_router_tpu.config import load_config
    from semantic_router_tpu.runtime.bootstrap import build_engine

    with phase("checkpoints"):
        ckpts = generate_checkpoints(root, sizes, seed)
    rng = np.random.default_rng(seed + 3)
    texts = [seeded_text(rng, n, sizes.vocab_size)
             for n in sizes.request_tokens for _ in range(2)]
    results: Dict[str, Any] = {}
    for label, mesh in (("mesh", {"enabled": True, "dp": 2, "tp": 2}),
                        ("single", None)):
        with phase(f"{label} engine"):
            cfg = load_config(write_config(root, ckpts, sizes, mesh=mesh))
            engine = build_engine(cfg)
            try:
                if mesh is not None:
                    check_mesh_placement(engine)
                engine.warmup()
                print_warmup(engine.warmup_report())
                results[label] = {
                    name: [engine.classify(name, t, timeout=300.0)
                           for t in texts]
                    for name in ("intent", "jailbreak")}
                if mesh is not None:
                    check_mesh_program(engine,
                                       jax.devices()[0].platform)
            finally:
                engine.shutdown()
    for name, rows in results["mesh"].items():
        worst = 0.0
        for m, s in zip(rows, results["single"][name]):
            check(m.label == s.label,
                  f"{name}: mesh label {m.label!r} != single {s.label!r}")
            worst = max(worst, max(abs(m.probs[l] - s.probs[l])
                                   for l in s.probs))
        print(f"mesh vs single {name}: {len(rows)} texts, same labels, "
              f"max|dprob| {worst:.2e}", flush=True)
        # both run the same float32 programs at the same precision; tp
        # only re-associates the row-parallel sums (all-reduce order)
        check(worst <= 1e-3, f"{name}: mesh vs single max|dprob| {worst}")
    with phase("ann"):
        check_ann_sharded(ann_rows)


def check_mesh_placement(engine) -> None:
    import jax

    from semantic_router_tpu.engine.mesh import mesh_signature

    sig = mesh_signature(engine._serving_mesh)
    check(sig == (2, 2, 1),
          f"mesh signature {sig}: the engine did not build dp=2 x tp=2 "
          f"(a failed build_serving_mesh serves single-device)")
    (g,) = engine._groups_by_gid.values()
    fns = g.fns
    check(fns["meta"]["mesh"] == (2, 2, 1),
          f"served program set built for mesh {fns['meta']['mesh']}")
    wqkv = fns["trunk_params"]["layers_0"]["attn"]["Wqkv"]["kernel"]
    devs = {s.device for s in wqkv.addressable_shards}
    check(len(devs) == 4, f"Wqkv shards on {len(devs)} device(s), not 4")
    shard_shapes = {s.data.shape for s in wqkv.addressable_shards}
    check(shard_shapes == {(wqkv.shape[0], wqkv.shape[1] // 2)},
          f"Wqkv shard shapes {shard_shapes}: not column-split over tp=2")
    ids = served_seq_program(engine)[1][2]
    bdevs = {s.device for s in ids.addressable_shards}
    bshapes = {s.data.shape for s in ids.addressable_shards}
    check(len(bdevs) == 4 and bshapes == {(1, ids.shape[1])},
          f"batch shards {bshapes} on {len(bdevs)} device(s): not "
          f"row-split over dp=2")
    print(f"mesh placement: signature {sig}, Wqkv {wqkv.shape} as "
          f"{sorted(shard_shapes)} on {len(devs)} devices, batch rows "
          f"split over dp on {len(bdevs)} devices "
          f"({sorted(str(d) for d in jax.devices())})", flush=True)


def check_mesh_program(engine, platform: str) -> None:
    seq_fn, args = served_seq_program(engine)
    text = seq_fn.lower(*args).compile().as_text()
    counts = {k: text.count(k) for k in
              ("all-reduce", "all-gather", "collective-permute",
               "tpu_custom_call")}
    print(f"mesh program text: {counts}", flush=True)
    check(counts["all-reduce"] > 0,
          "no all-reduce in the mesh program: tp's row-parallel sums "
          "are not there")
    if platform == "tpu":
        check(counts["tpu_custom_call"] > 0,
              "no tpu_custom_call in the mesh program")


def check_ann_sharded(rows: int) -> None:
    """TopKPrograms on a DeviceBank sharded over the four devices against
    the single-device bank: equal ids, scores within tolerance."""
    from semantic_router_tpu.ann import bank as ann_bank
    from semantic_router_tpu.ann.search import TopKPrograms
    from semantic_router_tpu.engine.mesh import (
        build_serving_mesh,
        normalize_mesh,
    )

    mesh = build_serving_mesh(
        normalize_mesh({"enabled": True, "dp": 2, "tp": 2}))
    rng = np.random.default_rng(0)
    dim, k = 768, 8
    vecs = rng.standard_normal((rows, dim)).astype(np.float32)
    ids = [f"e{i}" for i in range(rows)]
    queries = vecs[rng.integers(0, rows, 16)] \
        + 0.05 * rng.standard_normal((16, dim)).astype(np.float32)
    lock = ann_bank.MESH_EXEC_LOCK
    contended = not lock.acquire(blocking=False)
    if not contended:
        lock.release()

    def build(m):
        bank = ann_bank.DeviceBank(min_capacity=rows, max_capacity=rows,
                                   mesh=m)
        bank.extend(ids, vecs)
        return bank.publish()

    single, sharded = build(None), build(mesh)
    check(sharded.mesh_sig == (2, 2, 1),
          f"sharded ANN view has mesh signature {sharded.mesh_sig}")
    devs = {s.device for s in sharded.bank_t.addressable_shards}
    check(len(devs) == 4, f"ANN bank shards on {len(devs)} device(s)")
    programs = TopKPrograms()
    s_one, i_one = programs.run(single, queries, k=k)
    s_sh, i_sh = programs.run(sharded, queries, k=k)
    check(np.array_equal(i_one, i_sh), "sharded ANN ids differ")
    worst = float(np.max(np.abs(s_sh - s_one)))
    print(f"ann sharded vs single: {rows} rows x {dim}, shards "
          f"{sorted({s.data.shape for s in sharded.bank_t.addressable_shards})}"
          f" on {len(devs)} devices, ids equal, max|dscore| {worst:.2e}; "
          f"MESH_EXEC_LOCK contended at entry: {contended}", flush=True)
    # a sharded matmul may tile its reduction differently: last-bit noise
    # on cosine scores in [-1, 1]
    check(worst <= 1e-5, f"sharded ANN scores differ by {worst}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the mesh paths and their "
                         "single-device comparisons")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()

    from semantic_router_tpu.runtime.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    import jax

    try:
        device = device_gate(jax.devices(), 4 if args.multichip else 1)
    except SmokeFailure as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 2
    entries0 = _cache_entries(cache_dir)
    print(f"device: {device}; compile cache {cache_dir} "
          f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'set in code'}"
          f"), {entries0} entries at start", flush=True)
    print(f"native lexical library: {_native_library()}", flush=True)
    try:
        if args.multichip:
            run_multichip(FULL_MULTICHIP, args.seed, WORK_DIR)
        else:
            run_single(FULL, args.seed, WORK_DIR)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak device memory: "
          f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
          f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB; compile cache "
          f"entries {entries0} → {_cache_entries(cache_dir)}; total "
          f"{time.perf_counter() - t0:.1f} s wall", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
