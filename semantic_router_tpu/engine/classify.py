"""The TPU inference engine: classifier registry + batched jit execution.

This collapses the reference's N1–N5/N7 native inference stack (Candle/ORT
classifier + embedding engines behind the CGo FFI, SURVEY.md §2.1) into one
JAX service:

- tasks register a Flax module + params + tokenizer + label set;
- requests flow through the DynamicBatcher, grouped by (task, seq bucket),
  padded to bucket edges, executed as one jit forward per batch;
- sequence tasks return softmax label results; token tasks decode entity
  spans host-side with exact char offsets (hard-part 5).

Shape discipline: seq lens come from ``engine.seq_len_buckets``, batch dims
pad to powers of two, so the jit cache holds ≤ |buckets|·log2(max_batch)
entries per task — this is what keeps p99 added latency in budget on TPU
(SURVEY.md hard-part 1/2).

Fused classifier bank (TrunkGroup): sequence tasks registered with the
SAME backbone weights + tokenizer collapse into one batch group — the
batcher keys on (trunk, bucket) instead of (task, bucket), one trunk
forward serves sequences from *different* tasks, and every member head
applies as one batched matmul (models.lora.apply_head_bank) whose logits
demux back to each item's own label set.  A request fanning K learned
signals over one shared trunk pays 1 tokenization and 1 trunk forward
instead of K, and the jit cache holds ≤ |buckets|·log2(max_batch) shapes
per TRUNK instead of per task (S-LoRA / Punica BGMV serving shape,
re-designed for XLA's closed shape sets).  ``engine.fuse_trunks``
(default on) controls it; ``register_task(..., fuse=False)`` opts a task
out; docs/FUSED_BANK.md is the operator story.
"""

from __future__ import annotations

import hashlib
import threading
import time
import weakref
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import InferenceEngineConfig
from ..utils.tokenization import Encoding, Tokenizer, decode_entity_spans
from .batcher import BatchItem, DynamicBatcher, pick_bucket, pow2_batch
from .kernels import normalize_kernels, normalize_quant, quant_selects
from .mesh import (
    build_serving_mesh,
    mesh_axes,
    mesh_signature,
    mesh_suffix,
    normalize_mesh,
)
from .packing import (
    RowPlan,
    PackingBatcher,
    ShapeAutoTuner,
    normalize_packing,
    pack_items,
)
from .step import EngineStep

# batch-group key prefix for fused trunk groups — the group id, not the
# task name, is the batching unit (see module docstring)
TRUNK_KEY = "__trunk__"
# batch-group key of generative tasks: (GEN_KEY, task, prompt bucket,
# max_new_tokens, adapter row, stop strings)
GEN_KEY = "__generate__"

# content digests of trunk parameter leaves, memoized by object id with
# a weakref guard (id() values recycle after GC; the guard makes a
# recycled id recompute instead of serving a stale digest).  Keyed by
# id so the common case — K tasks registered over the SAME arrays —
# hashes each leaf once, not K times.
_LEAF_DIGESTS: Dict[int, tuple] = {}
_LEAF_DIGESTS_LOCK = threading.Lock()


def _leaf_digest(leaf) -> str:
    """Content address of one parameter array: blake2b over dtype +
    shape + bytes.  Registration-time only (never on the hot path)."""
    key = id(leaf)
    with _LEAF_DIGESTS_LOCK:
        hit = _LEAF_DIGESTS.get(key)
    if hit is not None:
        ref, digest = hit
        if ref() is leaf:
            return digest
    x = np.ascontiguousarray(np.asarray(leaf))
    h = hashlib.blake2b(digest_size=16)
    h.update(str(x.dtype).encode())
    h.update(str(x.shape).encode())
    h.update(x.data)
    digest = h.hexdigest()
    try:
        with _LEAF_DIGESTS_LOCK:
            _LEAF_DIGESTS[key] = (weakref.ref(leaf), digest)
            if len(_LEAF_DIGESTS) > 4096:
                # sweep entries whose arrays died (config hot reloads
                # re-register tasks; without this the memo grows one
                # stale tuple per collected leaf, forever)
                for k in [k for k, (r, _d) in _LEAF_DIGESTS.items()
                          if r() is None]:
                    del _LEAF_DIGESTS[k]
    except TypeError:
        pass  # not weakref-able: recompute next time
    return digest


def _tokenizer_fingerprint(tok) -> Hashable:
    """Content identity for a tokenizer — two equivalent tokenizers
    must not split a trunk group just for being distinct objects.
    HashTokenizer is fully described by its vocab size; file-backed
    tokenizers key on their source path + vocab; anything else keeps
    object identity (correct, just never cross-instance)."""
    name = type(tok).__name__
    vocab = getattr(tok, "vocab_size", None)
    if name == "HashTokenizer":
        return (name, vocab)
    path = getattr(tok, "path", "")
    if path:
        return (name, path, vocab)
    return (name, id(tok))


@dataclass
class ClassResult:
    """Sequence-classification result (reference: the C structs marshalled
    back through unified_classifier_cgo_results.go:261)."""

    label: str
    index: int
    confidence: float
    probs: Dict[str, float] = field(default_factory=dict)
    latency_s: float = 0.0
    # the classifier never saw the input's tail (tokenizer clipped at the
    # task's max_seq_len) — surfaced, never silent (VERDICT r4 weak 7)
    truncated: bool = False


@dataclass
class EntitySpan:
    type: str
    start: int
    end: int
    text: str
    score: float


@dataclass
class TokenClassResult:
    entities: List[EntitySpan] = field(default_factory=list)
    latency_s: float = 0.0
    truncated: bool = False  # span scan did not cover the input's tail


@dataclass
class _Task:
    name: str
    kind: str  # "sequence" | "token" | "embedding" | "generative"
    labels: List[str]
    tokenizer: Tokenizer
    apply_fn: Callable  # jitted (params, ids, mask, ...) -> logits/embeddings
    params: Any
    max_seq_len: int
    pad_id: int = 0
    generator: Any = None  # generative kind: models.generate.GreedyGenerator
    adapter_index: Dict[str, int] = field(default_factory=dict)
    module: Any = None  # the Flax module (introspection: attention impl &c)


@dataclass
class _Payload:
    text: str
    encoding: Encoding
    threshold: float = 0.5
    exit_layer: Optional[int] = None  # embedding: Matryoshka layer exit
    output_dim: Optional[int] = None  # embedding: Matryoshka dim truncation
    # fused trunk-group items: which member tasks this sequence needs
    # logits for.  One task → the future resolves a ClassResult; several
    # (the classify_multi fan-out: one item, K tasks, trunk paid once) →
    # a {task: ClassResult} dict.
    tasks: tuple = ()
    submit_t: float = field(default_factory=time.perf_counter)
    # host tokenization cost attribution for batch tracing
    # (observability.batchtrace emits a batch.tokenize span per traced
    # request): seconds actually spent encoding, and whether the
    # request-level EncodingCache already held the encoding
    tok_s: float = 0.0
    tok_cached: bool = False


@dataclass
class _Layout:
    """A fused step's host batch as its composition stacked it."""

    ids: np.ndarray
    mask: np.ndarray
    # a packed batch's position and segment planes, [rows, bucket] ...
    row_planes: tuple = ()
    # ... and its per-segment pooling maps (seg_row, seg_start), [K]
    seg_maps: tuple = ()
    # per unique encoding: (device row, slice of its tokens in that row,
    # clipped at the bucket edge)
    spans: List[tuple] = field(default_factory=list)


@dataclass
class _Composition:
    """How a fused step lays its unique encodings out on the device —
    fixed rows or packed rows — as far as the one fused runner needs to
    know it: the measured variant, the program's census key (before the
    pair and mesh suffixes), the row facts, a traced step's extra span
    attributes, and ``stack()``, which makes the host batch."""

    packed: bool
    variant: str
    census: str
    rows: int
    padded_rows: int
    stack: Callable[[], _Layout]
    span_attrs: Dict[str, Any] = field(default_factory=dict)


def _cut_to_bucket(enc: Encoding, bucket: int, keep_tail: int) -> Encoding:
    """A prompt longer than the largest bucket, cut to it: its first tokens
    and its last ``keep_tail`` (a template's closing words, which tell a
    generative model what to write) stay, the end of what lies between
    goes.  Marked ``truncated`` with the whole count, as a tokenizer's own
    clipping is; the generative runner flags the result and counts it."""
    tail = max(0, min(keep_tail, bucket - 1))
    head = bucket - tail
    n = len(enc)

    def cut(seq):
        return list(seq[:head]) + list(seq[n - tail:])

    return Encoding(ids=cut(enc.ids), attention_mask=cut(enc.attention_mask),
                    offsets=cut(enc.offsets), truncated=True,
                    total_tokens=enc.n_total)


class _GenerationObserver:
    """What a generator's ``generate`` reports to, turn by turn of the host
    (models.generate.NullObserver has the protocol): each stretch of
    device work the host dispatches before it reads anything back is one
    EngineStep — one ``engine.step`` annotation with its stages (flavour
    ``gen.prefill`` | ``gen.denoise`` | ``gen.commit`` | ``gen.decode``;
    facts ``rows``, ``padded_rows``, ``tokens_real`` — a prefill's padded
    positions are ``padded_rows`` x ``bucket`` — ``block`` (the block run),
    ``masks_left``) and one ``record_step`` sample (a
    prefill's with its token fill) under group ``gen:<task>`` with the
    flavour as its variant, whose clock runs from ``forward`` to ``done``
    — and one ``record_generation`` count (the counters of /metrics).  A
    token-at-a-time generator's ``gen.decode`` step is a generation's LOOP
    of decode steps, one program (a decode step is two positions a row
    where the model drafts for itself: ``done(drafted=, accepted=)`` then
    says how many drafts the steps verified and how many were right, and
    ``committed_tokens`` is one or two a row and step).  A block
    generator's is a BLOCK: ``gen.denoise`` is block 0's loop of forwards,
    ``gen.commit`` a later block's (its first forward commits block ``b -
    1`` beside block ``b``, two blocks of tokens a row, which is its
    ``tokens_real``; the loop is queued behind it); ``done(forwards=n)``
    says how many forwards the device ran in it, known only from the
    readback, so the fact is the ``engine.gen.forward`` marker's and not
    the step's.  The prefill step carries the batch items, so a traced
    request's queue wait ends where its generation begins.  A generation
    has one step open at a time: the observer is its handle.

    Between the steps the observer keeps the generation's clock
    (``batchtrace.GenerationClock``): an ``engine.gen.turn`` annotation
    from each step's close to the next one's open — this class's own
    marker and counters and the generator's loop top run inside it — and
    the one after the last step until the runner calls ``end()``, which
    writes ``engine.gen.done`` and the generation's seconds by phase
    (``runtimestats.record_generation_done``)."""

    def __init__(self, engine, task: str, bucket: int, items, padded_rows: int
                 ) -> None:
        from ..observability import batchtrace

        self.engine, self.task, self.bucket = engine, task, bucket
        self.items, self.padded_rows = items, padded_rows
        self.step: Optional[EngineStep] = None
        self.clock = batchtrace.GenerationClock(
            f"gen:{task}", rows=len(items), padded_rows=int(padded_rows),
            bucket=int(bucket))
        self._block = -1

    def forward(self, flavour: str, tokens_real: int = 0,
                tokens_padded: int = 0, **facts):
        self.clock.step_opens()
        self._block = int(facts.get("block", -1))
        self.step = EngineStep(
            self.engine, self.items if flavour == "gen.prefill" else (),
            scope="gen", name=self.task, bucket=self.bucket,
            padded_rows=self.padded_rows, kind="generative",
            flavour=flavour, variant=flavour, rows=len(self.items),
            tokens_real=tokens_real, tokens_padded=tokens_padded, **facts)
        self.step.program()
        return self

    def stage(self, name: str):
        return self.step.stage(name)

    def done(self, load=None, forwards: int = 1, committed_blocks: int = 0,
             committed_tokens: int = 0, cache_bytes=None, keys=None,
             rows_per_group=None, drafted: Optional[int] = None,
             accepted: int = 0, attn_tiles=None) -> None:
        """``load [layers, 4]`` of an expert model
        (models.experts.routed_experts), of a step of several
        ``forwards`` theirs stacked (``[forwards x layers, 4]``); a dense
        generator gives none.  ``committed_blocks`` / ``committed_tokens``:
        what this step FINISHED (a block's step its block, whichever step
        writes its K and V later; a decode loop one token a live row and
        step).  ``cache_bytes``: a prefill's cache by kind of state
        (``{"kv", "conv"}``, a latent cache's ``{"latent", "index",
        "window"}``, or ``{"full", "window"}`` of whole K/V beside rings;
        the step's marker carries each beside the generation's bucket).
        ``keys [rows, 2]``: of a model with a learned
        selection, the keys its queries selected and those visible to
        them, summed on the device over the full layers.
        ``rows_per_group``: of a prefill whose rows are mapped inside the
        program, how many of them one grouped matmul served.  ``drafted``
        / ``accepted``: of the decode loop of a model that drafts for
        itself, the drafts it verified (one a live row and step) and those
        that were right; its ``committed_tokens`` is then the true count,
        one or two a row and step.  A loop's ``load`` and ``keys`` are its
        steps' stacked.  ``attn_tiles = (visited, grid)``: of a prefill
        whose flash calls are handed the rows' lengths, the tiles they
        folded and those the bucket's grid folds without
        (``ops.flash_attention.tiles_for``, over layers and heads)."""
        from ..observability import batchtrace

        step = self.step
        step.ran()
        self.close(forwards)
        self.clock.blocks += committed_blocks
        self.clock.tokens += committed_tokens
        if load is not None:
            batchtrace.gen_forward(
                step.group, step.variant, load, keys, rows_per_group,
                forwards, None if drafted is None else
                (drafted, accepted, committed_tokens), bucket=self.bucket,
                cache_bytes=cache_bytes, attn_tiles=attn_tiles)
        try:
            self.engine._runtime_stats.record_generation(
                self.task, step.variant, forwards=forwards,
                committed_blocks=committed_blocks,
                committed_tokens=committed_tokens, cache_bytes=cache_bytes,
                rows_per_group=rows_per_group, drafted=drafted or 0,
                accepted=accepted, attn_tiles=attn_tiles)
        except Exception:
            pass  # observability never fails a generation

    def close(self, forwards: int = 1) -> None:
        """End the open step, after ``done`` or when its program raised
        before it (it counts as one forward then); the turn that follows it
        begins."""
        if self.step is not None:
            self.step.finish()
            self.clock.step_closed(self.step.variant, self._block, forwards)
            self.step = None

    def end(self, done: bool) -> None:
        """The runner has its results (``done``) or gave up: the open
        forward, if one raised, and the last turn end; a generation that
        came through says what it was (``engine.gen.done``) and where its
        seconds went."""
        self.close()
        seconds = self.clock.end(done)
        if seconds is None:
            return
        try:
            self.engine._runtime_stats.record_generation_done(
                self.task, seconds)
        except Exception:
            pass  # observability never fails a generation


@dataclass
class TrunkGroup:
    """Tasks sharing one backbone: the fused classifier-bank unit.

    Grouping key (engine._trunk_fingerprint): identity of the trunk
    parameter arrays + tokenizer identity + (max_seq_len, pad_id, config
    sans label count).  Tasks that land in one group batch together under
    (TRUNK_KEY, gid, bucket); their stacked heads live in ``bank``
    (models.lora.stack_head_bank) and apply in one batched matmul."""

    gid: str
    config: Any                # ModernBertConfig shared by every member
    trunk_module: Any          # bare ModernBertModel over the shared weights
    trunk_params: Any          # the shared (possibly mesh-sharded) subtree
    tokenizer: Tokenizer
    max_seq_len: int
    pad_id: int
    members: List[str] = field(default_factory=list)
    entries: List[dict] = field(default_factory=list)
    # sequence-head view (bank rows over SEQUENCE members only — token
    # members live in the parallel tok_* fields, stacked separately
    # because their heads apply per TOKEN, not per pooled row)
    widths: List[int] = field(default_factory=list)  # true label widths
    row_of: Dict[str, int] = field(default_factory=dict)
    bank: Any = None
    tok_bank: Any = None
    tok_widths: List[int] = field(default_factory=list)
    tok_row_of: Dict[str, int] = field(default_factory=dict)
    # the fused jit program set keyed by flavor: seq / tok / both plus
    # their packed_* siblings (engine.packing) — all share the ONE trunk
    # forward; the runner picks by batch contents, so a batch with no
    # token items never pays the per-token head matmul.  The dict ALSO
    # carries "trunk_params" (the SERVING trunk tree — the quantized
    # variant when engine.quant selects this group) and "meta" (the
    # kernel-knob snapshot these programs were built under), so one
    # atomic read pairs programs with the params they trace against —
    # a hot knob flip swaps the whole dict (docs/KERNELS.md)
    fns: Any = None
    # packed-shape census rows carried across a kernel-flip rebuild so
    # warmup_packed_hot can recompile the previously hot shapes against
    # the NEW program set (the rebuild purged their compile records)
    warm_hints: Any = None
    # atomic demux snapshot (banks + row maps + widths): the runner
    # reads ONE consistent view, so a concurrent re-registration can
    # never pair new row indices with old logits ordering
    demux: Any = None
    # the HOST trunk leaves whose id()s form this group's fingerprint:
    # retained so those ids can never be freed and recycled by a later
    # checkpoint load (a stale id-match would silently serve the wrong
    # trunk).  No-mesh serving aliases the live params (zero cost); mesh
    # serving keeps one host copy per group alive by design.
    host_refs: Any = None


class WarmupError(RuntimeError):
    """A warmup program failed to compile or run; the message names each
    (target, bucket) and the compiler's error."""


class InferenceEngine:
    """Owner of all TPU-served classifier tasks + the batching shim."""

    def __init__(self, cfg: Optional[InferenceEngineConfig] = None,
                 metrics=None, events=None, runtime_stats=None,
                 program_stats=None) -> None:
        self.cfg = cfg or InferenceEngineConfig()
        self._tasks: Dict[str, _Task] = {}
        self._lock = threading.Lock()
        # instance-routable observability (pkg/routerruntime decoupling):
        # None = the process defaults (single-engine posture)
        self._metrics = metrics
        self._events = events
        # always-on device-step accounting (observability.runtimestats):
        # the batch runners emit one sample per step — a bounded deque
        # append, nothing more — and the sampler aggregates off-path
        if runtime_stats is None:
            from ..observability.runtimestats import default_runtime_stats

            runtime_stats = default_runtime_stats
        self._runtime_stats = runtime_stats
        # XLA program-cost catalog (observability.programstats): fresh
        # compile sites register a deferred lower-thunk keyed like the
        # census; the AOT cost capture runs at catalog-read time, so
        # the hot path only ever pays an abstract-shape dict insert
        if program_stats is None:
            from ..observability.programstats import default_program_stats

            program_stats = default_program_stats
        self._program_stats = program_stats

        # serving-side sharded classifier bank (SURVEY §2.4 north-star
        # layout: pjit-sharded bank over a slice): engine.mesh_shape
        # builds a (dp, tp, sp) Mesh; task params shard per the Megatron
        # rules and batches land dp-sharded — XLA inserts the collectives
        self.mesh = None
        if self.cfg.mesh_shape:
            from ..parallel import create_mesh

            self.mesh = create_mesh(dict(self.cfg.mesh_shape))
            if self.mesh.shape.get("sp", 1) > 1:
                # an sp axis is only useful when attention actually
                # shards the sequence: ring-attention tasks serve with
                # inputs sharded (dp, sp); any non-ring task registered
                # on this mesh would silently replicate its sequence
                # work across sp — register_task refuses that instead
                sp = self.mesh.shape["sp"]
                bad = [b for b in self.cfg.seq_len_buckets if b % sp]
                if bad:
                    raise ValueError(
                        f"seq_len_buckets {bad} not divisible by sp={sp}"
                        f" (ring attention shards S over sp)")
        # sequence-packed continuous batching (engine.packing,
        # docs/PACKING.md): the batch composer is ALWAYS the packing
        # scheduler — with packing disabled every hook delegates to the
        # DynamicBatcher base class (byte-identical batching), so the
        # enabled knob hot-flips without swapping a live batcher
        self._packing = normalize_packing(
            getattr(self.cfg, "packing", None))
        self.batcher = PackingBatcher(
            self._run_batch,
            bucket_of=self._packing_bucket_of,
            segment_cap_of=self._packing_segment_cap_of,
            max_batch_size=self.cfg.max_batch_size,
            max_wait_ms=self.cfg.max_wait_ms,
            name="tpu-engine-batcher",
            dispatch_workers=self.cfg.dispatch_workers,
            metrics=metrics,
            enabled=self._packing["enabled"],
            max_segments_per_row=self._packing["max_segments_per_row"],
            max_items_per_step=self._packing["max_items_per_step"],
            max_inflight_steps=self._packing["max_inflight_steps"],
            starvation_steps=self._packing["starvation_steps"],
            patient=lambda key: isinstance(key, tuple) and key[0] == GEN_KEY,
        )
        # a generative group's key: (GEN_KEY, task, prompt bucket, ...)
        self.batcher.wait_facts = lambda key: {"bucket": int(key[2])} \
            if isinstance(key, tuple) and key[0] == GEN_KEY else {}
        # the online shape auto-tuner exists per engine (cheap state);
        # its POLLING THREAD is bootstrap's to start (apply_packing_knobs
        # honors engine.packing.autotune) — bare test engines stay
        # thread-free and drive step() directly
        at = self._packing["autotune"]
        self._autotuner = ShapeAutoTuner(
            self._runtime_stats, self.batcher,
            target_fill=at["target_fill"],
            min_samples=at["min_samples"],
            segments_floor=self._packing["max_segments_per_row"],
            max_segments_cap=at["max_segments_cap"],
            interval_s=at["interval_s"])
        # queue-depth / pool-saturation gauges ride the runtime-stats
        # sampler; keyed by batcher name, so a rebuilt engine replaces
        # the provider and shutdown() unregisters it.  The host instance
        # and callable are pinned so shutdown removes exactly what THIS
        # engine registered (never a sibling's live provider, and never
        # from a later-rebound stats instance).
        self._rs_provider_host = self._runtime_stats
        self._rs_provider_fn = self.batcher.queue_depths
        try:
            self._rs_provider_host.register_provider(
                self.batcher.name, self._rs_provider_fn)
        except Exception:
            pass
        # raw-engine-speed knob blocks (docs/KERNELS.md): quantized
        # trunk serving mode + tuned-kernel toggles, normalized through
        # the ONE interpretation point (engine.kernels) — defaults all
        # OFF, so an unconfigured engine serves byte-identically
        self._quant = normalize_quant(getattr(self.cfg, "quant", None))
        self._kernels = normalize_kernels(getattr(self.cfg, "kernels",
                                                  None))
        self._kernel_rebuilds = 0
        # serving mesh (engine.mesh, docs/PARALLEL.md): dp×tp placement
        # of the trunk-group serving containers — OFF by default
        # (byte-identical single-device serving).  Distinct from the
        # legacy registration-time engine.mesh_shape path above: when
        # THAT is active it owns placement and this block is inert.
        self._mesh_knobs = normalize_mesh(getattr(self.cfg, "mesh",
                                                  None))
        self._serving_mesh = None
        self._mesh_rebuilds = 0
        if self.mesh is None and self._mesh_knobs["enabled"]:
            try:
                self._serving_mesh = build_serving_mesh(
                    self._mesh_knobs)
                self.batcher.dp_degree = int(
                    self._serving_mesh.shape.get("dp", 1))
            except Exception as exc:
                # fail-open like the knob-apply paths: a malformed
                # mesh block (tp beyond the visible devices, a bad
                # axis product) must never stop the server at boot
                # any more than at hot reload — single-device posture,
                # loudly logged
                self._serving_mesh = None
                from ..observability.logging import component_event

                component_event(
                    "engine", "mesh_config_invalid", level="warning",
                    error=f"{type(exc).__name__}: {exc}"[:200])
        # fused classifier bank: trunk fingerprint → TrunkGroup, plus the
        # task→group and gid→group views the hot path reads
        self._trunk_groups: Dict[tuple, TrunkGroup] = {}
        self._task_group: Dict[str, TrunkGroup] = {}
        self._groups_by_gid: Dict[str, TrunkGroup] = {}
        self._next_gid = 0  # monotonic: eviction must never recycle a gid
        # distinct device batch shapes executed per batch group — the
        # jit-cache-budget regression surface (shape_census())
        self._shapes: Dict[str, set] = {}
        # (group, variant, shape) triples already executed — the step
        # sampler's per-PROGRAM compile detection (_step_fresh)
        self._compiled_steps: set = set()
        # per-(target, bucket) warmup outcomes (warmup_report())
        self._warmup_report: List[Dict[str, Any]] = []

    # -- registration ------------------------------------------------------

    def register_task(self, name: str, kind: str, module, params,
                      tokenizer: Tokenizer, labels: List[str],
                      max_seq_len: int = 0, pad_id: int = 0,
                      fuse: Optional[bool] = None) -> None:
        """``fuse``: join the fused classifier bank when this task's trunk
        weights + tokenizer match another registered task's (None → the
        engine.fuse_trunks config default).  Opt out (fuse=False) for
        tasks whose latency/batching must stay isolated from their trunk
        siblings."""
        if kind not in ("sequence", "token", "embedding"):
            raise ValueError(f"unknown task kind {kind!r}")
        if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1 \
                and getattr(getattr(module, "config", None),
                            "attention_impl", "") != "ring":
            # a non-ring model under an sp mesh would replicate its
            # whole sequence computation across the sp devices — half
            # the slice doing duplicate work looks healthy and is pure
            # waste; fail loudly at registration instead
            raise ValueError(
                f"task {name!r}: serving mesh has sp>1 but the model's "
                f"attention_impl is not 'ring' — sequence-parallel "
                f"serving needs ring attention (or fold sp into dp)")
        if kind == "embedding":
            # exit_layer/output_dim are static Matryoshka knobs: each
            # configured (exit, dim) pair is its own compiled program
            apply_fn = jax.jit(module.apply,
                               static_argnames=("exit_layer", "output_dim"))
        else:
            apply_fn = jax.jit(module.apply)
        max_len = max_seq_len or self.cfg.seq_len_buckets[-1]
        # bank-fusability check runs BEFORE sharding: the fingerprint is
        # the identity of the caller's host arrays (two tasks share a
        # trunk iff they registered the same trunk arrays), and the head
        # entry must stack from host copies
        entry = tkey = host_trunk = None
        want_fuse = self.cfg.fuse_trunks if fuse is None else bool(fuse)
        if want_fuse and kind in ("sequence", "token"):
            # token-classification heads (PII / hallucination spans)
            # fuse too: same trunk forward, their heads apply per token
            # and stack into the group's tok_bank (docs/FUSED_BANK.md)
            from ..models.lora import head_bank_entry

            entry = head_bank_entry(module, params)
            if entry is not None:
                tkey = self._trunk_fingerprint(module, params, tokenizer,
                                               max_len, pad_id)
                if tkey is not None:
                    p = params.get("params", params)
                    host_trunk = p.get("model")
        if self.mesh is not None:
            from ..parallel import shard_params

            params = shard_params(params, self.mesh)
        else:
            # weights live on the device.  A checkpoint load hands over
            # host (numpy) arrays; left as they are, the whole tree —
            # 0.57 GB for an mmBERT trunk — would cross the jit boundary,
            # and PCIe, on EVERY step.  A member of an existing trunk
            # group takes the group's trunk instead of uploading its own.
            g = self._trunk_groups.get(tkey) if tkey is not None else None
            if g is not None:
                p = dict(params.get("params", params))
                p["model"] = g.trunk_params
                params = {**dict(params), "params": p} \
                    if "params" in params else p
            params = jax.tree_util.tree_map(jnp.asarray, params)
        with self._lock:
            self._tasks[name] = _Task(name, kind, list(labels), tokenizer,
                                      apply_fn, params, max_len, pad_id,
                                      module=module)
        if entry is not None and tkey is not None:
            self._join_trunk_group(tkey, name, module, tokenizer, entry,
                                   host_trunk)
        else:
            # re-registration as non-fusable (fuse=False, new kind, or a
            # foreign architecture) must not leave a stale fused member
            with self._lock:
                self._evict_locked(name)
        self._emit_registered(name, kind)

    # -- fused trunk groups ------------------------------------------------

    @staticmethod
    def _trunk_fingerprint(module, params, tokenizer: Tokenizer,
                           max_seq_len: int, pad_id: int
                           ) -> Optional[tuple]:
        """Grouping key: tasks whose trunk parameter arrays hold the
        SAME CONTENT (blake2b digests, memoized by object id so the
        common same-arrays case hashes once), a content-equivalent
        tokenizer, and compatible shape discipline share one fused
        group.  Content addressing — not object identity — so two
        checkpoint files with identical frozen trunks fuse too; the
        digest memo's weakref guard keeps recycled ids from ever
        producing a false positive."""
        cfg = getattr(module, "config", None)
        if cfg is None:
            return None
        p = params.get("params", params)
        trunk = p.get("model") if hasattr(p, "get") else None
        if trunk is None:
            return None
        try:
            leaf_key = tuple(
                _leaf_digest(x)
                for x in jax.tree_util.tree_leaves(trunk))
        except Exception:
            # un-hashable leaves (exotic array types): fall back to the
            # identity fingerprint — correct, just never cross-file
            leaf_key = tuple(
                id(x) for x in jax.tree_util.tree_leaves(trunk))
        try:
            # label width is per-head, never part of the trunk identity
            cfg_key = repr(replace(cfg, num_labels=0))
        except TypeError:
            cfg_key = repr(cfg)
        return (leaf_key, _tokenizer_fingerprint(tokenizer),
                int(max_seq_len), int(pad_id), cfg_key)

    def _evict_locked(self, name: str) -> None:
        """Remove a task from its trunk group (caller holds self._lock):
        re-registration must REPLACE the member, not append a stale
        duplicate row to the bank.  Registration-time only — like
        registration itself, not safe concurrent with in-flight fused
        batches of the same group."""
        g = self._task_group.pop(name, None)
        if g is None:
            return
        try:
            idx = g.members.index(name)
        except ValueError:
            return
        g.members.pop(idx)
        g.entries.pop(idx)
        if g.members:
            self._rebuild_bank(g)  # re-derives row maps + widths
        else:
            self._groups_by_gid.pop(g.gid, None)
            for k, v in list(self._trunk_groups.items()):
                if v is g:
                    del self._trunk_groups[k]

    def _join_trunk_group(self, tkey: tuple, name: str, module,
                          tokenizer: Tokenizer, entry: dict,
                          host_trunk=None) -> None:
        from ..models.modernbert import ModernBertModel

        with self._lock:
            self._evict_locked(name)
            g = self._trunk_groups.get(tkey)
            if g is None:
                t = self._tasks[name]
                tp = t.params.get("params", t.params)
                g = TrunkGroup(
                    gid=f"trunk{self._next_gid}",
                    config=module.config,
                    trunk_module=ModernBertModel(module.config),
                    # first member's (possibly sharded) trunk subtree IS
                    # the group's — every member registered these same
                    # arrays, so no second copy lands on device
                    trunk_params=tp["model"],
                    tokenizer=tokenizer,
                    max_seq_len=t.max_seq_len,
                    pad_id=t.pad_id,
                    host_refs=host_trunk)
                self._trunk_groups[tkey] = g
                self._groups_by_gid[g.gid] = g
                self._next_gid += 1
            t = self._tasks[name]
            p = t.params.get("params", t.params)
            if hasattr(p, "get") and p.get("model") is not g.trunk_params:
                # alias the group's (possibly mesh-sharded) trunk into
                # this member's stored tree: without this, member N's
                # shard_params copy would keep a duplicate trunk in HBM
                # that only the rare classify_windowed fallback reads
                new_p = dict(p)
                new_p["model"] = g.trunk_params
                t.params = ({**dict(t.params), "params": new_p}
                            if "params" in t.params else new_p)
            g.members.append(name)
            g.entries.append(entry)
            self._rebuild_bank(g)  # derives row maps + widths per kind
            self._task_group[name] = g

    def _rebuild_bank(self, g: TrunkGroup) -> None:
        """Re-stack the head/adapter banks after membership changes —
        SEQUENCE heads and TOKEN heads stack separately (pooled-row vs
        per-token application).  The fused fns take the banks as
        arguments, so a new member costs one recompile (the task axis
        grew) — registration-time, never serving-time."""
        from ..models.lora import stack_head_bank

        def _stack(idxs: List[int]):
            if not idxs:
                return None
            bank = stack_head_bank([g.entries[i] for i in idxs])
            # either mesh path places the bank with head_bank_specs:
            # the TASK axis lays out over tp when it divides evenly
            mesh = self.mesh if self.mesh is not None \
                else self._serving_mesh
            if mesh is not None:
                from ..parallel import shard_head_bank

                return shard_head_bank(bank, mesh)
            # commit to device ONCE: a host-numpy bank would re-upload
            # tens of MB per batch through the jit boundary
            return {k: jnp.asarray(v) for k, v in bank.items()}

        seq_idx = [i for i, e in enumerate(g.entries)
                   if e.get("kind", "sequence") == "sequence"]
        tok_idx = [i for i, e in enumerate(g.entries)
                   if e.get("kind") == "token"]
        g.bank = _stack(seq_idx)
        g.tok_bank = _stack(tok_idx)
        g.row_of = {g.members[i]: r for r, i in enumerate(seq_idx)}
        g.widths = [int(np.shape(g.entries[i]["cls_kernel"])[1])
                    for i in seq_idx]
        g.tok_row_of = {g.members[i]: r for r, i in enumerate(tok_idx)}
        g.tok_widths = [int(np.shape(g.entries[i]["cls_kernel"])[1])
                        for i in tok_idx]
        # one atomic assignment: the runner's demux view stays consistent
        g.demux = {
            "bank": g.bank, "tok_bank": g.tok_bank,
            "row_of": dict(g.row_of), "widths": list(g.widths),
            "tok_row_of": dict(g.tok_row_of),
            "tok_widths": list(g.tok_widths),
        }
        self._refresh_serving(g, locked=True)

    # -- kernel/quant serving programs (docs/KERNELS.md) -------------------

    def _serving_meta(self, g: TrunkGroup) -> dict:
        """The kernel-knob snapshot one group's programs build under:
        quant mode (per-group selector), epilogue fusion, whether the
        BGMV gather engages (bank at least min_tasks heads wide), and
        the serving-mesh signature (a mesh flip is a program-set
        rebuild exactly like a quant flip — compile variants key on
        the mesh shape)."""
        kk = self._kernels
        return {
            "quant": quant_selects(self._quant, g.gid, g.members),
            "epilogue": bool(kk["epilogue"]["enabled"]),
            "bgmv": bool(kk["bgmv"]["enabled"]
                         and len(g.widths) >= kk["bgmv"]["min_tasks"]),
            "mesh": mesh_signature(self._serving_mesh),
        }

    def _refresh_serving(self, g: TrunkGroup,
                         locked: bool = False) -> None:
        """(Re)build the group's fused program set when the kernel-knob
        snapshot changed (or none exists yet).  The swap is ONE dict
        assignment — in-flight batches finish on the programs they
        already read; the next step serves the new set (the hot-flip
        contract, tests/test_kernels.py).  A real rebuild purges the
        group's compile records (the new programs' jit caches are cold)
        but keeps the packed-shape census as warm_hints so
        warmup_packed_hot can recompile the hot shapes off-path.

        ``locked``: the caller already holds self._lock (the
        registration path — _rebuild_bank runs under it); the purge
        must not re-acquire the non-reentrant lock."""
        meta = self._serving_meta(g)
        old = g.fns
        if old is not None and old.get("meta") == meta:
            if old.get("demux") is not g.demux:
                # membership changed but the programs are reusable
                # (banks are ARGUMENTS): refresh only the demux view,
                # still as ONE atomic dict swap — the runner reads the
                # (programs, params, mesh, demux) quad from a single
                # g.fns read, so it can never pair banks placed on one
                # mesh with programs built for another.  The swap is a
                # LOCKED compare-and-swap: an unlocked read-modify-
                # write here could clobber a concurrent full rebuild
                # (registration/mesh flip under self._lock) and revert
                # g.fns to old programs paired with the new demux —
                # exactly the torn pairing this snapshot exists to
                # prevent.
                def refresh():
                    cur = g.fns
                    if cur is not None and cur.get("meta") == meta \
                            and cur.get("demux") is not g.demux:
                        g.fns = {**cur, "demux": g.demux}

                if locked:
                    refresh()
                else:
                    with self._lock:
                        refresh()
            return
        # heavy build (quantization, device placement) OUTSIDE the
        # lock; the swap itself is a locked CAS like the demux refresh
        # above — an unlocked `g.fns = fns` could clobber a concurrent
        # locked rebuild (registration / mesh flip) and serve its
        # pre-swap demux forever
        fns = self._make_fused_fn(g, meta)

        def swap() -> bool:
            if self._serving_meta(g) != meta:
                # knobs/membership changed while we built: the
                # concurrent rebuild owns the newer truth — discard
                return False
            fns["demux"] = g.demux   # capture under the lock: pairs
            g.fns = fns              # with the LIVE banks
            return True

        if locked:
            swapped = swap()
        else:
            with self._lock:
                swapped = swap()
        if not swapped:
            return
        if old is not None:
            self._series().kernel_rebuilds.inc(group=g.gid)
            group = f"trunk:{g.gid}"

            def purge():
                # runs under self._lock on both paths below, so the
                # rebuild counter and the registry purge are one
                # atomic step (two concurrent reloads must not lose
                # an increment or interleave the purge)
                self._kernel_rebuilds += 1
                keys = [k for k in self._compiled_steps
                        if k[0] == group]
                self._compiled_steps = {
                    k for k in self._compiled_steps if k[0] != group}
                return keys

            if locked:
                keys = purge()
            else:
                with self._lock:
                    keys = purge()
            # MERGE with hints a prior rebuild already saved: a dual
            # flip (quant AND kernels in one reload) rebuilds twice,
            # and the second purge sees an empty registry — overwriting
            # would drop the first rebuild's census
            g.warm_hints = sorted(
                set(self._parse_census_keys(keys))
                | {tuple(r) for r in (g.warm_hints or ())})
            # the census purge's telemetry twin: the old programs no
            # longer exist, so their runtimestats EWMAs and cost-catalog
            # rows must go too — without this, repeated hot flips grow
            # (group, bucket, variant) cardinality without bound and
            # /debug/runtime keeps reporting dead programs
            self._retire_programs(group=group)

    def _retire_programs(self, group: Optional[str] = None,
                         variant_prefix: Optional[str] = None) -> None:
        """Retire measured + cost rows for rebuilt programs; fail-open
        (telemetry retirement must never break a hot flip)."""
        for store in (self._runtime_stats, self._program_stats):
            try:
                store.retire(group=group, variant_prefix=variant_prefix)
            except Exception:
                pass

    def configure_quant(self, knobs: Optional[Dict[str, Any]]) -> None:
        """Apply the engine.quant block (boot + config hot reload):
        normalize through the ONE interpretation point, then rebuild
        each affected trunk group's serving programs — quantization of
        the weights happens HERE (once), never on the forward path."""
        self._quant = normalize_quant(knobs)
        for g in list(self._groups_by_gid.values()):
            self._refresh_serving(g)

    def configure_kernels(self, knobs: Optional[Dict[str, Any]]) -> None:
        """Apply the engine.kernels block (boot + config hot reload):
        epilogue fusion + BGMV gather toggles; same rebuild contract as
        configure_quant."""
        self._kernels = normalize_kernels(knobs)
        for g in list(self._groups_by_gid.values()):
            self._refresh_serving(g)

    def configure_mesh(self, knobs: Optional[Dict[str, Any]]) -> None:
        """Apply the engine.mesh block (boot + config hot reload):
        build or tear down the serving mesh, re-stack each trunk
        group's banks onto the new placement, and atomically swap each
        group's program set — in-flight batches finish on the (mesh,
        programs, banks) snapshot they already read, exactly the
        configure_quant/configure_kernels hot-flip contract.  A no-op
        re-apply (same axis sizes) rebuilds nothing.  With the legacy
        registration-time engine.mesh_shape active this block is inert:
        that path owns placement."""
        mk = normalize_mesh(knobs)
        if self.mesh is not None:
            self._mesh_knobs = mk   # inert block: report only
            return
        # build BEFORE publishing the knobs: a rejected shape (loud
        # resolve_axes failure) must leave /debug/runtime reporting
        # the config that is actually serving, not the rejected one
        new_mesh = build_serving_mesh(mk)   # None when disabled
        self._mesh_knobs = mk
        with self._lock:
            if mesh_signature(new_mesh) != \
                    mesh_signature(self._serving_mesh):
                self._serving_mesh = new_mesh
                self._mesh_rebuilds += 1
                for g in list(self._groups_by_gid.values()):
                    if g.members:
                        # re-derives banks on the new placement, then
                        # _refresh_serving sees the meta mesh changed
                        # and swaps the program set whole
                        self._rebuild_bank(g)
            dp = 1
            if self._serving_mesh is not None:
                dp = int(self._serving_mesh.shape.get("dp", 1))
            # scheduler step-size / row-trim scaling rides the dp
            # degree (single atomic int publish — the picker thread
            # reads it concurrently)
            if isinstance(self.batcher, PackingBatcher):
                self.batcher.dp_degree = dp
        axes = mesh_axes(self._serving_mesh)
        m = self._series()
        for ax in ("dp", "tp"):
            m.mesh_devices.set(
                float(axes.get(ax, 1)) if self._serving_mesh is not None
                else 0.0, axis=ax)

    def mesh_report(self) -> Dict[str, Any]:
        """Operator snapshot (GET /debug/runtime rides this): the live
        normalized knob block, the active mesh (axes, per-axis device
        counts, which path owns placement), per-group sharding state,
        and how many mesh flips rebuilt program sets this process."""
        active = self.mesh if self.mesh is not None \
            else self._serving_mesh
        out: Dict[str, Any] = {
            "knobs": dict(self._mesh_knobs),
            "enabled": active is not None,
            "source": ("mesh_shape" if self.mesh is not None else
                       "engine.mesh" if self._serving_mesh is not None
                       else None),
            "visible_devices": jax.device_count(),
            "mesh_devices": int(active.devices.size)
            if active is not None else 0,
            "axes": {ax: int(active.shape.get(ax, 1))
                     for ax in ("dp", "tp", "sp")}
            if active is not None else {},
            "rebuilds": self._mesh_rebuilds,
        }
        groups = {}
        for gid, g in list(self._groups_by_gid.items()):
            fns = g.fns
            if fns is not None:
                sig = fns["meta"].get("mesh")
                groups[gid] = {"sharded": sig is not None,
                               "mesh": list(sig) if sig else None}
        out["groups"] = groups
        return out

    def kernels_report(self) -> Dict[str, Any]:
        """Operator snapshot (GET /debug/runtime rides this): the live
        normalized knob blocks, per-group serving meta, and how many
        hot flips rebuilt jit program sets this process."""
        out: Dict[str, Any] = {
            "quant": {k: (dict(v) if isinstance(v, dict) else
                          list(v) if isinstance(v, list) else v)
                      for k, v in self._quant.items()},
            "kernels": {k: dict(v) for k, v in self._kernels.items()},
            "rebuilds": self._kernel_rebuilds,
        }
        groups = {}
        for gid, g in list(self._groups_by_gid.items()):
            fns = g.fns
            if fns is not None:
                groups[gid] = dict(fns["meta"])
        out["groups"] = groups
        return out

    def _make_fused_fn(self, g: TrunkGroup, meta: Optional[dict] = None):
        """Build the group's fused jit program set.  Every flavor shares
        the SAME trunk forward; only the head application differs:

        - seq:  pooled rows → apply_head_bank → [B, T, L]
        - tok:  every token → apply_head_bank on [B·S, D] → [B, S, T, L]
        - both: one trunk forward feeding both head banks
        - packed_*: the sequence-packing siblings (engine.packing) —
          block-diagonal attention + per-segment positions in the trunk,
          per-SEGMENT pooling for sequence heads (docs/PACKING.md).

        jit() is free until called: flavors a deployment never uses are
        never compiled.

        ``meta`` (engine.kernels / engine.quant snapshot,
        _serving_meta) shapes the programs: quant swaps the trunk for
        its bf16/int8 serving variant (models.quant.build_quant_trunk —
        weights transform HERE, once, never per step); epilogue routes
        the head banks through the fused Pallas epilogue; bgmv swaps
        the all-heads sequence matmul for the per-pair gather, which
        adds (pair_rows, pair_tasks) operands to the seq-carrying
        flavors.  The returned dict carries the SERVING trunk params +
        the meta so the runner reads one consistent snapshot."""
        from ..models.lora import apply_head_bank, apply_head_bank_bgmv
        from ..models.modernbert import activation
        from ..ops.attention import (
            cls_pool,
            mean_pool,
            packed_cls_pool,
            packed_mean_pool,
        )

        cfg = g.config
        meta = dict(meta or {"quant": "off", "epilogue": False,
                             "bgmv": False, "mesh": None})
        meta.setdefault("mesh", None)
        act = activation(cfg.classifier_activation)
        use_mean = cfg.classifier_pooling == "mean"
        srv_mesh = self._serving_mesh if meta["mesh"] is not None \
            else None
        trunk_cfg = cfg
        if srv_mesh is not None and cfg.attention_impl == "flash":
            # GSPMD cannot partition the Pallas kernel: the trunk must
            # know its mesh so attention shard_maps the kernel over it
            trunk_cfg = replace(cfg, mesh=srv_mesh)
        if meta["quant"] == "off":
            trunk = g.trunk_module if trunk_cfg is cfg \
                else type(g.trunk_module)(trunk_cfg)
            serving_params = g.trunk_params
        else:
            from ..models.quant import build_quant_trunk

            trunk, serving_params = build_quant_trunk(
                trunk_cfg, g.trunk_params, meta["quant"])
        # serving-mesh placement (docs/PARALLEL.md): the SERVING copy of
        # the trunk tree lands on the mesh per the Megatron rules (tp=1
        # degenerates to replication); g.trunk_params keeps the
        # unplaced original, so a mesh teardown restores byte-identical
        # single-device serving from the same source arrays
        if srv_mesh is not None:
            from ..parallel import shard_params

            serving_params = shard_params(serving_params, srv_mesh)
        elif serving_params is not g.trunk_params:
            # int8: commit the quantized leaves to device ONCE — a
            # host-numpy tree would re-upload per batch through the
            # jit boundary
            serving_params = jax.tree_util.tree_map(
                jnp.asarray, serving_params)
        epilogue = meta["epilogue"]
        bgmv = meta["bgmv"]

        def hidden_fn(trunk_params, ids, mask, pos=None, seg=None):
            return trunk.apply({"params": trunk_params}, ids, mask,
                               position_ids=pos, segment_ids=seg)

        # pool / heads / token_heads: named scopes beside the trunk's
        # own (models/modernbert.py), so a device profile tells the head
        # banks' ops from the trunk's — metadata only, same arithmetic
        def pool(hidden, mask):
            with jax.named_scope("pool"):
                return mean_pool(hidden, mask) if use_mean \
                    else cls_pool(hidden)

        def ppool(hidden, seg, seg_row, seg_start):
            with jax.named_scope("pool"):
                return packed_mean_pool(hidden, seg, seg_row.shape[0]) \
                    if use_mean else packed_cls_pool(hidden, seg_row,
                                                     seg_start)

        def seq_heads(bank, pooled, pair_rows=None, pair_tasks=None):
            with jax.named_scope("heads"):
                if bgmv:
                    return apply_head_bank_bgmv(bank, pooled, pair_rows,
                                                pair_tasks, act,
                                                cfg.norm_eps)
                return apply_head_bank(bank, pooled, act, cfg.norm_eps,
                                       epilogue=epilogue)

        def tok_heads(tok_bank, hidden):
            with jax.named_scope("token_heads"):
                B, S, H = hidden.shape
                flat = apply_head_bank(tok_bank,
                                       hidden.reshape(B * S, H), act,
                                       cfg.norm_eps, epilogue=epilogue)
                return flat.reshape(B, S, flat.shape[-2], flat.shape[-1])

        if bgmv:
            def seq_fn(trunk_params, bank, ids, mask, pr, pt):
                h = hidden_fn(trunk_params, ids, mask)
                return seq_heads(bank, pool(h, mask), pr, pt)

            def both_fn(trunk_params, bank, tok_bank, ids, mask, pr,
                        pt):
                h = hidden_fn(trunk_params, ids, mask)
                return (seq_heads(bank, pool(h, mask), pr, pt),
                        tok_heads(tok_bank, h))

            def packed_seq_fn(trunk_params, bank, ids, mask, pos, seg,
                              seg_row, seg_start, pr, pt):
                h = hidden_fn(trunk_params, ids, mask, pos, seg)
                return seq_heads(bank, ppool(h, seg, seg_row,
                                             seg_start), pr, pt)

            def packed_both_fn(trunk_params, bank, tok_bank, ids, mask,
                               pos, seg, seg_row, seg_start, pr, pt):
                h = hidden_fn(trunk_params, ids, mask, pos, seg)
                return (seq_heads(bank, ppool(h, seg, seg_row,
                                              seg_start), pr, pt),
                        tok_heads(tok_bank, h))
        else:
            def seq_fn(trunk_params, bank, ids, mask):
                h = hidden_fn(trunk_params, ids, mask)
                return seq_heads(bank, pool(h, mask))

            def both_fn(trunk_params, bank, tok_bank, ids, mask):
                h = hidden_fn(trunk_params, ids, mask)
                return (seq_heads(bank, pool(h, mask)),
                        tok_heads(tok_bank, h))

            def packed_seq_fn(trunk_params, bank, ids, mask, pos, seg,
                              seg_row, seg_start):
                h = hidden_fn(trunk_params, ids, mask, pos, seg)
                return seq_heads(bank, ppool(h, seg, seg_row,
                                             seg_start))

            def packed_both_fn(trunk_params, bank, tok_bank, ids, mask,
                               pos, seg, seg_row, seg_start):
                h = hidden_fn(trunk_params, ids, mask, pos, seg)
                return (seq_heads(bank, ppool(h, seg, seg_row,
                                              seg_start)),
                        tok_heads(tok_bank, h))

        def tok_fn(trunk_params, tok_bank, ids, mask):
            return tok_heads(tok_bank, hidden_fn(trunk_params, ids,
                                                 mask))

        def packed_tok_fn(trunk_params, tok_bank, ids, mask, pos, seg):
            return tok_heads(tok_bank,
                             hidden_fn(trunk_params, ids, mask, pos,
                                       seg))

        return {
            "seq": jax.jit(seq_fn),
            "tok": jax.jit(tok_fn),
            "both": jax.jit(both_fn),
            "packed_seq": jax.jit(packed_seq_fn),
            "packed_tok": jax.jit(packed_tok_fn),
            "packed_both": jax.jit(packed_both_fn),
            "trunk_params": serving_params,
            # the Mesh this program set serves under (None = single
            # device): carried IN the snapshot so an in-flight batch
            # pads, places, and demuxes with the mesh its programs were
            # built for — a hot mesh flip can never tear a batch
            "mesh": srv_mesh,
            "meta": meta,
        }

    def trunk_group_info(self) -> Dict[str, List[str]]:
        """gid → member task names (management API / tests)."""
        with self._lock:
            return {g.gid: list(g.members)
                    for g in self._groups_by_gid.values()}

    # -- sequence packing (engine.packing, docs/PACKING.md) ----------------

    def _packing_bucket_of(self, key: Hashable) -> Optional[int]:
        """The packing scheduler's eligibility callback: the row length
        for groups the fused runner can PACK, else None (the composer
        then keeps base fixed-batch behavior, so a step can never carry
        more items than the unpacked path could serve).  Packable =
        fused trunk group, dense attention, no serving mesh (sharded
        packed gathers are the ROADMAP follow-on), bucket not demoted by
        the auto-tuner."""
        if not (isinstance(key, tuple) and len(key) == 3
                and key[0] == TRUNK_KEY):
            return None
        if self.mesh is not None:
            return None
        g = getattr(self, "_groups_by_gid", {}).get(key[1])
        if g is None or getattr(g.config, "attention_impl",
                                "dense") != "dense":
            return None
        tuner = getattr(self, "_autotuner", None)
        if tuner is not None and tuner.blocked(f"trunk:{key[1]}",
                                               key[2]):
            return None
        return int(key[2])

    def _packing_segment_cap_of(self, key: Hashable) -> int:
        """Per-group segment cap, tuner policy over the config default —
        the ONE value the scheduler's take AND the runner's pack both
        use, so a planned step always re-plans identically."""
        base = self._packing["max_segments_per_row"]
        tuner = getattr(self, "_autotuner", None)
        if tuner is None or not (isinstance(key, tuple)
                                 and len(key) == 3):
            return base
        pol = tuner.policy(f"trunk:{key[1]}")
        try:
            return max(1, int(pol.get("max_segments_per_row", base)))
        except (TypeError, ValueError):
            return base

    def configure_packing(self, knobs: Optional[Dict[str, Any]]) -> None:
        """Apply the engine.packing block (boot + config hot reload):
        normalizes through the ONE interpretation point and retunes the
        live scheduler + auto-tuner in place — no batcher swap, no
        pending-item loss."""
        pk = normalize_packing(knobs)
        was_enabled = bool(self._packing.get("enabled"))
        self._packing = pk
        if was_enabled and not pk["enabled"]:
            # packing off: the packed programs stop serving.  Purge
            # their census keys into warm hints (re-enable warms them
            # back via warmup_packed_hot, same as a rebuild) and retire
            # their measured/cost rows so repeated enable/disable flips
            # can't grow label cardinality or report dead packed EWMAs.
            with self._lock:
                keys = [k for k in self._compiled_steps
                        if k[1].startswith("packed:")]
                self._compiled_steps -= set(keys)
            by_group: Dict[str, List[tuple]] = {}
            for k in keys:
                by_group.setdefault(k[0], []).append(k)
            for g in list(self._groups_by_gid.values()):
                gkeys = by_group.get(f"trunk:{g.gid}")
                if gkeys:
                    g.warm_hints = sorted(
                        set(self._parse_census_keys(gkeys))
                        | {tuple(r) for r in (g.warm_hints or ())})
            self._retire_programs(variant_prefix="packed")
        if isinstance(self.batcher, PackingBatcher):
            self.batcher.configure(pk)
        tuner = self._autotuner
        if tuner is not None:
            at = pk["autotune"]
            tuner.target_fill = at["target_fill"]
            tuner.min_samples = at["min_samples"]
            tuner.max_segments_cap = at["max_segments_cap"]
            tuner.interval_s = max(0.5, at["interval_s"])
            # per-group caps grow from the (possibly re-tuned) config
            # default, not a stale boot-time floor
            tuner.segments_floor = pk["max_segments_per_row"]

    def packing_report(self) -> Dict[str, Any]:
        """Operator snapshot (GET /debug/runtime rides this via the
        engine owner): live knobs, scheduler state, auto-tuner policy."""
        out: Dict[str, Any] = {"knobs": {
            k: (dict(v) if isinstance(v, dict) else v)
            for k, v in self._packing.items()}}
        b = self.batcher
        if isinstance(b, PackingBatcher):
            out["scheduler"] = {
                "enabled": b.enabled,
                "max_segments_per_row": b.max_segments_per_row,
                "max_items_per_step": b._item_budget(),
                "max_inflight_steps": b.max_inflight_steps,
                "starvation_steps": b.starvation_steps,
            }
        if self._autotuner is not None:
            out["autotuner"] = self._autotuner.report()
        return out

    def _common_trunk_group(self, tasks: Sequence[str]
                            ) -> Optional[TrunkGroup]:
        """The single TrunkGroup serving every task, or None."""
        if not tasks:
            return None
        g = self._task_group.get(tasks[0])
        return g if all(self._task_group.get(t) is g for t in tasks) \
            else None

    def fused_covers(self, tasks: Sequence[str]) -> bool:
        """True when one fused execution serves every listed task: the
        sequence and token tasks of ONE trunk group (classify_multi sends
        them as one item a text) — the dispatcher's gather gate."""
        tasks = list(tasks)
        return all(self.task_kind(t) in ("sequence", "token")
                   for t in tasks) \
            and self._common_trunk_group(tasks) is not None

    def classify_multi(self, tasks: Sequence[str], texts: Sequence[str],
                       timeout: float = 30.0,
                       enc_cache=None,
                       threshold: float = 0.5) -> Dict[str, List[Any]]:
        """Classify the same texts under several tasks — the signal
        fan-out shape: ``{task: [result per text]}``, a ClassResult for a
        sequence task and a TokenClassResult (entity spans scored at
        ``threshold``, token_classify's argument) for a token task.
        Tasks sharing a fused trunk group — sequence and token members
        alike — ride ONE batched submit (tokenize once, trunk forward
        once, heads demuxed); tasks of different trunks fall back to
        per-task classify_batch / token_classify under one deadline."""
        tasks = list(tasks)
        token_tasks = set()
        for t in tasks:
            if self._require(t).kind == "token":
                token_tasks.add(t)
            else:
                self._require(t, kind="sequence")
        group = self._common_trunk_group(tasks)
        if group is not None:
            return self._fused_multi(group, tasks, texts, timeout=timeout,
                                     enc_cache=enc_cache,
                                     threshold=threshold)
        deadline = time.perf_counter() + timeout

        def remaining() -> float:
            return max(0.05, deadline - time.perf_counter())

        return {t: [self.token_classify(t, text, threshold=threshold,
                                        timeout=remaining(),
                                        enc_cache=enc_cache)
                    for text in texts] if t in token_tasks
                else self.classify_batch(t, texts, timeout=remaining(),
                                         enc_cache=enc_cache)
                for t in tasks}

    def _fused_multi(self, g: TrunkGroup, tasks: Sequence[str],
                     texts: Sequence[str], timeout: float = 30.0,
                     enc_cache=None, threshold: float = 0.5
                     ) -> Dict[str, List[Any]]:
        """The trunk-group fan-out: each text is ONE batch item carrying
        every requested task, token members (spans scored at
        ``threshold``) beside sequence members — tokenized once,
        submitted as one submit_many per bucket (guaranteed coalescing),
        trunk forward shared, per-task logits demuxed by the fused
        runner."""
        deadline = time.perf_counter() + timeout
        tasks = list(tasks)
        by_bucket: Dict[int, List[tuple]] = {}
        for ti, text in enumerate(texts):
            enc, tok_s, cached = self._encode_group_info(g, tasks, text,
                                                         enc_cache)
            bucket = pick_bucket(len(enc), self.cfg.seq_len_buckets)
            by_bucket.setdefault(bucket, []).append(
                (ti, _Payload(text, enc, threshold, tasks=tuple(tasks),
                              tok_s=tok_s, tok_cached=cached)))
        futs: List[tuple] = []
        for bucket, entries in by_bucket.items():
            fs = self.batcher.submit_many(
                (TRUNK_KEY, g.gid, bucket), [p for _, p in entries])
            futs.extend(zip((ti for ti, _ in entries), fs))
        results: List[Optional[Dict[str, Any]]] = [None] * len(texts)
        for ti, f in futs:
            res = f.result(timeout=max(0.05,
                                       deadline - time.perf_counter()))
            if not isinstance(res, dict):  # single-task fused item
                res = {tasks[0]: res}
            results[ti] = res
        return {t: [results[i][t] for i in range(len(texts))]
                for t in tasks}

    def _emit_registered(self, name: str, kind: str) -> None:
        """Model-runtime lifecycle event (pkg/modelruntime role)."""
        from ..runtime.events import TASK_REGISTERED, default_bus

        bus = self._events if self._events is not None else default_bus
        bus.emit(TASK_REGISTERED, task=name, kind=kind,
                 sharded=self.mesh is not None)

    def _shard_generator_params(self, generator) -> None:
        """Generator-backed tasks (generative KV decode, multimodal
        towers) hold their params inside the generator object — with a
        serving mesh they shard like every other task instead of
        silently bypassing the bank layout (VERDICT r2 weak #7)."""
        if self.mesh is None:
            return
        params = getattr(generator, "params", None)
        if params is None:
            return
        from ..parallel import shard_params

        generator.params = shard_params(params, self.mesh)

    def register_multimodal(self, name: str, embedder) -> None:
        """Register a shared text/image embedding space task
        (multimodal_embedding.rs role; embedder = models.siglip
        SiglipEmbedder)."""
        self._shard_generator_params(embedder)
        with self._lock:
            self._tasks[name] = _Task(
                name, "multimodal", [], getattr(embedder, "tokenizer", None),
                None, None, 0, generator=embedder)
        self._emit_registered(name, "multimodal")

    def embed_multimodal(self, task: str, texts=None, images=None,
                         image_refs=None) -> Dict[str, np.ndarray]:
        """Embed texts and/or images into the task's shared space.
        ``images`` are preprocessed float arrays; ``image_refs`` are
        wire-format references (data URIs / base64) decoded host-side.
        Returns {"text": [n, d], "image": [m, d]} (present keys only);
        cross-modal similarity is the dot product."""
        t = self._require(task, kind="multimodal")
        out: Dict[str, np.ndarray] = {}
        if texts:
            out["text"] = t.generator.embed_text(list(texts))
        if images is not None and len(images):
            out["image"] = t.generator.embed_image(images)
        elif image_refs:
            out["image"] = t.generator.embed_image_refs(list(image_refs))
        return out

    def register_generative(self, name: str, generator,
                            labels: Optional[List[str]] = None,
                            adapter_index: Optional[Dict[str, int]] = None
                            ) -> None:
        """Register a KV-cached greedy generator as a "generative" task
        (qwen3_multi_lora_classifier.rs / qwen3_guard.rs serving role).
        ``adapter_index`` maps logical adapter names → LoRA task rows so a
        request can select its adapter by name (O(1) swap, no recompile)."""
        self._shard_generator_params(generator)
        with self._lock:
            self._tasks[name] = _Task(
                name, "generative", list(labels or []),
                generator.tokenizer, None, None, 0,
                generator=generator, adapter_index=dict(adapter_index or {}))
        self._emit_registered(name, "generative")

    def generate(self, task: str, prompts: Sequence[str],
                 max_new_tokens: int = 64, adapter: str = "",
                 stop_strings: Sequence[str] = (),
                 keep_tail: int = 0) -> List[Any]:
        """Greedy generation on a generative task; ``adapter`` selects the
        LoRA row by name (generative multi-LoRA per-request selection).
        A prompt longer than the largest bucket keeps its first tokens and
        its last ``keep_tail``; its result says ``truncated`` and
        llm_batcher_bucket_overflow_total counts it (never silent)."""
        t = self._require(task, kind="generative")
        if adapter:
            if adapter not in t.adapter_index:
                # a silent row-0 fallback would run the WRONG safety/LoRA
                # policy on config drift — fail loudly instead
                raise KeyError(
                    f"unknown adapter {adapter!r} for task {task!r} "
                    f"(known: {sorted(t.adapter_index)})")
            task_index = t.adapter_index[adapter]
        else:
            task_index = 0
        # a batch group per (task, prompt bucket, generation settings):
        # concurrent callers ride one generation in lock step
        # (_run_generative), at most one in flight per group
        largest = self.cfg.seq_len_buckets[-1]
        payloads = []
        from ..observability import batchtrace

        for p in prompts:
            # beside the tokenize seam (no length to clip at, no cache, and
            # no counter or span on a path where sixteen callers stand in
            # line between two generations): the marker alone
            t0 = time.perf_counter()
            enc = t.tokenizer.encode(p)
            batchtrace.tokenized(task, time.perf_counter() - t0, len(enc),
                                 cached=False)
            # a generator of prompts only tokenizes for itself: no cut here
            if len(enc) > largest and getattr(t.generator, "batched", False):
                enc = _cut_to_bucket(enc, largest, keep_tail)
            payloads.append(_Payload(p, enc))
        futures = [self.batcher.submit(
            (GEN_KEY, task,
             pick_bucket(len(p.encoding), self.cfg.seq_len_buckets),
             int(max_new_tokens), task_index, tuple(stop_strings)), p)
            for p in payloads]
        return [f.result() for f in futures]

    def guard_classify(self, task: str, text: str, role: str = "user",
                       adapter: str = "",
                       max_new_tokens: Optional[int] = None):
        """Qwen3Guard-style safety classification: structured-output
        generation + regex parse (qwen3_guard.rs:513). Returns a
        GuardVerdict; parse failures fail closed to Controversial.  The
        verdict's length is the generator's own ``gen_length`` (the
        task's ``generation.gen_length``; what ``warmup`` compiled for).
        Of a prompt longer than the largest bucket the END OF THE TEXT is
        cut, not the template's closing words; the verdict then says
        ``truncated``."""
        from ..models.generate import (
            GEN_LENGTH,
            GUARD_PROMPT_TAIL,
            build_guard_prompt,
            parse_guard_output,
        )

        t = self._require(task, kind="generative")
        out = self.generate(
            task, [build_guard_prompt(text, role=role)],
            max_new_tokens=max_new_tokens
            or getattr(t.generator, "gen_length", GEN_LENGTH),
            adapter=adapter,
            keep_tail=len(t.tokenizer.encode(GUARD_PROMPT_TAIL)))
        verdict = parse_guard_output(out[0].text)
        verdict.truncated = getattr(out[0], "truncated", False)
        return verdict

    def has_task(self, name: str) -> bool:
        return name in self._tasks

    def task_kind(self, name: str) -> str:
        """"sequence" | "token" | "embedding" | "generative" | "" (absent)."""
        t = self._tasks.get(name)
        return t.kind if t is not None else ""

    def task_labels(self, name: str) -> List[str]:
        return list(self._tasks[name].labels)

    def tasks(self) -> List[str]:
        return list(self._tasks)

    def task_info(self, name: str) -> Dict[str, Any]:
        """Serving metadata for the management API (/info/models):
        kind, labels, max_seq_len, attention impl, mesh placement."""
        t = self._tasks.get(name)
        if t is None:
            return {}
        impl = getattr(getattr(t.module, "config", None),
                       "attention_impl", None)
        info: Dict[str, Any] = {
            "task": name, "kind": t.kind,
            "max_seq_len": t.max_seq_len,
        }
        if impl:
            info["attention_impl"] = impl
        if self.mesh is not None:
            info["mesh"] = {k: int(v) for k, v in
                            self.mesh.shape.items() if v > 1}
        g = self._task_group.get(name)
        if g is not None:
            info["trunk_group"] = g.gid
        return info

    # -- public inference --------------------------------------------------

    def classify(self, task: str, text: str, timeout: float = 30.0,
                 enc_cache=None) -> ClassResult:
        return self.classify_batch(task, [text], timeout=timeout,
                                   enc_cache=enc_cache)[0]

    def classify_batch(self, task: str, texts: Sequence[str],
                       timeout: float = 30.0,
                       enc_cache=None) -> List[ClassResult]:
        futures = self._submit_texts(task, texts, enc_cache=enc_cache)
        return [f.result(timeout=timeout) for f in futures]

    def classify_async(self, task: str, text: str, enc_cache=None):
        return self._submit_texts(task, [text], enc_cache=enc_cache)[0]

    def classify_windowed(self, task: str, text: str, stride: int = 64,
                          timeout: float = 30.0) -> ClassResult:
        """Whole-input classification for texts past ``max_seq_len``:
        stride/overflow windows (utils.tokenization.encode_windows —
        every window a valid CLS/SEP-framed input) classified as one
        device batch, probabilities combined weighted by each window's
        content share.  The result covers the ENTIRE text, so it is
        never marked truncated — the honest alternative to the flagged
        tail-drop ``classify`` reports (VERDICT r4 item 6; reference
        candle-binding core/tokenization.rs stride mode)."""
        from ..utils.tokenization import encode_windows

        t = self._require(task, kind="sequence")
        windows = encode_windows(t.tokenizer, text, t.max_seq_len,
                                 stride=stride)
        if len(windows) == 1:
            return self.classify(task, text, timeout=timeout)
        futures = []
        for enc in windows:
            bucket = pick_bucket(len(enc), self.cfg.seq_len_buckets)
            futures.append(self.batcher.submit(
                (task, bucket), _Payload(text, enc)))
        results = [f.result(timeout=timeout) for f in futures]
        weights = np.asarray([len(w) for w in windows], np.float64)
        weights = weights / weights.sum()
        labels = list(results[0].probs)
        combined = {
            l: float(sum(w * r.probs.get(l, 0.0)
                         for w, r in zip(weights, results)))
            for l in labels}
        best = max(combined, key=combined.get)
        return ClassResult(
            label=best,
            index=t.labels.index(best) if best in t.labels else -1,
            confidence=combined[best],
            probs=combined,
            latency_s=max(r.latency_s for r in results),
            truncated=False,
        )

    def token_classify(self, task: str, text: str, threshold: float = 0.5,
                       timeout: float = 30.0,
                       enc_cache=None) -> TokenClassResult:
        t = self._require(task, kind="token")
        enc, tok_s, cached = self._encode_info(t, text, enc_cache)
        bucket = pick_bucket(len(enc), self.cfg.seq_len_buckets)
        g = self._task_group.get(task)
        if g is not None:
            # fused token member: batch under the TRUNK — one trunk
            # forward serves concurrent sequence AND token siblings,
            # and the packed path covers token spans too
            fut = self.batcher.submit(
                (TRUNK_KEY, g.gid, bucket),
                _Payload(text, enc, threshold, tasks=(task,),
                         tok_s=tok_s, tok_cached=cached))
        else:
            fut = self.batcher.submit(
                (task, bucket),
                _Payload(text, enc, threshold,
                         tok_s=tok_s, tok_cached=cached))
        return fut.result(timeout=timeout)

    def embed(self, task: str, texts: Sequence[str],
              exit_layer: Optional[int] = None,
              output_dim: Optional[int] = None,
              timeout: float = 30.0) -> np.ndarray:
        """Batch-embed texts → [n, dim] float32 (L2-normalized). Matryoshka
        knobs select the layer-exit/dim-truncation variant (N5 2D-Matryoshka;
        GetEmbedding2DMatryoshka semantic-router.go:1514)."""
        if not texts:
            return np.zeros((0, 0), dtype=np.float32)
        futures = self.embed_async(task, texts, exit_layer, output_dim)
        return np.stack([f.result(timeout=timeout) for f in futures])

    def embed_async(self, task: str, texts: Sequence[str],
                    exit_layer: Optional[int] = None,
                    output_dim: Optional[int] = None) -> list:
        t = self._require(task, kind="embedding")
        futures = []
        for text in texts:
            enc = self._encode_info(t, text)[0]
            bucket = pick_bucket(len(enc), self.cfg.seq_len_buckets)
            # exit/dim participate in the group key: different variants are
            # different XLA programs and must not share a device batch
            fut = self.batcher.submit(
                (task, bucket, exit_layer, output_dim),
                _Payload(text, enc, exit_layer=exit_layer,
                         output_dim=output_dim))
            futures.append(fut)
        return futures

    def warmup(self, tasks: Optional[Sequence[str]] = None,
               buckets: Optional[Sequence[int]] = None,
               batch_sizes: Sequence[int] = (1,)) -> None:
        """Pre-trigger jit compilation for the hot (task, bucket, batch)
        shapes (reference warmupRouterRuntime, runtime_bootstrap.go:439).
        ``batch_sizes``: real-row counts to warm, each padded the way the
        batcher pads; the default warms batch 1 only, so every other
        padded batch still compiles on its first use.

        EVERY bucket a task can serve warms by default — a cold bucket in
        production is a guaranteed SLO breach (one full XLA compile on the
        first request of that shape).  Warmup calls the task's jitted
        apply DIRECTLY instead of going through the batcher: the batcher
        has ONE worker thread shared with live traffic, and parking a
        multi-second 32K-bucket compile on it would queue real requests
        past their timeouts — the exact breach warmup exists to prevent.
        The compile cache is on the jitted function, so live requests of
        the same shape hit it either way."""
        failures: List[str] = []
        with self._lock:
            self._warmup_report = []

        def warm(target: str, bucket: int, rows: int,
                 fn: Callable[[], None]) -> None:
            # every (target, bucket, batch) is attempted and timed; a
            # failure is recorded and raised at the END — startup must
            # say which program the compiler refused, never serve on
            # without it
            t0 = time.perf_counter()
            row = {"target": target, "bucket": int(bucket),
                   "rows": int(rows), "error": ""}
            try:
                fn()
            except Exception as exc:
                row["error"] = f"{type(exc).__name__}: {exc}"
                failures.append(
                    f"{target}@{bucket}x{rows}: {row['error'][:400]}")
            row["seconds"] = round(time.perf_counter() - t0, 3)
            with self._lock:
                self._warmup_report.append(row)

        for name in tasks or list(self._tasks):
            t = self._tasks.get(name)
            if t is None or t.kind == "multimodal":
                continue  # its compile cache keys on other shapes
            if t.kind == "generative":
                # the programs of (rows, prompt bucket) at the generator's
                # own length of generation: what guard_classify will run
                if not hasattr(t.generator, "warm"):
                    continue
                for b, n in ((b, n)
                             for b in buckets or self.cfg.seq_len_buckets
                             for n in batch_sizes):
                    warm(f"gen:{name}", b, n,
                         lambda t=t, b=b, n=n: t.generator.warm(
                             self._padded_batch(n), b))
                continue
            if name in self._task_group:
                # fused members serve through their trunk group's
                # programs (warmed below); the per-task program only
                # runs classify_windowed's multi-window batches, whose
                # batch sizes this batch-1 warmup never covered — a
                # full-width compile per bucket for nothing
                continue
            for b, n in ((b, n)
                         for b in buckets or self.cfg.seq_len_buckets
                         for n in batch_sizes):
                if b > t.max_seq_len:
                    continue

                def warm_task(t=t, b=b, n=n) -> None:
                    padded_n = self._padded_batch(n)
                    ids = np.full((padded_n, b), t.pad_id, np.int32)
                    ids[:, 0] = 1
                    mask = np.ones((padded_n, b), np.int32)
                    ids_dev, mask_dev = self._to_device(ids, mask)
                    if t.kind == "embedding":
                        # every configured Matryoshka variant is its own
                        # XLA program (static exit/dim): warm them ALL —
                        # engine.matryoshka_layers/dims declare which
                        # (layer, dim) pairs this deployment serves
                        for el, od in self._matryoshka_variants():
                            out = t.apply_fn(t.params, ids_dev, mask_dev,
                                             exit_layer=el, output_dim=od)
                            jax.block_until_ready(out)
                    else:
                        out = t.apply_fn(t.params, ids_dev, mask_dev)
                        jax.block_until_ready(out)

                warm(f"task:{name}", b, n, warm_task)
        # fused trunk groups compile their OWN programs (trunk + stacked
        # heads): warm those the same way — one cold fused bucket would
        # stall the whole bank's traffic, not one task's.  Every flavor
        # the group can serve warms: seq AND tok/both (token members),
        # AND the packed siblings when packing is enabled — a cold
        # packed program would compile inline on the dispatch worker,
        # the exact stall this warmup exists to prevent.
        for g in list(self._groups_by_gid.values()):
            if tasks and not any(m in tasks for m in g.members):
                continue
            for b, n in ((b, n)
                         for b in buckets or self.cfg.seq_len_buckets
                         for n in batch_sizes):
                if b > g.max_seq_len:
                    continue

                def warm_group(g=g, b=b, n=n) -> None:
                    fns = g.fns
                    srv_mesh = fns.get("mesh")
                    # banks from the SAME snapshot as the programs —
                    # the runner's consistency contract applies to
                    # warmup too (a mesh flip mid-warmup must not mix
                    # placements)
                    dmx = fns.get("demux") or g.demux or {}
                    bank = dmx.get("bank")
                    tok_bank = dmx.get("tok_bank")
                    padded_n = self._padded_batch(n, mesh=srv_mesh)
                    ids = np.full((padded_n, b), g.pad_id, np.int32)
                    ids[:, 0] = 1
                    mask = np.ones((padded_n, b), np.int32)
                    ids_dev, mask_dev = self._to_device(ids, mask,
                                                        mesh=srv_mesh)
                    tp = fns["trunk_params"]
                    # BGMV programs carry the pair operands; warm the
                    # 1-pair entry shape (other pair widths compile on
                    # demand — each is one more pow2 program)
                    pair = (jnp.zeros(1, jnp.int32),
                            jnp.zeros(1, jnp.int32)) \
                        if fns["meta"]["bgmv"] else ()
                    if bank is not None:
                        jax.block_until_ready(fns["seq"](
                            tp, bank, ids_dev, mask_dev, *pair))
                    if tok_bank is not None:
                        jax.block_until_ready(fns["tok"](
                            tp, tok_bank, ids_dev, mask_dev))
                        if bank is not None:
                            out = fns["both"](tp, bank, tok_bank,
                                              ids_dev, mask_dev, *pair)
                            jax.block_until_ready(out)
                    self._warm_packed(g, b)

                warm(f"trunk:{g.gid}", b, n, warm_group)
        if failures:
            raise WarmupError(
                f"{len(failures)} warmup program(s) failed: "
                + "; ".join(failures))

    def warmup_report(self) -> List[Dict[str, Any]]:
        """One row per (target, bucket, rows) the last warmup()
        attempted: {target: "task:<name>" | "trunk:<gid>", bucket, rows,
        seconds, error} — seconds is compile + one execution of every
        program of the target at that shape; error is "" on success."""
        with self._lock:
            return [dict(r) for r in self._warmup_report]

    def _warm_packed(self, g: TrunkGroup, bucket: int) -> None:
        """Pre-compile the hot packed programs for one (group, bucket):
        a 1-row, 2-segment packed batch per flavor — the min_segments
        entry shape every packed bucket hits first.  Other (rows, K)
        shapes warm from the compiled-step census via
        warmup_packed_hot (docs/PACKING.md "packed-path warmup")."""
        mesh = g.fns.get("mesh") if g.fns is not None else None
        self._warm_packed_shape(g, bucket, k_pad=2,
                                padded_rows=self._padded_batch(
                                    1, mesh=mesh))

    def _warm_packed_shape(self, g: TrunkGroup, bucket: int, k_pad: int,
                           padded_rows: int, pair_pad: int = 0,
                           flavors: Optional[Sequence[str]] = None
                           ) -> bool:
        """Compile one packed (padded_rows, bucket, K_pad) program set
        off the dispatch path, then MARK it in the compiled-step
        registry: the first real packed step of this shape is a warm
        execute and must account as one (cold-count stays flat —
        tests/test_packing.py TestPackedWarmup).  False = packing cannot
        serve this group right now; a program that fails to compile or
        run RAISES."""
        if not self._packing["enabled"] or self.mesh is not None \
                or g.fns is None \
                or getattr(g.config, "attention_impl",
                           "dense") != "dense":
            return False
        fns = g.fns
        srv_mesh = fns.get("mesh")
        msfx = mesh_suffix(fns["meta"].get("mesh"))
        dmx = fns.get("demux") or g.demux or {}
        bank = dmx.get("bank")
        tok_bank = dmx.get("tok_bank")

        class _WarmEnc:
            """Minimal Encoding shim so warmup builds its packed
            batch through pack_items — ONE layout implementation,
            the warm program traces exactly what real packed steps
            will."""

            def __init__(self, n: int) -> None:
                self.ids = np.ones(n, np.int32)
                self.attention_mask = np.ones(n, np.int32)

            def __len__(self) -> int:
                return len(self.ids)

        k_eff = max(2, int(k_pad))
        half = max(1, bucket // 2)
        pb = pack_items(
            [_WarmEnc(half), _WarmEnc(bucket - half)], bucket,
            g.pad_id, max_rows=1, max_segments_per_row=2,
            pad_rows_to=padded_rows, pad_segments_to=k_eff)
        ids_dev, mask_dev = self._to_device(pb.ids, pb.mask,
                                            mesh=srv_mesh)
        if srv_mesh is not None:
            from ..parallel import batch_sharding, replicated

            row_sh = batch_sharding(srv_mesh)
            rep = replicated(srv_mesh)
            pos_dev = jax.device_put(pb.position_ids, row_sh)
            seg_dev = jax.device_put(pb.segment_ids, row_sh)
            row_dev = jax.device_put(pb.seg_row, rep)
            start_dev = jax.device_put(pb.seg_start, rep)
        else:
            pos_dev = jnp.asarray(pb.position_ids)
            seg_dev = jnp.asarray(pb.segment_ids)
            row_dev = jnp.asarray(pb.seg_row)
            start_dev = jnp.asarray(pb.seg_start)
        tp = fns["trunk_params"]
        if fns["meta"]["bgmv"]:
            pp = int(pair_pad) or 2
            pair = (jnp.zeros(pp, jnp.int32),
                    jnp.zeros(pp, jnp.int32))
            sfx = f":p{pp}"
        else:
            pair, sfx = (), ""
        want = set(flavors or ("seq", "tok", "both"))
        meta = fns["meta"]
        measured = "packed_mesh" if srv_mesh is not None else "packed"
        if bank is not None and "seq" in want:
            jax.block_until_ready(fns["packed_seq"](
                tp, bank, ids_dev, mask_dev,
                pos_dev, seg_dev, row_dev, start_dev, *pair))
            if self._step_fresh(f"trunk:{g.gid}",
                                f"packed:seq:{k_eff}{sfx}{msfx}",
                                (padded_rows, bucket)):
                self._capture_program(
                    f"trunk:{g.gid}", bucket,
                    f"packed:seq:{k_eff}{sfx}{msfx}",
                    (padded_rows, bucket), fns["packed_seq"],
                    (tp, bank, ids_dev, mask_dev, pos_dev, seg_dev,
                     row_dev, start_dev, *pair), measured, meta)
        if tok_bank is not None and "tok" in want:
            jax.block_until_ready(fns["packed_tok"](
                tp, tok_bank, ids_dev, mask_dev,
                pos_dev, seg_dev))
            if self._step_fresh(f"trunk:{g.gid}",
                                f"packed:tok:{k_eff}{msfx}",
                                (padded_rows, bucket)):
                self._capture_program(
                    f"trunk:{g.gid}", bucket,
                    f"packed:tok:{k_eff}{msfx}",
                    (padded_rows, bucket), fns["packed_tok"],
                    (tp, tok_bank, ids_dev, mask_dev, pos_dev,
                     seg_dev), measured, meta)
        if bank is not None and tok_bank is not None \
                and "both" in want:
            out = fns["packed_both"](
                tp, bank, tok_bank, ids_dev, mask_dev,
                pos_dev, seg_dev, row_dev, start_dev, *pair)
            jax.block_until_ready(out)
            if self._step_fresh(f"trunk:{g.gid}",
                                f"packed:both:{k_eff}{sfx}{msfx}",
                                (padded_rows, bucket)):
                self._capture_program(
                    f"trunk:{g.gid}", bucket,
                    f"packed:both:{k_eff}{sfx}{msfx}",
                    (padded_rows, bucket), fns["packed_both"],
                    (tp, bank, tok_bank, ids_dev, mask_dev, pos_dev,
                     seg_dev, row_dev, start_dev, *pair),
                    measured, meta)
        return True

    def _packed_census_rows(self, gid: str) -> list:
        """Packed program shapes this engine has executed for one
        group, recovered from the compiled-step registry:
        (bucket, k_pad, padded_rows, flavor, pair_pad) tuples — the
        shape census warmup_packed_hot recompiles after a retune or a
        kernel-flip rebuild."""
        group = f"trunk:{gid}"
        with self._lock:
            keys = [k for k in self._compiled_steps if k[0] == group]
        return self._parse_census_keys(keys)

    @staticmethod
    def _parse_census_keys(keys) -> list:
        out = set()
        for k in keys:
            variant = k[1]
            if not variant.startswith("packed:"):
                continue
            try:
                parts = variant.split(":")
                flavor, k_pad = parts[1], int(parts[2])
                # optional trailing parts: ":pN" (BGMV pair pad) and
                # ":mAxB" (mesh signature — not part of the census row;
                # warmup re-derives the CURRENT mesh at warm time)
                pair_pad = 0
                for extra in parts[3:]:
                    if extra.startswith("p"):
                        pair_pad = int(extra[1:])
                padded_rows, bucket = int(k[2]), int(k[3])
            except (IndexError, ValueError):
                continue
            out.add((bucket, k_pad, padded_rows, flavor, pair_pad))
        return sorted(out)

    def packed_shape_census(self) -> Dict[str, list]:
        """gid → packed shape rows (operator/tests view)."""
        return {gid: self._packed_census_rows(gid)
                for gid in list(self._groups_by_gid)}

    def warmup_packed_hot(self) -> int:
        """Pre-compile every packed shape the census (plus any
        warm_hints a kernel-flip rebuild carried over) says is hot,
        against the CURRENT program set.  Bootstrap calls this at
        apply-knobs time (boot + hot reload) so the first packed step
        after a boot/retune/kernel-flip is a warm execute, not an
        inline XLA compile on the dispatch worker.  Returns the number
        of shapes warmed."""
        n = 0
        for gid, g in list(self._groups_by_gid.items()):
            rows = set(self._packed_census_rows(gid))
            rows.update(tuple(r) for r in (g.warm_hints or ()))
            # rows that cannot warm RIGHT NOW (packing hot-disabled, a
            # transient failure) stay as hints — re-enabling packing
            # later must still find the hot shapes to warm
            remaining = set()
            for row in sorted(rows):
                bucket, k_pad, padded_rows, flavor, pair_pad = row
                try:
                    warmed = self._warm_packed_shape(
                        g, bucket, k_pad, padded_rows,
                        pair_pad=pair_pad, flavors=(flavor,))
                except Exception as exc:
                    # knob-apply time (boot + hot reload) stays
                    # fail-open like every apply_* path, but says what
                    # the compiler refused
                    warmed = False
                    from ..observability.logging import component_event

                    component_event(
                        "engine", "packed_warmup_failed",
                        level="warning", group=gid, row=list(row),
                        error=f"{type(exc).__name__}: {exc}"[:400])
                if warmed:
                    n += 1
                else:
                    remaining.add(row)
            g.warm_hints = sorted(remaining) if remaining else None
        return n

    def _matryoshka_variants(self):
        """(exit_layer, output_dim) pairs to pre-compile: the full model
        plus every configured 2D-Matryoshka combination."""
        variants = [(None, None)]
        for el in (self.cfg.matryoshka_layers or []):
            variants.append((int(el), None))
        for od in (self.cfg.matryoshka_dims or []):
            variants.append((None, int(od)))
        for el in (self.cfg.matryoshka_layers or []):
            for od in (self.cfg.matryoshka_dims or []):
                variants.append((int(el), int(od)))
        return variants

    def shutdown(self) -> None:
        try:
            self._rs_provider_host.unregister_provider(
                self.batcher.name, self._rs_provider_fn)
        except Exception:
            pass
        if self._autotuner is not None:
            self._autotuner.stop()
        self.batcher.shutdown()

    # -- internals ---------------------------------------------------------

    def _require(self, task: str, kind: Optional[str] = None) -> _Task:
        t = self._tasks.get(task)
        if t is None:
            raise KeyError(f"task {task!r} not registered "
                           f"(known: {sorted(self._tasks)})")
        if kind is not None and t.kind != kind:
            right_call = {"token": "token_classify", "sequence": "classify",
                          "embedding": "embed",
                          "generative": "generate",
                          "multimodal": "embed_multimodal"}[t.kind]
            raise TypeError(
                f"task {task!r} is a {t.kind} task; use {right_call}()")
        return t

    def _series(self):
        if self._metrics is not None:
            return self._metrics
        from ..observability import metrics as M

        return M.default_series

    def _count_tokenization(self, task: str) -> None:
        self._series().tokenizations.inc(task=task)

    def _note_shape(self, group: str, shape: tuple) -> None:
        """Record a device shape this group executed (shape_census)."""
        with self._lock:
            self._shapes.setdefault(group, set()).add(tuple(shape))

    def _step_fresh(self, group: str, variant: str, shape: tuple) -> bool:
        """Compile detection for the step sampler, keyed per (group,
        VARIANT, shape): the fused flavours, the packed and the per-task
        paths are distinct XLA programs, so a shape first seen by one
        must still count another's first execution as a compile
        (shape_census stays variant-free — it budgets device shapes,
        not programs)."""
        key = (group, variant, *shape)
        with self._lock:
            fresh = key not in self._compiled_steps
            self._compiled_steps.add(key)
        return fresh

    def _capture_program(self, group: str, bucket: int, variant: str,
                         shape: tuple, fn, args,
                         measured_variant: str,
                         meta: Optional[Dict[str, Any]] = None,
                         kwargs: Optional[Dict[str, Any]] = None) -> None:
        """Register a freshly-compiled program with the cost catalog
        (observability.programstats).  Called exactly where
        ``_step_fresh`` said the census key is new — the same sites that
        count an XLA compile.  The hot path only pays a tree_map to
        ShapeDtypeStructs (no device arrays pinned) plus one dict
        insert; the AOT ``lower().compile().cost_analysis()`` runs
        deferred at catalog-read time.  Never raises."""
        ps = self._program_stats
        if ps is None or not getattr(ps, "enabled", False):
            return
        try:
            abstract = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.result_type(x)),
                tuple(args))
            kw = dict(kwargs or {})

            def lower(fn=fn, abstract=abstract, kw=kw):
                return fn.lower(*abstract, **kw)

            meta = meta or {}
            kernels = "+".join(k for k in ("epilogue", "bgmv")
                               if meta.get(k)) or "off"
            sig = meta.get("mesh")
            mesh = "x".join(str(s) for s in sig) if sig else "off"
            ps.note_compile(
                group, bucket, variant, tuple(shape), lower,
                measured_variant=measured_variant,
                quant=str(meta.get("quant") or "off"),
                kernels=kernels, mesh=mesh)
        except Exception:
            pass

    def shape_census(self) -> Dict[str, list]:
        """Distinct (padded_batch, bucket) device shapes executed per
        batch group — the jit-cache-budget regression surface: a fused
        trunk stays ≤ |buckets|·log2(max_batch) shapes TOTAL regardless
        of member count."""
        with self._lock:
            return {k: sorted(v) for k, v in self._shapes.items()}

    def _encode_with(self, tokenizer: Tokenizer, max_seq_len: int,
                     text: str, enc_cache, tok_tag: str,
                     trunc_tags: Sequence[str]
                     ) -> tuple[Encoding, float, bool]:
        """Tokenize (or reuse the request's shared Encoding): the single
        tokenize-once seam.  ``tok_tag`` labels the tokenization counter
        (group id for shared group encodes — the work IS shared);
        ``trunc_tags`` labels truncation per member TASK, matching the
        traditional path's per-task attribution so existing dashboards
        keep reading.  Returns (encoding, seconds spent encoding,
        cache-hit) so batch tracing can attribute host tokenization per
        request; the same seconds go on the profiler's clock here
        (``engine.tokenize``; a cache hit's are the lookup)."""
        from ..observability import batchtrace

        t0 = time.perf_counter()
        missed = []
        if enc_cache is None:
            enc = tokenizer.encode(text, max_length=max_seq_len)
            self._count_tokenization(tok_tag)
            missed.append(True)
        else:
            def on_miss():
                missed.append(True)
                self._count_tokenization(tok_tag)

            enc = enc_cache.get_or_encode(tokenizer, text, max_seq_len,
                                          on_miss=on_miss)
        tok_s = time.perf_counter() - t0
        batchtrace.tokenized(tok_tag, tok_s, len(enc), cached=not missed)
        if enc.truncated:
            s = self._series()
            for tag in trunc_tags:
                s.truncated_inputs.inc(task=tag)
        return enc, tok_s, not missed

    def _encode_info(self, t: _Task, text: str, enc_cache=None
                     ) -> tuple[Encoding, float, bool]:
        return self._encode_with(t.tokenizer, t.max_seq_len, text,
                                 enc_cache, t.name, (t.name,))

    def _encode_group_info(self, g: TrunkGroup, tasks: Sequence[str],
                           text: str, enc_cache=None
                           ) -> tuple[Encoding, float, bool]:
        return self._encode_with(g.tokenizer, g.max_seq_len, text,
                                 enc_cache, g.gid, tuple(tasks))

    def _to_device(self, ids: np.ndarray, mask: np.ndarray, mesh=None):
        """Host batch → device, dp/sp-sharded when a mesh serves.
        ``mesh``: the fused runner's per-batch serving mesh (from its
        program-set snapshot — a hot mesh flip must not reshard a batch
        mid-flight); the legacy whole-engine mesh wins when set."""
        if self.mesh is not None:
            mesh = self.mesh
        if mesh is not None:
            from ..parallel import batch_sharding

            # device_put the HOST arrays directly: each device receives
            # only its shard (asarray-then-reshard would stage the full
            # batch on device 0 first — double transfer on the hot path)
            sh = batch_sharding(mesh,
                                shard_seq=mesh.shape.get("sp", 1) > 1)
            return jax.device_put(ids, sh), jax.device_put(mask, sh)
        return jnp.asarray(ids), jnp.asarray(mask)

    def _submit_texts(self, task: str, texts: Sequence[str],
                      enc_cache=None):
        t = self._require(task, kind="sequence")
        g = self._task_group.get(task)
        futures = []
        for text in texts:
            enc, tok_s, cached = self._encode_info(t, text, enc_cache)
            bucket = pick_bucket(len(enc), self.cfg.seq_len_buckets)
            if g is not None:
                # fused member: batch under the TRUNK, so concurrent
                # requests for sibling tasks coalesce into one forward
                futures.append(self.batcher.submit(
                    (TRUNK_KEY, g.gid, bucket),
                    _Payload(text, enc, tasks=(task,),
                             tok_s=tok_s, tok_cached=cached)))
            else:
                futures.append(self.batcher.submit(
                    (task, bucket),
                    _Payload(text, enc, tok_s=tok_s, tok_cached=cached)))
        return futures

    def _padded_batch(self, n: int, mesh=None) -> int:
        """Padded row count for ``n`` real rows.  ``mesh``: the fused
        runner's per-batch serving mesh — the row cap scales by dp
        (each shard serves up to max_batch_size rows) and the padded
        count divides evenly across the data axis."""
        cap = self.cfg.max_batch_size
        dp = 1
        if mesh is not None and self.mesh is None:
            dp = int(mesh.shape.get("dp", 1))
            cap *= dp
        elif self.mesh is not None:
            dp = int(self.mesh.shape.get("dp", 1))
        padded_n = pow2_batch(n, cap)
        if dp > 1:
            # dp-sharded batches must divide evenly across the data axis
            padded_n = max(dp, ((padded_n + dp - 1) // dp) * dp)
        return padded_n

    def _stack_items(self, items: List[BatchItem], bucket: int,
                     padded_n: int, pad_id: int,
                     tag: Optional[str] = None):
        """Pad item encodings into one (padded_n, bucket) host batch.
        Returns (ids, mask, clipped): an encoding longer than the bucket
        clips at the bucket edge — tagged per item (the result reports
        truncated=True) and counted, never silent (a task whose
        max_seq_len exceeds the largest bucket hits this).  ``tag`` None
        = the caller attributes the overflow count itself (the fused
        runner counts per member task)."""
        ids = np.full((padded_n, bucket), pad_id, dtype=np.int32)
        mask = np.zeros((padded_n, bucket), dtype=np.int32)
        clipped = [False] * len(items)
        for i, item in enumerate(items):
            enc: Encoding = item.payload.encoding
            L = min(len(enc), bucket)
            clipped[i] = len(enc) > bucket
            ids[i, :L] = enc.ids[:L]
            mask[i, :L] = enc.attention_mask[:L]
        n_clipped = sum(clipped)
        if n_clipped and tag is not None:
            self._series().bucket_overflows.inc(n_clipped, task=tag)
        return ids, mask, clipped

    def _run_batch(self, group_key: Hashable,
                   items: List[BatchItem]) -> Sequence[Any]:
        if group_key[0] == TRUNK_KEY:
            return self._run_fused_batch(group_key[1], group_key[2], items)
        if group_key[0] == GEN_KEY:
            return self._run_generative(*group_key[1:], items)
        task_name, bucket = group_key[0], group_key[1]
        t = self._require(task_name)
        n = len(items)
        padded_n = self._padded_batch(n)
        embedding = t.kind == "embedding"
        with EngineStep(
                self, items, scope="task", name=task_name, bucket=bucket,
                padded_rows=padded_n, kind=t.kind,
                flavour="embed" if embedding else task_name,
                variant="split",
                tokens_real=_tokens_real(items, bucket)) as step:
            with step.stage("stack"):
                ids, mask, clipped = self._stack_items(
                    items, bucket, padded_n, t.pad_id, task_name)
            with step.stage("h2d"):
                ids_dev, mask_dev = self._to_device(ids, mask)
            # a batch group of an embedding task holds ONE Matryoshka
            # variant (exit/dim are in its key): static arguments
            p = items[0].payload
            kwargs = {"exit_layer": p.exit_layer,
                      "output_dim": p.output_dim} if embedding else {}
            step.program(t.apply_fn, (t.params, ids_dev, mask_dev), kwargs)
            with step.stage("dispatch"):
                out = t.apply_fn(t.params, ids_dev, mask_dev, **kwargs)
            with step.stage("readback"):
                out = np.asarray(jax.device_get(out), dtype=np.float32)
            step.ran()
            now = time.perf_counter()
            with step.stage("demux"):
                if embedding:
                    return [out[i] for i in range(n)]
                results = []
                for i, item in enumerate(items):
                    enc = item.payload.encoding
                    latency = now - item.payload.submit_t
                    trunc = enc.truncated or clipped[i]
                    if t.kind == "sequence":
                        results.append(self._demux_seq(
                            task_name, _softmax(out[i]), latency, trunc))
                    else:
                        L = min(len(enc), bucket)
                        results.append(self._demux_tok(
                            task_name, _softmax(out[i, :L]), item, enc, L,
                            latency, trunc))
                return results

    def _run_generative(self, task_name: str, bucket: int,
                        max_new_tokens: int, task_index: int,
                        stop_strings: tuple,
                        items: List[BatchItem]) -> Sequence[Any]:
        """The fourth runner: a batch of prompts of one generative task
        and bucket through the generator in lock step, from prefill to the
        last token (request-level batching).  Every turn of the host in it (a
        forward, or a block generator's block of forwards) is one
        ``engine.step`` and one ``record_step`` sample
        (``_GenerationObserver``)."""
        gen = self._require(task_name, kind="generative").generator
        texts = [it.payload.text for it in items]
        settings = dict(max_new_tokens=max_new_tokens,
                        task_index=task_index, stop_strings=stop_strings)
        if not getattr(gen, "batched", False):  # a generator of prompts only
            return gen.generate(texts, **settings)
        padded_n = self._padded_batch(len(items))
        encodings = [it.payload.encoding for it in items]
        observer = _GenerationObserver(self, task_name, bucket, items,
                                       padded_n)
        done = False
        try:
            results = gen.generate(
                texts, **settings, encodings=encodings, bucket=bucket,
                padded_rows=padded_n, observer=observer)
            # prompts that generate() cut to the largest bucket
            n_cut = sum(enc.truncated for enc in encodings)
            if n_cut:
                self._series().bucket_overflows.inc(n_cut, task=task_name)
                for res, enc in zip(results, encodings):
                    res.truncated = enc.truncated
            done = True
        finally:
            observer.end(done)
        return results

    def _run_fused_batch(self, gid: str, bucket: int,
                         items: List[BatchItem]) -> Sequence[Any]:
        """One trunk forward for a batch MIXING member tasks — sequence
        and token heads alike: dedup identical encodings, decide packed
        vs unpacked composition (engine.packing), execute the matching
        fused program, then demux each item's (row/segment, task) logits
        against the task's own label set — decode semantics identical to
        the traditional path."""
        g = self._groups_by_gid[gid]
        # ONE consistent snapshot for this whole batch: g.fns carries
        # the programs, serving trunk params, meta, serving mesh AND
        # the demux view (banks + row maps + widths), swapped as a
        # single dict assignment — a concurrent re-registration or a
        # hot kernel/quant/MESH flip can never pair new row indices
        # with this batch's logits ordering, nor banks placed on one
        # mesh with programs built for another (a torn demux/fns pair
        # under a mesh flip would mix committed arrays from different
        # device sets and fail the batch)
        fns = g.fns
        demux = fns["demux"] if fns is not None else g.demux
        n = len(items)
        # identical token sequences within the batch ride a SINGLE
        # trunk row (the trunk output depends only on ids+mask; per-item
        # task mixes differ at demux, not at the forward).  Key on the
        # encoding bytes clipped at the bucket edge — the exact rows the
        # device would see.  K requests for the same hot prompt cost one
        # row instead of K.
        urow: List[int] = []
        uniq_items: List[BatchItem] = items
        if n > 1:
            uniq_items = []
            index: Dict[bytes, int] = {}
            for item in items:
                enc = item.payload.encoding
                L = min(len(enc), bucket)
                # the clip flag is part of the key: a 45-token item
                # clipped at a 32 bucket shares device rows with a
                # 32-token item, but their truncation/overflow
                # accounting must not cross-attribute
                key = (np.asarray(enc.ids[:L]).tobytes() + b"|"
                       + np.asarray(enc.attention_mask[:L]).tobytes()
                       + (b"|c" if len(enc) > bucket else b"|f"))
                at = index.get(key)
                if at is None:
                    at = index[key] = len(uniq_items)
                    uniq_items.append(item)
                urow.append(at)
        else:
            urow = list(range(n))
        n_rows = len(uniq_items)
        if n_rows < n:
            self._series().fused_dedup_rows.inc(n - n_rows)

        # which head banks this batch actually needs: a batch with no
        # token items never pays the per-token head matmul
        kinds = {self._tasks[t].kind for item in items
                 for t in item.payload.tasks if t in self._tasks}
        need_tok = "token" in kinds
        need_seq = "sequence" in kinds or not need_tok
        flavor = "both" if (need_tok and need_seq) \
            else ("tok" if need_tok else "seq")

        # packed vs unpacked composition (engine.packing): pack when the
        # plan strictly reduces padded device rows (or the continuous
        # scheduler over-took on the promise of packing); 1-unique-row
        # batches — including the fused-dedup hot-prompt case — stay on
        # the unpacked path bit-identically
        pk = self._packing
        packable = (pk["enabled"] and self.mesh is None
                    and fns is not None
                    and getattr(g.config, "attention_impl",
                                "dense") == "dense")
        # the serving mesh this batch pads/places/executes under comes
        # from its program-set snapshot, never live engine state — the
        # hot-flip atomicity contract (docs/PARALLEL.md)
        srv_mesh = fns.get("mesh") if fns is not None else None
        dp = int(srv_mesh.shape.get("dp", 1)) if srv_mesh is not None \
            else 1
        row_cap = self.cfg.max_batch_size * dp
        use_packed = False
        plan_rows = 0
        tuner = self._autotuner
        # the same per-group cap the scheduler's take planned with
        max_segs = self._packing_segment_cap_of((TRUNK_KEY, gid, bucket))
        if packable and n_rows >= pk["min_segments"]:
            blocked = tuner is not None and \
                tuner.blocked(f"trunk:{gid}", bucket)
            must_pack = n_rows > row_cap
            if must_pack or not blocked:
                plan = RowPlan(bucket, row_cap, max_segs)
                fits = all(
                    plan.add(min(len(it.payload.encoding), bucket))
                    is not None for it in uniq_items)
                if fits:
                    packed_padded = self._padded_batch(plan.rows_used,
                                                       mesh=srv_mesh)
                    unpacked_padded = self._padded_batch(
                        min(n_rows, row_cap), mesh=srv_mesh)
                    if must_pack or packed_padded < unpacked_padded:
                        use_packed = True
                        plan_rows = plan.rows_used
        if not use_packed and n_rows > row_cap:
            # the scheduler over-took but the plan no longer fits (a
            # hot-reload raced the knobs down): serve in halves —
            # correctness over one perfect step
            mid = max(1, n // 2)
            return (list(self._run_fused_batch(gid, bucket, items[:mid]))
                    + list(self._run_fused_batch(gid, bucket,
                                                 items[mid:])))
        comp = self._packed_rows(g, bucket, uniq_items, flavor, srv_mesh,
                                 row_cap, max_segs, plan_rows) \
            if use_packed \
            else self._fixed_rows(g, bucket, uniq_items, flavor, srv_mesh)
        return self._run_fused(gid, bucket, items, urow, uniq_items,
                               demux, fns, flavor, comp)

    def _bgmv_pairs(self, items: List[BatchItem], urow: List[int],
                    demux: dict):
        """(pair_rows, pair_tasks, pair_index) for the BGMV gather path
        (docs/KERNELS.md): one pair per distinct (trunk row, bank row) a
        sequence task in this batch needs — deduped items share pairs
        exactly like they share trunk rows.  The pair axis pads to a
        power of two (dummy pairs compute row 0 × task 0 and demux to
        nothing) so it joins the closed static-shape set."""
        pair_index: Dict[tuple, int] = {}
        for i, item in enumerate(items):
            for task in item.payload.tasks:
                t = self._tasks.get(task)
                if t is None or t.kind == "token":
                    continue
                key = (urow[i], demux["row_of"][task])
                if key not in pair_index:
                    pair_index[key] = len(pair_index)
        n = max(1, len(pair_index))
        p_pad = 1 << (n - 1).bit_length()
        pr = np.zeros(p_pad, np.int32)
        pt = np.zeros(p_pad, np.int32)
        for (u, row), p in pair_index.items():
            pr[p] = u
            pt[p] = row
        return pr, pt, pair_index

    @staticmethod
    def _kernel_labels(meta: dict, used_bgmv: bool) -> List[str]:
        """The tuned-kernel paths a fused step ran through (the
        ``kernel`` labels of llm_engine_kernel_steps_total)."""
        labels = []
        if meta["quant"] != "off":
            labels.append(f"quant_{meta['quant']}")
        if meta["epilogue"]:
            labels.append("epilogue")
        if used_bgmv:
            labels.append("bgmv")
        return labels

    # -- decoding an item's answer -----------------------------------------

    def _demux_seq(self, task: str, p: np.ndarray, latency_s: float,
                   truncated: bool) -> ClassResult:
        """Decode one item's sequence logits (already softmaxed over the
        task's true width) with ITS label set — identical semantics to
        the traditional path's width-tolerant decode."""
        idx = int(p.argmax())
        labels = self._tasks[task].labels
        return ClassResult(
            label=labels[idx] if idx < len(labels) else str(idx),
            index=idx,
            confidence=float(p[idx]),
            probs={(labels[j] if j < len(labels) else str(j)):
                   float(p[j]) for j in range(p.shape[-1])},
            latency_s=latency_s,
            truncated=truncated,
        )

    def _demux_tok(self, task: str, tok_probs: np.ndarray, item,
                   enc: Encoding, L: int, latency_s: float,
                   truncated: bool) -> TokenClassResult:
        """Decode one item's per-token logits → entity spans with exact
        char offsets, same contract as the traditional token branch."""
        t = self._tasks[task]
        pred = tok_probs.argmax(-1)
        labels = [t.labels[j] if j < len(t.labels) else str(j)
                  for j in pred]
        scores = [float(tok_probs[k, j]) for k, j in enumerate(pred)]
        spans = decode_entity_spans(
            item.payload.text, enc.offsets[:L], labels, scores,
            threshold=item.payload.threshold)
        return TokenClassResult(
            entities=[EntitySpan(**s) for s in spans],
            latency_s=latency_s,
            truncated=truncated,
        )

    # -- the fused runner and its two compositions --------------------------

    def _fixed_rows(self, g: TrunkGroup, bucket: int,
                    uniq_items: List[BatchItem], flavor: str, srv_mesh
                    ) -> _Composition:
        """One trunk row per unique encoding, padded to the bucket edge."""
        padded_rows = self._padded_batch(len(uniq_items), mesh=srv_mesh)
        # sharding-aware compile variants key on the mesh shape: the
        # sharded and single-device programs are distinct XLA programs
        # with their own compile/EWMA accounting
        variant = "fused_mesh" if srv_mesh is not None else "fused"

        def stack() -> _Layout:
            ids, mask, clipped = self._stack_items(uniq_items, bucket,
                                                   padded_rows, g.pad_id)
            return _Layout(ids, mask, spans=[
                (u, slice(0, min(len(it.payload.encoding), bucket)),
                 clipped[u]) for u, it in enumerate(uniq_items)])

        return _Composition(False, variant, f"{variant}:{flavor}",
                            len(uniq_items), padded_rows, stack)

    def _packed_rows(self, g: TrunkGroup, bucket: int,
                     uniq_items: List[BatchItem], flavor: str, srv_mesh,
                     row_cap: int, max_segs: int, plan_rows: int
                     ) -> _Composition:
        """The sequence-packed composition (docs/PACKING.md): unique
        encodings bin-pack into shared rows under a block-diagonal
        attention mask with per-segment RoPE positions; sequence heads
        pool PER SEGMENT, token heads read each segment's span of the
        per-token logits.  Logit parity with fixed rows is the golden
        gate (tests/test_packing.py, ≤1e-4)."""
        n_segs = len(uniq_items)
        padded_rows = self._padded_batch(plan_rows, mesh=srv_mesh)
        # the segment axis pads to a power of two — K_pad joins the
        # closed static-shape set like the row axis does, and is part of
        # the census key: a fresh K over a warm row shape is a compile
        k_pad = 1 << max(0, n_segs - 1).bit_length()

        def stack() -> _Layout:
            pb = pack_items(
                [it.payload.encoding for it in uniq_items], bucket,
                g.pad_id, max_rows=row_cap,
                max_segments_per_row=max_segs,
                pad_rows_to=padded_rows, pad_segments_to=k_pad)
            return _Layout(
                pb.ids, pb.mask,
                row_planes=(pb.position_ids, pb.segment_ids),
                seg_maps=(pb.seg_row, pb.seg_start),
                spans=[(sg.row, slice(sg.start, sg.start + sg.length),
                        sg.clipped) for sg in pb.segments])

        # "packed_mesh" when dp-sharded: the auto-tuner reads only the
        # single-device series by design
        return _Composition(
            True, "packed_mesh" if srv_mesh is not None else "packed",
            f"packed:{flavor}:{k_pad}", plan_rows, padded_rows, stack,
            # HOW packed this step ran, beside a traced step's batch
            # size and fill attributes
            span_attrs={
                "packing.packed": True, "packing.segments": n_segs,
                "packing.rows": plan_rows,
                "packing.token_fill": round(
                    _tokens_real(uniq_items, bucket)
                    / max(1, padded_rows * bucket), 4)})

    @staticmethod
    def _fused_program(fns: dict, demux: dict, flavor: str, packed: bool,
                       ids_dev, mask_dev, row_planes: tuple,
                       seg_maps: tuple, pairs: tuple):
        """``(fn, args)`` of a fused step: the program of the flavour and
        composition out of the batch's snapshot, the banks it applies, the
        batch, a packed batch's planes, and — for the sequence heads only
        — its per-segment pooling maps and the BGMV pairs."""
        banks = {"seq": ("bank",), "tok": ("tok_bank",),
                 "both": ("bank", "tok_bank")}[flavor]
        args = (fns["trunk_params"], *(demux[b] for b in banks),
                ids_dev, mask_dev, *row_planes)
        if flavor != "tok":
            args += (*seg_maps, *pairs)
        return fns["packed_" + flavor if packed else flavor], args

    def _run_fused(self, gid: str, bucket: int, items: List[BatchItem],
                   urow: List[int], uniq_items: List[BatchItem],
                   demux: dict, fns: dict, flavor: str,
                   comp: _Composition) -> Sequence[Any]:
        """One fused step: the composition's batch through the flavour's
        program, then each item's (row or segment, task) logits against
        the task's own label set."""
        srv_mesh = fns.get("mesh")
        meta = fns["meta"]
        use_bgmv = meta["bgmv"] and flavor in ("seq", "both")
        pr = pt = pair_index = None
        pair_sfx = ""
        if use_bgmv:
            # deduped items share pairs as they share trunk rows; packed
            # pairs index SEGMENTS (the packed pool emits one pooled row
            # a segment, and urow is the segment index).  The padded pair
            # count is its own static program dimension
            pr, pt, pair_index = self._bgmv_pairs(items, urow, demux)
            pair_sfx = f":p{pr.shape[0]}"
        with EngineStep(
                self, items, scope="trunk", name=gid, bucket=bucket,
                padded_rows=comp.padded_rows, kind="fused",
                flavour=flavor, variant=comp.variant,
                census=f"{comp.census}{pair_sfx}"
                       f"{mesh_suffix(meta.get('mesh'))}",
                rows=comp.rows,
                tokens_real=_tokens_real(uniq_items, bucket),
                tokens_padded=comp.padded_rows * bucket,
                segments=len(uniq_items), meta=meta, packed=comp.packed,
                mesh=srv_mesh is not None,
                kernels=self._kernel_labels(meta, use_bgmv),
                span_attrs=comp.span_attrs) as step:
            with step.stage("stack"):
                lay = comp.stack()
                for i, item in enumerate(items):
                    if lay.spans[urow[i]][2]:
                        for task in item.payload.tasks:
                            self._series().bucket_overflows.inc(task=task)
            with step.stage("h2d"):
                ids_dev, mask_dev = self._to_device(lay.ids, lay.mask,
                                                    mesh=srv_mesh)
                if srv_mesh is not None and comp.packed:
                    # position/segment planes shard with their rows so
                    # each dp shard masks/pools ITS row slice; the
                    # per-segment maps ([K] gathers) replicate — XLA
                    # inserts the gather collectives
                    from ..parallel import batch_sharding, replicated

                    row_sh = batch_sharding(srv_mesh)
                    rep = replicated(srv_mesh)
                    row_planes = tuple(jax.device_put(x, row_sh)
                                       for x in lay.row_planes)
                    seg_maps = tuple(jax.device_put(x, rep)
                                     for x in lay.seg_maps)
                else:
                    row_planes = tuple(map(jnp.asarray, lay.row_planes))
                    seg_maps = tuple(map(jnp.asarray, lay.seg_maps))
                pairs = (jnp.asarray(pr), jnp.asarray(pt)) if use_bgmv \
                    else ()
            fn, args = self._fused_program(
                fns, demux, flavor, comp.packed, ids_dev, mask_dev,
                row_planes, seg_maps, pairs)
            step.program(fn, args)
            with step.stage("dispatch"):
                res = fn(*args)
            # (sequence logits, token logits), None for the bank the
            # flavour does not run
            seq_logits, tok_logits = (res, None) if flavor == "seq" else \
                (None, res) if flavor == "tok" else res
            with step.stage("readback"):
                if seq_logits is not None:
                    seq_logits = np.asarray(jax.device_get(seq_logits),
                                            dtype=np.float32)
                if tok_logits is not None:
                    tok_logits = np.asarray(jax.device_get(tok_logits),
                                            dtype=np.float32)
            step.ran()

            now = time.perf_counter()
            out: List[Any] = []
            with step.stage("demux"):
                for i, item in enumerate(items):
                    enc = item.payload.encoding
                    u = urow[i]
                    row, span, clipped = lay.spans[u]
                    latency = now - item.payload.submit_t
                    trunc = enc.truncated or clipped
                    per_task: Dict[str, Any] = {}
                    for task in item.payload.tasks:
                        if self._tasks[task].kind == "token":
                            b = demux["tok_row_of"][task]
                            width = demux["tok_widths"][b]
                            per_task[task] = self._demux_tok(
                                task,
                                _softmax(tok_logits[row, span, b, :width]),
                                item, enc, span.stop - span.start,
                                latency, trunc)
                        else:
                            b = demux["row_of"][task]
                            width = demux["widths"][b]
                            # a shared trunk row's logits fan out to
                            # every duplicate item here; the BGMV path
                            # demuxes by PAIR instead of (row, task) —
                            # same logits, gathered on device
                            src = seq_logits[pair_index[(u, b)], :width] \
                                if use_bgmv else seq_logits[u, b, :width]
                            per_task[task] = self._demux_seq(
                                task, _softmax(src), latency, trunc)
                    # one task: its result; several (classify_multi's
                    # one item a text): {task: result}
                    tasks = item.payload.tasks
                    out.append(per_task[tasks[0]] if len(tasks) == 1
                               else per_task)
            return out


def _tokens_real(items: Sequence[BatchItem], bucket: int) -> int:
    """Unpadded tokens a step carries: each item's encoding, clipped to
    the bucket."""
    return sum(min(len(it.payload.encoding), bucket) for it in items)


def _softmax(x: np.ndarray) -> np.ndarray:
    x = x - x.max(axis=-1, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=-1, keepdims=True)
