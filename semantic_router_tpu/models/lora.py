"""Stacked multi-task LoRA for the classifier bank.

TPU-first re-design of the reference's LoRA path (N4:
candle-binding/src/model_architectures/lora/ adapter load/merge,
classifiers/lora/parallel_engine.rs multi-task intent+PII+security in one
batched pass; memory win documented at paper evaluation.tex:127-140 —
6 tasks: 3,438 MB independent models → 575 MB base+adapters).

Design: instead of the reference's per-task adapter objects dispatched by a
Rust engine, adapters live as ONE stacked parameter tree with a leading task
axis ``[T, ...]``. A single jit forward vmaps the trunk over the task axis —
every task's adapted forward runs in the same XLA program (MXU-friendly: the
base projection is computed once per task via batched matmuls; adapter
deltas are two skinny matmuls fused by XLA). Adding a task = concatenating
along axis 0; selecting tasks = indexing — no recompilation beyond the new
T. This is the natural TPU shape of "runtime adapter hot-swap"
(qwen3_multi_lora_classifier.rs, FFI LoadQwen3LoRAAdapter
semantic-router.go:3603).

``LoRADense`` augments a frozen base kernel with ``scale · (x A) B``; with a
task axis the module computes all tasks' outputs in one call.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .modernbert import (
    ModernBertConfig,
    ModernBertForSequenceClassification,
    ModernBertModel,
    ModernBertPredictionHead,
)
from ..ops.attention import cls_pool, mean_pool
from ..ops.matryoshka import truncate_normalize


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int = 8
    alpha: float = 16.0
    num_tasks: int = 1
    # which projections get adapters (the reference adapts attention + MLP)
    adapt_attention: bool = True
    adapt_mlp: bool = True

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


class LoRADelta(nn.Module):
    """Task-stacked low-rank delta: x[T?, B, S, D] → delta[T, B, S, out].

    Parameters: A [T, D, r], B [T, r, out]. When the input has no task axis
    the same x feeds every task (the multi-task single-pass case)."""

    features: int
    cfg: LoRAConfig
    name_suffix: str = ""

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        T, r = self.cfg.num_tasks, self.cfg.rank
        d = x.shape[-1]
        A = self.param(f"lora_A{self.name_suffix}",
                       nn.initializers.normal(stddev=0.02), (T, d, r))
        B = self.param(f"lora_B{self.name_suffix}",
                       nn.initializers.zeros, (T, r, self.features))
        if x.ndim == 4 and x.shape[0] == T:  # already task-stacked
            h = jnp.einsum("tbsd,tdr->tbsr", x, A)
        else:
            h = jnp.einsum("bsd,tdr->tbsr", x, A)
        return self.cfg.scale * jnp.einsum("tbsr,tro->tbso", h, B)


def merge_lora_into_base(base_kernel: np.ndarray, lora_A: np.ndarray,
                         lora_B: np.ndarray, scale: float) -> np.ndarray:
    """Merge one task's adapter into a dense kernel (the reference's
    "merged" deployment path, lora/lora_adapter.rs merge)."""
    return base_kernel + scale * (lora_A @ lora_B)


class ModernBertLoRAHeadClassifier(nn.Module):
    """Single-task classifier with a LoRA-adapted prediction head: frozen
    shared trunk + (dense + scale·(x A)B) → act → norm → classifier.

    This is the per-task *unit* of the fused classifier bank
    (engine.classify TrunkGroup): tasks registered with the same trunk
    parameter arrays share ONE trunk forward; each task's head — including
    this module's LoRA delta — stacks into the bank via
    ``head_bank_entry``/``stack_head_bank`` and fans out as one batched
    matmul.  Standalone ``apply`` computes the same head math (same
    dtype, within XLA reduction-order rounding) the fused path
    reproduces, so either execution path serves the task."""

    config: ModernBertConfig
    lora: LoRAConfig
    num_labels: int

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        from .modernbert import activation

        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        hidden = ModernBertModel(cfg, name="model")(input_ids, attention_mask)
        pooled = (mean_pool(hidden, attention_mask)
                  if cfg.classifier_pooling == "mean" else cls_pool(hidden))
        h = nn.Dense(cfg.hidden_size, use_bias=cfg.classifier_bias,
                     name="head_dense", dtype=cfg.dtype)(pooled)
        A = self.param("lora_A", nn.initializers.normal(stddev=0.02),
                       (pooled.shape[-1], self.lora.rank))
        B = self.param("lora_B", nn.initializers.zeros,
                       (self.lora.rank, cfg.hidden_size))
        h = h + self.lora.scale * ((pooled @ A) @ B)
        h = activation(cfg.classifier_activation)(h)
        h = nn.LayerNorm(epsilon=cfg.norm_eps, use_bias=cfg.norm_bias,
                         name="head_norm", dtype=cfg.dtype)(h)
        return nn.Dense(self.num_labels, use_bias=True, name="classifier",
                        dtype=cfg.dtype)(h)


def head_bank_entry(module, params) -> Optional[Dict[str, Any]]:
    """Extract the stackable prediction head of a bank-fusable classifier.

    Returns host-side arrays {dense_kernel, dense_bias?, lora_A?, lora_B?,
    scale, norm_scale, norm_bias?, cls_kernel, cls_bias, kind}, or None
    when the module is not fusable (unknown architecture) — the engine
    then keeps the task on its traditional per-task path.  ``kind``
    ("sequence" | "token") tells the engine which bank the head stacks
    into: token heads (PII / hallucination spans) run the same head math
    per TOKEN instead of per pooled row, sharing the trunk forward with
    their sequence siblings (docs/FUSED_BANK.md)."""
    from .modernbert import ModernBertForTokenClassification

    p = params.get("params", params)
    try:
        if isinstance(module, ModernBertLoRAHeadClassifier):
            return {
                "dense_kernel": p["head_dense"]["kernel"],
                "dense_bias": p["head_dense"].get("bias"),
                "lora_A": p["lora_A"],
                "lora_B": p["lora_B"],
                "scale": float(module.lora.scale),
                "norm_scale": p["head_norm"]["scale"],
                "norm_bias": p["head_norm"].get("bias"),
                "cls_kernel": p["classifier"]["kernel"],
                "cls_bias": p["classifier"]["bias"],
                "kind": "sequence",
            }
        if isinstance(module, (ModernBertForSequenceClassification,
                               ModernBertForTokenClassification)):
            head, cls = p["head"], p["classifier"]
            return {
                "dense_kernel": head["dense"]["kernel"],
                "dense_bias": head["dense"].get("bias"),
                "lora_A": None,
                "lora_B": None,
                "scale": 0.0,
                "norm_scale": head["norm"]["scale"],
                "norm_bias": head["norm"].get("bias"),
                "cls_kernel": cls["kernel"],
                "cls_bias": cls["bias"],
                "kind": "token"
                if isinstance(module, ModernBertForTokenClassification)
                else "sequence",
            }
    except (KeyError, TypeError):
        return None
    return None


def stack_head_bank(entries: List[Dict[str, Any]]) -> Dict[str, jnp.ndarray]:
    """Stack per-task head entries into one gatherable bank of [T, ...]
    arrays.  Label columns zero-pad to the widest member (padded logits
    are sliced away before softmax); LoRA ranks zero-pad to the widest
    adapter, and non-LoRA members get all-zero A/B rows — an exact no-op
    delta, which is how LoRA and non-LoRA tasks share one batch.

    The bank keeps the members' own dtype (bf16 heads stay bf16): the
    fused path must reproduce the standalone modules' numerics, not
    silently upcast them."""
    D, H = np.shape(entries[0]["dense_kernel"])
    dt = np.asarray(entries[0]["dense_kernel"]).dtype
    l_max = max(int(np.shape(e["cls_kernel"])[1]) for e in entries)
    r_max = max([int(np.shape(e["lora_A"])[1])
                 for e in entries if e["lora_A"] is not None] or [1])

    def stacked(key, pad_to=None, axis=None):
        rows = []
        for e in entries:
            a = np.asarray(e[key], dtype=dt)
            if pad_to is not None and a.shape[axis] < pad_to:
                widths = [(0, 0)] * a.ndim
                widths[axis] = (0, pad_to - a.shape[axis])
                a = np.pad(a, widths)
            rows.append(a)
        return np.stack(rows)

    bank: Dict[str, Any] = {
        "dense_kernel": stacked("dense_kernel"),             # [T, D, H]
        "norm_scale": stacked("norm_scale"),                 # [T, H]
        "cls_kernel": stacked("cls_kernel", l_max, 1),       # [T, H, L]
        "cls_bias": stacked("cls_bias", l_max, 0),           # [T, L]
        "scale": np.asarray([e["scale"] for e in entries], dt),
    }
    if entries[0]["dense_bias"] is not None:
        bank["dense_bias"] = stacked("dense_bias")           # [T, H]
    if entries[0]["norm_bias"] is not None:
        bank["norm_bias"] = stacked("norm_bias")             # [T, H]
    if any(e["lora_A"] is not None for e in entries):
        bank["lora_A"] = np.stack([
            np.pad(np.asarray(e["lora_A"], dt),
                   ((0, 0), (0, r_max - e["lora_A"].shape[1])))
            if e["lora_A"] is not None else np.zeros((D, r_max), dt)
            for e in entries])                               # [T, D, r]
        bank["lora_B"] = np.stack([
            np.pad(np.asarray(e["lora_B"], dt),
                   ((0, r_max - e["lora_B"].shape[0]), (0, 0)))
            if e["lora_B"] is not None else np.zeros((r_max, H), dt)
            for e in entries])                               # [T, r, H]
    return bank


def apply_head_bank(bank: Dict[str, jnp.ndarray], pooled: jnp.ndarray,
                    activation, norm_eps: float,
                    epilogue: bool = False) -> jnp.ndarray:
    """Fan pooled trunk features [B, D] out through EVERY stacked head as
    batched einsums → logits [B, T, L_max].

    At classifier-bank task counts (~18 heads over one ModernBERT trunk)
    computing all heads for all rows is cheaper than a per-item gather —
    head FLOPs are ~0.1% of the trunk's — and keeps the jit cache keyed on
    (batch, seq) only.  The engine demultiplexes each item's (row, task)
    logits host-side and softmaxes over the task's true label width; for
    much wider banks ``apply_head_bank_bgmv`` below gathers per item
    instead (engine.kernels.bgmv, docs/KERNELS.md).

    ``epilogue=True`` routes the dense+bias+activation through the fused
    Pallas epilogue kernel (ops.epilogue — one MXU dispatch instead of
    matmul + bias-add + activation; the LoRA delta's skinny matmuls stay
    XLA einsums feeding the kernel).  Parity with the einsum path is
    ≤1e-4 (tests/test_kernels.py)."""
    if epilogue:
        from ..ops.epilogue import head_epilogue

        delta = None
        if "lora_A" in bank:
            low = jnp.einsum("bd,tdr->btr", pooled, bank["lora_A"])
            delta = bank["scale"][None, :, None] * jnp.einsum(
                "btr,trh->bth", low, bank["lora_B"])
        h = head_epilogue(pooled, bank["dense_kernel"],
                          bank.get("dense_bias"), delta, activation)
    else:
        h = jnp.einsum("bd,tdh->bth", pooled, bank["dense_kernel"])
        if "dense_bias" in bank:
            h = h + bank["dense_bias"][None]
        if "lora_A" in bank:
            low = jnp.einsum("bd,tdr->btr", pooled, bank["lora_A"])
            h = h + bank["scale"][None, :, None] * jnp.einsum(
                "btr,trh->bth", low, bank["lora_B"])
        h = activation(h)
    mu = h.mean(axis=-1, keepdims=True)
    var = ((h - mu) ** 2).mean(axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + norm_eps)
    h = h * bank["norm_scale"][None]
    if "norm_bias" in bank:
        h = h + bank["norm_bias"][None]
    return jnp.einsum("bth,thl->btl", h, bank["cls_kernel"]) \
        + bank["cls_bias"][None]


def apply_head_bank_bgmv(bank: Dict[str, jnp.ndarray],
                         pooled: jnp.ndarray,
                         pair_rows: jnp.ndarray,
                         pair_tasks: jnp.ndarray,
                         activation, norm_eps: float) -> jnp.ndarray:
    """Per-item gathered head application (the BGMV serving shape,
    docs/KERNELS.md): each (row, task) PAIR computes only ITS task's
    head — pooled [N, D] × pairs [P] → logits [P, L_max].  Work scales
    with pairs, not rows × tasks, which is what stops wide banks paying
    the zero-padded all-heads matmul.

    The two full-width matmuls (head dense, classifier) ride the Pallas
    BGMV gather kernel on TPU (ops.bgmv; XLA take+einsum elsewhere);
    the rank-r LoRA matmuls stay XLA einsums (skinny lanes tile poorly
    on the MXU).  Numerics: same math as ``apply_head_bank`` restricted
    to the requested pairs — parity ≤1e-4 is the gate
    (tests/test_kernels.py, packed + deduped batches included)."""
    from ..ops.bgmv import bgmv

    x = jnp.take(pooled, pair_rows, axis=0)             # [P, D]
    h = bgmv(x, bank["dense_kernel"], pair_tasks)       # [P, H]
    if "dense_bias" in bank:
        h = h + jnp.take(bank["dense_bias"], pair_tasks, axis=0)
    if "lora_A" in bank:
        low = jnp.einsum("pd,pdr->pr", x,
                         jnp.take(bank["lora_A"], pair_tasks, axis=0))
        h = h + jnp.take(bank["scale"], pair_tasks)[:, None] \
            * jnp.einsum("pr,prh->ph", low,
                         jnp.take(bank["lora_B"], pair_tasks, axis=0))
    h = activation(h)
    mu = h.mean(axis=-1, keepdims=True)
    var = ((h - mu) ** 2).mean(axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + norm_eps)
    h = h * jnp.take(bank["norm_scale"], pair_tasks, axis=0)
    if "norm_bias" in bank:
        h = h + jnp.take(bank["norm_bias"], pair_tasks, axis=0)
    return bgmv(h, bank["cls_kernel"], pair_tasks) \
        + jnp.take(bank["cls_bias"], pair_tasks, axis=0)


class MultiTaskLoRAClassifier(nn.Module):
    """Shared frozen ModernBERT trunk + per-task LoRA'd prediction heads.

    The parallel multi-task engine shape: ONE forward evaluates every task
    (intent, jailbreak/security, PII…) on the same batch. Trunk runs once
    (frozen, task-independent); per-task adaptation lives in the pooled
    head: pooled[B, D] → per-task LoRA-adapted dense head → logits list.

    Heads may have different label counts, so logits return as a dict
    {task_name: [B, n_labels]}. Token-level tasks get per-token logits.

    This is deliberately a *head-adapted* bank (trunk shared exactly) — the
    highest-throughput layout on TPU: trunk FLOPs are paid once regardless
    of task count, matching the reference's observed memory/latency win for
    the LoRA path, and the full trunk-adapted variant is available via
    ``LoRAModernBertModel`` below when per-task trunk deltas are required.
    """

    config: ModernBertConfig
    lora: LoRAConfig
    task_names: List[str] = dataclasses.field(default_factory=list)
    task_labels: Dict[str, int] = dataclasses.field(default_factory=dict)
    task_kinds: Dict[str, str] = dataclasses.field(default_factory=dict)

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None
                 ) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        hidden = ModernBertModel(cfg, name="model")(input_ids, attention_mask)
        pooled = (mean_pool(hidden, attention_mask)
                  if cfg.classifier_pooling == "mean" else cls_pool(hidden))

        # Shared head dense with task-stacked LoRA delta. Base projection
        # and ALL tasks' deltas are computed exactly once per feature kind
        # (pooled / per-token) — the per-task loop only indexes.
        base = nn.Dense(cfg.hidden_size, use_bias=cfg.classifier_bias,
                        name="head_dense", dtype=cfg.dtype)
        delta = LoRADelta(cfg.hidden_size, self.lora, name="head_lora")

        kinds = {self.task_kinds.get(t, "sequence") for t in self.task_names}
        feats_by_kind: Dict[str, jnp.ndarray] = {}
        if "sequence" in kinds:
            xp = pooled[:, None, :]
            feats_by_kind["sequence"] = base(xp) + delta(xp)  # [T?,B,1,D]
        if "token" in kinds:
            feats_by_kind["token"] = base(hidden) + delta(hidden)

        out: Dict[str, jnp.ndarray] = {}
        for ti, task in enumerate(self.task_names):
            kind = self.task_kinds.get(task, "sequence")
            h = feats_by_kind[kind][ti]
            h = jax.nn.gelu(h, approximate=False)
            h = nn.LayerNorm(epsilon=cfg.norm_eps, use_bias=cfg.norm_bias,
                             name=f"head_norm_{task}", dtype=cfg.dtype)(h)
            logits = nn.Dense(self.task_labels[task], use_bias=True,
                              name=f"classifier_{task}", dtype=cfg.dtype)(h)
            out[task] = logits[:, 0, :] if kind == "sequence" else logits
        return out


class LoRADense(nn.Module):
    """Dense layer with a task-stacked LoRA delta, selecting ONE task per
    call via an integer index (trunk-adapted path). The base kernel is the
    pretrained weight; ``task_index`` picks the adapter pair — a gather, so
    switching adapters never recompiles."""

    features: int
    cfg: LoRAConfig
    use_bias: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, task_index: jnp.ndarray) -> jnp.ndarray:
        d = x.shape[-1]
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (d, self.features))
        y = x @ kernel
        if self.use_bias:
            y = y + self.param("bias", nn.initializers.zeros,
                               (self.features,))
        A = self.param("lora_A", nn.initializers.normal(stddev=0.02),
                       (self.cfg.num_tasks, d, self.cfg.rank))
        B = self.param("lora_B", nn.initializers.zeros,
                       (self.cfg.num_tasks, self.cfg.rank, self.features))
        Ai = jnp.take(A, task_index, axis=0)  # [d, r]
        Bi = jnp.take(B, task_index, axis=0)  # [r, out]
        return y + self.cfg.scale * ((x @ Ai) @ Bi)


class LoRAModernBertForSequenceClassification(nn.Module):
    """Trunk-adapted LoRA classifier: every attention/MLP projection carries
    a task-stacked adapter selected by ``task_index`` at call time (BERT+LoRA
    classifier parity, lora/bert_lora.rs:867). One set of base weights, T
    adapters, O(1) switch cost.

    The trunk IS ``ModernBertModel`` (same YaRN rope, chunked-attention
    support, activation config, and param tree — pretrained base weights
    convert with modernbert_params_from_state_dict unchanged); the LoRA
    adaptation threads in via the trunk's ``dense_factory`` seam."""

    config: ModernBertConfig
    lora: LoRAConfig
    num_labels: int

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None,
                 task_index: jnp.ndarray | int = 0) -> jnp.ndarray:
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        lora_cfg = self.lora

        def dense_factory(features: int, use_bias: bool, name: str):
            return LoRADense(features, lora_cfg, use_bias=use_bias, name=name)

        hidden = ModernBertModel(cfg, name="model",
                                 dense_factory=dense_factory)(
            input_ids, attention_mask, task_index=jnp.asarray(task_index))
        pooled = (mean_pool(hidden, attention_mask)
                  if cfg.classifier_pooling == "mean" else cls_pool(hidden))
        pooled = ModernBertPredictionHead(cfg, name="head")(pooled)
        return nn.Dense(self.num_labels, name="classifier",
                        dtype=cfg.dtype)(pooled)


class LoRAModernBertForTokenClassification(nn.Module):
    """Token-level sibling of the LoRA sequence classifier (the PII /
    hallucination-span training shape): same adapted trunk, per-token
    head → [B, S, num_labels]."""

    config: ModernBertConfig
    lora: LoRAConfig
    num_labels: int

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None,
                 task_index: jnp.ndarray | int = 0) -> jnp.ndarray:
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        lora_cfg = self.lora

        def dense_factory(features: int, use_bias: bool, name: str):
            return LoRADense(features, lora_cfg, use_bias=use_bias,
                             name=name)

        hidden = ModernBertModel(cfg, name="model",
                                 dense_factory=dense_factory)(
            input_ids, attention_mask, task_index=jnp.asarray(task_index))
        hidden = ModernBertPredictionHead(cfg, name="head")(hidden)
        return nn.Dense(self.num_labels, use_bias=True, name="classifier",
                        dtype=cfg.dtype)(hidden)


class LoRAMmBertEmbeddingModel(nn.Module):
    """LoRA-adapted embedding trunk (cache/domain embedding fine-tunes,
    reference src/training/model_embeddings/cache_embeddings/lora_trainer.py
    role): every trunk projection carries a task-stacked adapter; pool →
    L2-normalize like MmBertEmbeddingModel. Base weights stay frozen under
    ``lora_param_filter``; the trained artifact is just the adapter stack."""

    config: ModernBertConfig
    lora: LoRAConfig
    pooling: str = "mean"

    @nn.compact
    def __call__(self, input_ids: jnp.ndarray,
                 attention_mask: Optional[jnp.ndarray] = None,
                 task_index: jnp.ndarray | int = 0) -> jnp.ndarray:
        cfg = self.config
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        lora_cfg = self.lora

        def dense_factory(features: int, use_bias: bool, name: str):
            return LoRADense(features, lora_cfg, use_bias=use_bias,
                             name=name)

        hidden = ModernBertModel(cfg, name="model",
                                 dense_factory=dense_factory)(
            input_ids, attention_mask, task_index=jnp.asarray(task_index))
        pooled = (cls_pool(hidden) if self.pooling == "cls"
                  else mean_pool(hidden, attention_mask))
        return truncate_normalize(pooled, None).astype(cfg.dtype)


def lora_param_filter(path: tuple, _leaf) -> bool:
    """optax trainable-param predicate: True for adapter params only (the
    fine-tune recipe freezes the base; scripts/train-mmbert32k-gpu.sh
    trains rank-32/α64 adapters)."""
    return any(isinstance(p, str) and p.startswith("lora_") for p in path)
