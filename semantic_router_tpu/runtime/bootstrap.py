"""Runtime bootstrap: assemble and launch the full router.

Parity with the reference's startup sequence (cmd/main.go:18 →
runtime_bootstrap.go, SURVEY.md §3.1): load config → start status tracking
early → initialize the TPU engine (classifier tasks from config) → build
the router (+cache, vectorstores, memory, replay) → warm up → start the
server with config hot-reload (file watch → rebuild → atomic swap,
server_config_watch.go + RouterService.Swap).

Model loading: checkpoint paths in cfg.classifier_models map task name →
{checkpoint, tokenizer, kind, labels}; absent checkpoints leave the task
unloaded (signals fail open) — the model-free mock seam is
``--mock-models`` which installs the tiny random test engine.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Optional

from ..config import ConfigWatcher, RouterConfig, load_config, replace
from ..observability.logging import component_event
from ..replay import ReplayRecorder, ReplayStore
from ..router.pipeline import Router
from ..router.server import RouterServer
from .startup import StartupTracker


# Dense SDPA is O(S^2) memory; the reference built its chunked/flash paths
# (N8/N12) after production OOMs at >=8K tokens (candle-binding
# chunked_sdpa.rs:1-25, issue #1957).  Above this limit we never serve dense.
LONG_SEQ_DENSE_LIMIT = 4096


def select_attention_impl(engine_cfg, max_seq_len: int,
                          platform: Optional[str] = None,
                          mesh=None) -> str:
    """Map the engine config's ``use_flash_attention`` knob onto a model's
    ``attention_impl`` (VERDICT r4 weak 3: the knob previously had no
    reader, so serving was dense-only at every length).

    - serving mesh with an sp axis -> 'ring' (sequence-parallel exact
      attention, ops.ring_attention — the sequence outgrew one chip);
    - TPU platform + knob on -> 'flash'
      (the Pallas online-softmax kernel, O(S) memory);
    - long context anywhere else -> 'chunked' (streamed query blocks,
      O(S) memory, bit-identical oracle);
    - short sequences -> 'dense' (XLA's fused SDPA wins at small S).
    """
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        return "ring"
    if platform is None:
        import jax

        platform = jax.default_backend()
    if getattr(engine_cfg, "use_flash_attention", False) \
            and platform == "tpu":
        return "flash"
    if max_seq_len and max_seq_len > LONG_SEQ_DENSE_LIMIT:
        return "chunked"
    return "dense"


def _held(spec: dict, key: str):
    """A task's ``<key>: [first, count]`` (a chip's share), or None."""
    return tuple(spec[key]) if spec.get(key) else None


def _token_at_a_time(generation: dict) -> dict:
    if set(generation) - {"gen_length"}:
        raise ValueError(f"generation settings of a token-at-a-time "
                         f"generative task: gen_length only, not "
                         f"{sorted(generation)}")
    return generation


def _serve_sdar_moe(spec, hf_cfg, path, tokenizer, load_state, eos,
                    generation):
    from ..models.generate import BlockDiffusionGenerator
    from ..models.sdar_moe import SdarMoeConfig, params_from_checkpoint

    mcfg = SdarMoeConfig.from_hf(hf_cfg,
                                 experts_held=_held(spec, "experts_held"))
    unknown = set(generation) - {
        "block_length", "denoising_steps", "confidence_threshold",
        "mask_token_id", "gen_length"}
    if unknown or "mask_token_id" not in generation:
        raise ValueError(
            f"generation settings of an sdar_moe task: mask_token_id is "
            f"required, unknown keys {sorted(unknown)}")
    return BlockDiffusionGenerator(
        mcfg, params_from_checkpoint(path, mcfg), tokenizer,
        eos_token_ids=eos, **generation), {}


def _serve_cached(module_name: str, config_name: str, shares=("experts_held",)):
    """A decoder that owns its cache (``<module>.CachedModel``) under the
    token-at-a-time loop, with the task's shares of a layer."""
    def serve(spec, hf_cfg, path, tokenizer, load_state, eos, generation):
        import importlib

        from ..models.generate import GreedyGenerator

        module = importlib.import_module(f"..models.{module_name}",
                                         __package__)
        generation = _token_at_a_time(generation)
        mcfg = getattr(module, config_name).from_hf(
            hf_cfg, **{key: _held(spec, key) for key in shares})
        return GreedyGenerator(
            mcfg, module.params_from_checkpoint(path, mcfg), tokenizer,
            eos_token_ids=eos, model=module.CachedModel(mcfg),
            **generation), {}
    return serve


def _serve_qwen3(spec, hf_cfg, path, tokenizer, load_state, eos, generation):
    from types import SimpleNamespace

    from ..models.generate import GreedyGenerator, with_lora_leaves
    from ..models.lora import LoRAConfig
    from ..models.qwen3 import Qwen3Config, qwen3_params_from_state_dict

    generation = _token_at_a_time(generation)
    qcfg = Qwen3Config.from_hf(SimpleNamespace(**hf_cfg))
    adapters = {name: i for i, name in
                enumerate(spec.get("adapters", []) or [])}
    lora_spec = spec.get("lora") or {}
    lora = LoRAConfig(
        rank=int(lora_spec.get("rank", 8)),
        alpha=float(lora_spec.get("alpha", 16.0)),
        num_tasks=max(1, len(adapters))) if adapters else None
    qparams = qwen3_params_from_state_dict(load_state(path), wrap="model")
    if lora is not None:
        qparams = with_lora_leaves(qcfg, lora, qparams)
    return GreedyGenerator(qcfg, qparams, tokenizer, lora=lora,
                           eos_token_ids=eos, **generation), adapters


# the served generative model types: ``model_type`` of the checkpoint's
# ``config.json`` -> (what it is, how it is built); a new type is a row
GENERATORS = {
    "sdar_moe": (
        "sparse experts, generation by diffusion over blocks "
        "(``generation: {block_length, denoising_steps, "
        "confidence_threshold, mask_token_id, gen_length}``; "
        "``experts_held``).", _serve_sdar_moe),
    "lfm2_moe": (
        "short-convolution and attention layers over one hybrid cache, "
        "sigmoid-and-bias expert routing, greedy decoding a token at a "
        "time (``generation: {gen_length}``; ``experts_held``).",
        _serve_cached("lfm2_moe", "Lfm2MoeConfig")),
    "qwen3": (
        "the dense Qwen3 causal LM, the token-at-a-time loop with "
        "per-request LoRA adapters (``adapters:``, ``lora: {rank, alpha}``; "
        "``generation: {gen_length}``).", _serve_qwen3),
    "dots3_note": (
        "latent attention over one latent cache (full layers under a "
        "learned top-k selection of keys, sliding layers over a ring), "
        "sigmoid-and-bias expert routing beside a shared expert, the same "
        "loop (``generation: {gen_length}``; ``experts_held``, "
        "``vocab_held``: ids and logits are then over that slice).",
        _serve_cached("dots3_note", "Dots3NoteConfig",
                      ("experts_held", "vocab_held"))),
    "joyai_llm_flash": (
        "DeepSeek-V3's layers (latent attention in every layer, "
        "sigmoid-and-bias expert routing beside a shared expert) with the "
        "checkpoint's multi-token-prediction module as its own drafter: "
        "the same loop, whose decode step then runs the last committed "
        "token and the module's draft of the next and commits one token "
        "or two a row, the tokens being those of one-at-a-time decoding; "
        "no setting turns it on or off, a checkpoint without the module "
        "(``num_nextn_predict_layers`` 0) decodes a token at a time "
        "(``generation: {gen_length}``; ``experts_held``).",
        _serve_cached("joyai_llm_flash", "JoyaiLlmFlashConfig")),
    "laguna": (
        "grouped-query attention in two geometries by layer type (full "
        "layers over a whole K/V cache, sliding layers of another head "
        "count over a ring of ``sliding_window`` positions; per-type RoPE, "
        "YaRN and a partial rotary width among them), a sigmoid gate a "
        "head, softmax expert routing beside a gated shared expert, the "
        "same loop (``generation: {gen_length}``; ``experts_held``, "
        "``vocab_held``).",
        _serve_cached("laguna", "LagunaConfig",
                      ("experts_held", "vocab_held"))),
    "olmo_hybrid": (
        "a dense decoder of gated-delta-rule linear-attention layers (a "
        "float32 matrix state a head, moved by a chunked scan in a prefill "
        "and a token at a time after it, behind three short causal "
        "convolutions) and full-attention layers in the checkpoint's "
        "``layer_types`` order, the norm on each sub-layer's output, over "
        "one cache of K/V, states and conv windows, the same loop "
        "(``generation: {gen_length}``; held whole: a chip has no share "
        "of a layer).",
        _serve_cached("olmo_hybrid", "OlmoHybridConfig", shares=())),
}
GENERATIVE_MODEL_TYPES = tuple(GENERATORS)


def build_generator(spec: dict, hf_cfg: dict, path: str, tokenizer,
                    load_state):
    """The generator of a ``kind: generative`` task, by the checkpoint's
    ``model_type`` (``GENERATORS`` is the table of the served types; any
    other is refused by name before anything is built); every model number
    comes from the checkpoint's ``config.json`` through the architecture's
    own ``from_hf`` (dtype from ``torch_dtype``), the generation settings
    from the task's ``generation:`` block.  Returns ``(generator, adapter
    index)``.

    ``experts_held: [first, count]`` (and, where the type takes it,
    ``vocab_held``) is a chip's share of a layer that several chips divide.
    ``gen_length`` is the length of a guard's verdict: what
    ``engine.guard_classify`` asks for and ``engine.warmup`` compiles."""
    eos_raw = spec.get("eos_token_ids") or hf_cfg.get("eos_token_id", 0)
    # HF configs carry int OR list (Qwen family uses a list)
    eos = list(eos_raw) if isinstance(eos_raw, (list, tuple)) else [eos_raw]
    model_type = hf_cfg.get("model_type")
    if model_type not in GENERATORS:
        raise ValueError(
            f"a generative task's checkpoint says model_type "
            f"{model_type!r}; served: {', '.join(GENERATORS)}")
    _, serve = GENERATORS[model_type]
    return serve(spec, hf_cfg, path, tokenizer, load_state, eos,
                 dict(spec.get("generation") or {}))


build_generator.__doc__ += "\n\n    Served:\n" + "".join(
    f"\n    ``{name}``: {doc}" for name, (doc, _)
    in GENERATORS.items())


def build_engine(cfg: RouterConfig, mock: bool = False, registry=None):
    """Engine from config (or the mock seam). Returns None when no
    classifier models are configured — the router then runs heuristics-only
    (fail-open posture).  ``registry`` (a RuntimeRegistry) routes the
    engine's metrics + lifecycle events to that registry's sinks instead
    of the process globals (pkg/routerruntime isolation)."""
    if mock:
        from ..engine.testing import make_embedding_engine

        return make_embedding_engine()
    specs = cfg.classifier_models or {}
    if not specs:
        return None
    import jax
    import numpy as np

    from ..engine.classify import InferenceEngine
    from ..models.convert import modernbert_params_from_state_dict
    from ..models.modernbert import (
        ModernBertConfig,
        ModernBertForSequenceClassification,
        ModernBertForTokenClassification,
    )
    from ..models.embeddings import MmBertEmbeddingModel
    from ..utils.tokenization import HFTokenizer

    # resolve/auto-download checkpoints not already on disk
    # (pkg/modeldownload role; absent CLI or gated repos soft-skip and
    # the task's signals fail open)
    from .modeldownload import ModelDownloader

    from .events import (
        DOWNLOAD_DONE,
        DOWNLOAD_FAILED,
        DOWNLOAD_STARTED,
        ENGINE_READY,
        default_bus,
    )

    downloader = ModelDownloader()
    missing = {t: s for t, s in specs.items()
               if s.get("checkpoint")
               and not os.path.exists(s["checkpoint"])}
    resolved_paths = {}
    if missing:
        default_bus.emit(DOWNLOAD_STARTED, tasks=sorted(missing))
        try:
            resolved_paths = downloader.ensure_all(missing)
        except Exception as exc:
            # per-task soft-skips happen INSIDE ensure_all; anything
            # escaping it is a downloader/host fault that must keep
            # failing startup fast (pre-events behavior), not leave the
            # router serving with zero checkpoints
            default_bus.emit(DOWNLOAD_FAILED,
                             error=f"{type(exc).__name__}: {exc}"[:200])
            raise
        default_bus.emit(DOWNLOAD_DONE, resolved=sorted(resolved_paths))

    engine = InferenceEngine(
        cfg.engine,
        metrics=registry.metric_series() if registry is not None else None,
        events=registry.events if registry is not None else None,
        runtime_stats=registry.get("runtimestats")
        if registry is not None else None,
        program_stats=registry.get("programstats")
        if registry is not None else None)

    # Dedup caches: tasks whose specs point at the SAME checkpoint /
    # tokenizer path must receive the same array and tokenizer OBJECTS —
    # the engine's fused classifier bank groups by identity, so without
    # this every task would hold its own trunk copy and the bank could
    # never form in production.  Only CONVERTED params are cached (raw
    # safetensors state dicts are loaded per use and dropped — retaining
    # every checkpoint's raw arrays for the whole loop would raise peak
    # host RAM from ~one checkpoint to the sum of all of them).
    # Cross-checkpoint trunk dedup (two files, identical frozen trunk)
    # is the ROADMAP content-fingerprint follow-on.
    mb_params_cache: dict = {}
    tok_cache: dict = {}

    def load_state(p: str):
        from safetensors.numpy import load_file

        return load_file(os.path.join(p, "model.safetensors")) \
            if os.path.isdir(p) else load_file(p)

    def tokenizer_for(tok_path: str) -> HFTokenizer:
        if tok_path not in tok_cache:
            tok_cache[tok_path] = HFTokenizer.from_pretrained_dir(tok_path)
        return tok_cache[tok_path]

    for task, spec in specs.items():
        path = spec.get("checkpoint", "")
        if path and not os.path.exists(path):
            path = resolved_paths.get(task, "")
        if not path or not os.path.exists(path):
            component_event("bootstrap", "model_missing", task=task,
                            path=spec.get("checkpoint", ""),
                            level="warning")
            continue
        import json

        cfg_path = os.path.join(path, "config.json") if os.path.isdir(path) \
            else os.path.join(os.path.dirname(path), "config.json")
        with open(cfg_path) as f:
            hf_cfg = json.load(f)
        labels = spec.get("labels") or \
            [hf_cfg.get("id2label", {}).get(str(i), str(i))
             for i in range(len(hf_cfg.get("id2label", {})))]
        # effective serving length: task cap (spec) else model max, never
        # beyond the engine's largest padding bucket — this drives the
        # dense/chunked/flash choice below
        buckets = cfg.engine.seq_len_buckets or [512]
        eff_max_seq = int(spec.get("max_seq_len", 0)) or \
            int(hf_cfg.get("max_position_embeddings", 8192))
        eff_max_seq = min(eff_max_seq, max(buckets))
        if spec.get("kind") == "multimodal":
            # SigLIP shared text/image space (N5 multimodal; the
            # multimodal-routing e2e profile's embedder) — its HF config
            # nests per-tower configs, so it never reaches the
            # ModernBERT path below
            from types import SimpleNamespace

            from ..models.siglip import (
                SiglipEmbedder,
                SiglipTowerConfig,
                siglip_params_from_state_dict,
            )

            text_tc = SiglipTowerConfig.from_hf(
                SimpleNamespace(**hf_cfg["text_config"]))
            vis_tc = SiglipTowerConfig.from_hf(
                SimpleNamespace(**hf_cfg["vision_config"]))
            tok = tokenizer_for(
                spec.get("tokenizer", path if os.path.isdir(path)
                         else os.path.dirname(path)))
            engine.register_multimodal(
                task, SiglipEmbedder(
                    text_tc, vis_tc,
                    siglip_params_from_state_dict(load_state(path)),
                    tokenizer=tok))
            component_event("bootstrap", "model_loaded", task=task,
                            kind="multimodal", architecture="siglip")
            continue
        attn_impl = select_attention_impl(cfg.engine, eff_max_seq,
                                          mesh=engine.mesh)
        mcfg = ModernBertConfig(
            vocab_size=hf_cfg["vocab_size"],
            hidden_size=hf_cfg["hidden_size"],
            intermediate_size=hf_cfg["intermediate_size"],
            num_hidden_layers=hf_cfg["num_hidden_layers"],
            num_attention_heads=hf_cfg["num_attention_heads"],
            max_position_embeddings=hf_cfg.get("max_position_embeddings",
                                               8192),
            rope_scaling=hf_cfg.get("rope_scaling"),
            num_labels=max(len(labels), 2),
            classifier_pooling=hf_cfg.get("classifier_pooling", "cls"),
            attention_impl=attn_impl,
            mesh=engine.mesh if attn_impl == "ring" else None,
        )
        component_event("bootstrap", "attention_impl", task=task,
                        impl=attn_impl, max_seq=eff_max_seq)
        kind = spec.get("kind", "sequence")
        arch = spec.get("architecture",
                        hf_cfg.get("model_type", "modernbert"))
        if arch in ("deberta", "deberta-v2", "deberta-v3") \
                and kind in ("sequence", "token"):
            from types import SimpleNamespace

            from ..models.deberta import (
                DebertaV3Config,
                DebertaV3ForSequenceClassification,
                DebertaV3ForTokenClassification,
                deberta_params_from_state_dict,
            )

            # single source of truth for the HF-config mapping
            dcfg = DebertaV3Config.from_hf(SimpleNamespace(**hf_cfg))
            dcfg.num_labels = max(len(labels), 2)
            module = DebertaV3ForTokenClassification(dcfg) \
                if kind == "token" \
                else DebertaV3ForSequenceClassification(dcfg)
            params = deberta_params_from_state_dict(load_state(path))
            tok = tokenizer_for(
                spec.get("tokenizer", path if os.path.isdir(path) else
                         os.path.dirname(path)))
            engine.register_task(task, kind, module, params, tok, labels,
                                 max_seq_len=int(spec.get("max_seq_len",
                                                          0)))
            component_event("bootstrap", "model_loaded", task=task,
                            kind=kind, architecture="deberta-v3")
            continue
        if kind == "generative":
            tok = tokenizer_for(
                spec.get("tokenizer", path if os.path.isdir(path) else
                         os.path.dirname(path)))
            generator, adapters = build_generator(spec, hf_cfg, path, tok,
                                                  load_state)
            engine.register_generative(task, generator, labels=labels,
                                       adapter_index=adapters)
            component_event("bootstrap", "model_loaded", task=task,
                            kind=kind,
                            architecture=hf_cfg["model_type"])
            continue
        if kind == "embedding":
            module = MmBertEmbeddingModel(mcfg)
        elif kind == "token":
            module = ModernBertForTokenClassification(mcfg)
        else:
            module = ModernBertForSequenceClassification(mcfg)
        # converted params dedup by path: two tasks served from one
        # ModernBERT checkpoint share the SAME param arrays, which is
        # exactly what lets the engine's trunk fingerprint fuse them
        if path not in mb_params_cache:
            mb_params_cache[path] = modernbert_params_from_state_dict(
                load_state(path))
        params = mb_params_cache[path]
        tok = tokenizer_for(
            spec.get("tokenizer", path if os.path.isdir(path) else
                     os.path.dirname(path)))
        engine.register_task(task, kind, module, params, tok, labels,
                             max_seq_len=int(spec.get("max_seq_len", 0)))
        component_event("bootstrap", "model_loaded", task=task, kind=kind)
    default_bus.emit(ENGINE_READY, tasks=sorted(engine.tasks()),
                     mesh=bool(engine.mesh))
    return engine


def build_router(cfg: RouterConfig, engine=None,
                 replay_path: Optional[str] = None,
                 carry_from: Optional[Router] = None,
                 registry=None) -> Router:
    """Build a router; ``carry_from`` transplants the stateful subsystems
    (semantic cache, memory, vectorstores, replay store/hooks) from a
    previous router so a config hot-reload keeps accumulated state
    (RouterService.Swap semantics — swap routing logic, keep state).
    ``registry`` (a RuntimeRegistry) binds the router's metric series to
    that registry's sinks — pass RuntimeRegistry.isolated() to embed a
    second router with fully independent observability."""
    router = Router(cfg, engine=engine,
                    cache=carry_from.cache if carry_from is not None else None,
                    metrics=registry.metric_series()
                    if registry is not None else None,
                    tracer=registry.tracer if registry is not None else None,
                    flightrec=registry.get("flightrec")
                    if registry is not None else None,
                    explain=registry.get("explain")
                    if registry is not None else None,
                    resilience=registry.get("resilience")
                    if registry is not None else None)
    # upstream resilience plane (resilience/upstream.py): carried like
    # every registry-slotted service; apply_upstream_knobs owns
    # attach/detach, this just re-binds an existing plane on rebuilds
    if registry is not None and registry.get("upstreams") is not None:
        router.upstream_health = registry.get("upstreams")
    from ..memory import InMemoryMemoryStore
    from ..vectorstore import VectorStoreManager

    embed_fn = None
    if engine is not None and engine.has_task("embedding"):
        embed_fn = lambda text: engine.embed("embedding", [text])[0]

    # shared state plane (stateplane/): constructed once and carried
    # across hot reloads like every stateful subsystem; enabled=false
    # (the default) builds NOTHING — byte-identical single-process
    # behavior.  A plane that fails to construct degrades to local
    # state with a warning, never a dead replica.
    sp_cfg = cfg.stateplane_config()
    plane = None
    if sp_cfg["enabled"]:
        if carry_from is not None \
                and getattr(carry_from, "stateplane", None) is not None:
            plane = carry_from.stateplane
        elif registry is not None \
                and registry.get("stateplane") is not None:
            plane = registry.get("stateplane")
        else:
            try:
                from ..stateplane import build_state_plane

                plane = build_state_plane(
                    cfg, metrics=registry.metrics
                    if registry is not None else None)
                if plane is not None:
                    plane.start()
                    if registry is not None:
                        registry.swap(stateplane=plane)
                    component_event("bootstrap", "stateplane_attached",
                                    backend=sp_cfg["backend"],
                                    replica=plane.replica_id)
            except Exception as exc:
                component_event("bootstrap", "stateplane_failed",
                                level="warning",
                                error=f"{type(exc).__name__}: "
                                      f"{exc}"[:200])
                plane = None
    else:
        # hot-reload DISABLE: a previously-attached plane must actually
        # stop — heartbeat thread, registry slot, /debug/stateplane,
        # fleet sensing — or the operator's "off" means nothing
        old_plane = getattr(carry_from, "stateplane", None) \
            if carry_from is not None else None
        if old_plane is None and registry is not None:
            old_plane = registry.get("stateplane")
        if old_plane is not None:
            try:
                old_plane.close()
            except Exception:
                pass
            if registry is not None:
                registry.swap(stateplane=None)
            component_event("bootstrap", "stateplane_detached")
    router.stateplane = plane

    # plane-shared semantic cache: only in-proc backends get wrapped —
    # an operator-configured redis/qdrant/milvus cache is already
    # cross-replica by nature.  The wrapped in-proc cache stays as the
    # local fallback the plane degrades to.  Reload-aware both ways: a
    # carried plain cache gets wrapped when the plane turns on, a
    # carried SharedSemanticCache unwraps to its local fallback when
    # the plane (or share.cache) turns off.
    if plane is not None and sp_cfg["share"]["cache"] \
            and router.cache is not None \
            and cfg.semantic_cache.backend_type in ("memory", "hnsw",
                                                    "hybrid"):
        from ..stateplane import SharedSemanticCache

        cache_embed = getattr(router.cache, "embed_fn", None) or embed_fn
        if not isinstance(router.cache, SharedSemanticCache) \
                and cache_embed is not None:
            router.cache = SharedSemanticCache(
                plane, cache_embed,
                similarity_threshold=cfg.semantic_cache
                .similarity_threshold,
                ttl_seconds=cfg.semantic_cache.ttl_seconds,
                local=router.cache)
    elif router.cache is not None:
        sp_cache_mod = sys.modules.get(
            "semantic_router_tpu.stateplane.cache")
        if sp_cache_mod is not None and isinstance(
                router.cache, sp_cache_mod.SharedSemanticCache) \
                and router.cache.local is not None:
            router.cache = router.cache.local

    if carry_from is not None:
        router.memory_store = carry_from.memory_store
        router.vectorstores = carry_from.vectorstores
        router.response_hooks = list(carry_from.response_hooks)
        if hasattr(carry_from, "replay_store"):
            router.replay_store = carry_from.replay_store
        return router

    # memory backend (pkg/memory external stores role; the reference's
    # default memory store is Milvus — milvus_store*.go)
    mem_cfg = cfg.memory or {}
    backend = mem_cfg.get("backend", "")
    if backend == "sqlite" and mem_cfg.get("path"):
        from ..memory.sqlite_store import SQLiteMemoryStore

        router.memory_store = SQLiteMemoryStore(mem_cfg["path"], embed_fn)
    elif backend in ("qdrant", "milvus"):
        mem_embed = embed_fn
        if mem_embed is None:
            # ANN stores need vectors; the remote embedding provider
            # (external_models) covers engines without a local task
            remote = getattr(router, "_remote_embedder_cache", None)
            if remote is not None:
                mem_embed = lambda text: remote.embed("embedding",
                                                      [text])[0]
        if mem_embed is None:
            component_event("bootstrap", "memory_backend_fallback",
                            backend=backend, level="warning",
                            reason="no embedding source; using in-proc")
            router.memory_store = InMemoryMemoryStore(embed_fn)
        elif backend == "qdrant":
            from ..memory.ann_store import QdrantMemoryStore

            router.memory_store = QdrantMemoryStore(
                mem_embed,
                base_url=mem_cfg.get("base_url",
                                     "http://127.0.0.1:6333"),
                api_key=str(mem_cfg.get("api_key", "")),
                collection=mem_cfg.get("collection", "vsr_memory"))
        else:
            from ..memory.ann_store import MilvusMemoryStore

            router.memory_store = MilvusMemoryStore(
                mem_embed,
                base_url=mem_cfg.get("base_url",
                                     "http://127.0.0.1:19530"),
                token=str(mem_cfg.get("token", "")),
                db_name=mem_cfg.get("db_name", "default"),
                collection=mem_cfg.get("collection", "vsr_memory"))
    else:
        router.memory_store = InMemoryMemoryStore(embed_fn)

    # vectorstore backend (pkg/vectorstore registry role)
    vs_cfg = cfg.vectorstore or {}
    registry = None
    reg_cfg = vs_cfg.get("registry") or {}
    if reg_cfg.get("backend") == "postgres":
        from ..vectorstore.pg_registry import PostgresMetadataRegistry

        try:
            registry = PostgresMetadataRegistry(
                host=reg_cfg.get("host", "127.0.0.1"),
                port=int(reg_cfg.get("port", 5432)),
                user=reg_cfg.get("user", "postgres"),
                database=reg_cfg.get("database", "postgres"),
                password=str(reg_cfg.get("password", "")))
        except Exception as exc:
            component_event("bootstrap", "vectorstore_registry_failed",
                            level="warning", error=str(exc)[:200])
    # plane-shared vector stores: like the cache, only the in-proc
    # default rides the plane — sqlite/qdrant/milvus/llamastack are
    # already durable/shared backends in their own right
    vs_backend = vs_cfg.get("backend", "memory")
    if plane is not None and sp_cfg["share"]["vectorstore"] \
            and vs_backend == "memory":
        vs_backend = "stateplane"
    router.vectorstores = VectorStoreManager(
        embed_fn, backend=vs_backend,
        base_path=vs_cfg.get("path"),
        backend_config=vs_cfg.get("backend_config"),
        registry=registry, stateplane=plane)
    if registry is not None:
        attached = router.vectorstores.load_from_registry()
        if attached:
            component_event("bootstrap", "vectorstore_registry_attach",
                            stores=attached)

    replay_cfg = cfg.router_replay or {}
    if replay_cfg.get("enabled", True):
        if replay_cfg.get("backend") == "sqlite" \
                and (replay_path or replay_cfg.get("path")):
            from ..replay.sqlite_store import SQLiteReplayStore

            store = SQLiteReplayStore(
                replay_path or replay_cfg["path"],
                max_records=int(replay_cfg.get("max_records", 100_000)))
        elif replay_cfg.get("backend") == "postgres":
            from ..replay.postgres_store import PostgresReplayStore

            store = PostgresReplayStore(
                host=replay_cfg.get("host", "127.0.0.1"),
                port=int(replay_cfg.get("port", 5432)),
                user=replay_cfg.get("user", "postgres"),
                database=replay_cfg.get("database", "postgres"),
                password=str(replay_cfg.get("password", "")),
                max_records=int(replay_cfg.get("max_records", 100_000)))
        else:
            store = ReplayStore(
                max_records=int(replay_cfg.get("max_records", 10_000)),
                path=replay_path or replay_cfg.get("path"))
        router.replay_store = store
        router.response_hooks.append(ReplayRecorder(
            store,
            capture_request_body=bool(
                replay_cfg.get("capture_request_body", False)),
            capture_response_body=bool(
                replay_cfg.get("capture_response_body", False)),
        ))
    return router


def apply_observability_knobs(cfg: RouterConfig, registry) -> None:
    """Apply the observability block's runtime knobs (config.schema
    accessors are the one interpretation point) to a registry's slotted
    sinks: batch-trace sampling on the tracer, OpenMetrics exemplars on
    the metrics registry, flight-recorder retention.  Called at boot and
    from the config hot-reload handler — registry-slotted, so isolated
    instances configure independently, and a malformed telemetry knob
    must never stop (or wedge) the server."""
    try:
        registry.tracer.sample_rate = cfg.tracing_sample_rate()
    except Exception:
        pass
    try:
        # unconditional set: a reload must be able to turn exemplars OFF
        registry.metrics.enable_exemplars(cfg.metrics_exemplars_enabled())
    except Exception:
        pass
    try:
        fr_cfg = cfg.flight_recorder_config()
        fr = registry.get("flightrec")
        if fr is not None and fr_cfg:
            fr.configure(**fr_cfg)
        # tail-based sampling: retained (slowest-N / threshold) traces
        # pin themselves force-sampled on this registry's tracer
        if fr is not None and getattr(fr, "on_retain", None) is None \
                and hasattr(registry.tracer, "force_sample"):
            fr.on_retain = registry.tracer.force_sample
    except Exception as exc:
        component_event("bootstrap", "flight_recorder_config_invalid",
                        error=str(exc)[:200], level="warning")
    try:
        # always-on runtime telemetry: the device-step sampler + process
        # gauges (observability.runtimestats) start here and retune on
        # hot reload; disabling stops the thread AND short-circuits the
        # engine's per-step append (the bench overhead-arm baseline)
        rs = registry.get("runtimestats")
        if rs is not None:
            rs_cfg = cfg.runtime_stats_config()
            rs.enabled = rs_cfg["enabled"]
            if rs_cfg["enabled"]:
                rs.start(rs_cfg["interval_s"])
            else:
                rs.stop()
    except Exception as exc:
        component_event("bootstrap", "runtime_stats_config_invalid",
                        error=str(exc)[:200], level="warning")
    try:
        # XLA program-cost catalog (observability.programstats): the
        # enabled knob gates the engine's compile-site capture hooks;
        # slo_capture arms the SLO-burn-triggered bounded profiler
        # trace + catalog snapshot on THIS registry's event bus
        ps = registry.get("programstats")
        if ps is not None:
            ps_cfg = cfg.programstats_config()
            ps.enabled = ps_cfg["enabled"]
            cap_cfg = ps_cfg["slo_capture"]
            ctl = getattr(ps, "slo_capture", None)
            if ps_cfg["enabled"] and cap_cfg["enabled"]:
                if ctl is None:
                    from ..observability.programstats import (
                        SLOCaptureController,
                    )

                    ctl = SLOCaptureController(catalog=ps)
                    ps.slo_capture = ctl
                # (re)bind to the registry's live slots every apply —
                # a hot reload may have swapped any of them
                ctl.runtime_stats = registry.get("runtimestats")
                ctl.profiler = registry.get("profiler")
                ctl.flightrec = registry.get("flightrec")
                ctl.trace_s = cap_cfg["trace_s"]
                ctl.cooldown_s = cap_cfg["cooldown_s"]
                fr = registry.get("flightrec")
                if fr is not None:
                    fr.capture_provider = ctl.links
                ctl.attach(registry.get("events"))
            elif ctl is not None:
                ctl.detach()
    except Exception as exc:
        component_event("bootstrap", "programstats_config_invalid",
                        error=str(exc)[:200], level="warning")
    try:
        # in-process SLO engine (observability.slo): objectives parse
        # here, burn-rate monitors run on their own thread, /health
        # reads the degraded flag.  Malformed objectives are skipped and
        # reported via /debug/slo config_errors — never fatal.  Firing
        # alerts also export as runtime events on THIS registry's bus so
        # the kube operator can react (shed traffic / scale) instead of
        # only reporting.
        slo = registry.get("slo")
        if slo is not None:
            slo.event_bus = registry.get("events")
            slo.configure(cfg.slo_config())
            if slo.enabled:
                slo.start(slo.evaluation_interval_s)
            else:
                slo.stop()
            if slo.config_errors:
                component_event("bootstrap", "slo_objectives_invalid",
                                errors=slo.config_errors[:5],
                                level="warning")
    except Exception as exc:
        component_event("bootstrap", "slo_config_invalid",
                        error=str(exc)[:200], level="warning")
    try:
        # decision explainability (observability.explain): per-request
        # routing audit records — ring size / sampling / PII redaction
        # retune on hot reload like every other telemetry knob
        explain = registry.get("explain")
        if explain is not None:
            ex_cfg = cfg.decision_explain_config()
            explain.configure(ex_cfg)
            # optional durable backend (explain_store.py): records also
            # land in SQLite so post-restart audits work; idempotent on
            # hot reload (same path keeps the same store).  With a state
            # plane attached (and no explicit sqlite config) the durable
            # mirror rides the plane instead — every replica serves the
            # FLEET's audit trail at /debug/decisions?source=durable.
            durable = ex_cfg.get("durable") or {}
            plane = registry.get("stateplane")
            sp_share = cfg.stateplane_config()["share"] \
                if plane is not None else {}
            if durable.get("backend") == "sqlite" and durable.get("path"):
                cur = getattr(explain, "durable_store", None)
                if cur is None or getattr(cur, "path", "") \
                        != durable["path"]:
                    from ..observability.explain_store import (
                        SQLiteDecisionStore,
                    )

                    explain.attach_durable(SQLiteDecisionStore(
                        durable["path"],
                        max_records=int(durable.get("max_records",
                                                    100_000))))
            elif plane is not None and sp_share.get("explain"):
                from ..stateplane import StatePlaneDecisionStore

                cur = getattr(explain, "durable_store", None)
                if not isinstance(cur, StatePlaneDecisionStore) \
                        or cur.plane is not plane:
                    explain.attach_durable(StatePlaneDecisionStore(
                        plane,
                        max_records=int(durable.get("max_records",
                                                    10_000))))
            elif getattr(explain, "durable_store", None) is not None:
                explain.attach_durable(None)
    except Exception as exc:
        component_event("bootstrap", "decision_explain_config_invalid",
                        error=str(exc)[:200], level="warning")
    try:
        # fleet observability plane (observability.fleet): metric
        # federation, fleet-scoped SLO counts, and cross-replica debug
        # aggregation over the stateplane (observability/fleetobs.py).
        # Built only when BOTH stateplane.enabled and
        # observability.fleet.enabled — the default-off posture
        # constructs nothing, publishes nothing, and /metrics stays
        # byte-identical.
        fl_cfg = cfg.fleet_obs_config()
        plane = registry.get("stateplane")
        fobs = registry.get("fleetobs")
        slo = registry.get("slo")
        if fl_cfg["enabled"] and plane is not None:
            if fobs is None or fobs.plane is not plane:
                from ..observability.fleetobs import build_fleet_obs

                if fobs is not None:  # plane was swapped out under us
                    try:
                        fobs.plane.remove_publisher(
                            fobs.publisher.maybe_publish)
                    except Exception:
                        pass
                fobs = build_fleet_obs(
                    fl_cfg, plane, registry.metrics,
                    flightrec=registry.get("flightrec"),
                    explain=registry.get("explain"), slo=slo)
                plane.add_publisher(fobs.publisher.maybe_publish)
                registry.swap(fleetobs=fobs)
                component_event("bootstrap", "fleetobs_attached",
                                replica=plane.replica_id)
            else:
                # hot reload: retune knobs + rebind sinks in place (a
                # reload may have swapped any of the slots)
                fobs.publisher.interval_s = fl_cfg["publish_interval_s"]
                fobs.publisher.debug_top_n = fl_cfg["debug_top_n"]
                fobs.aggregator.cache_s = fl_cfg["cache_s"]
                fobs.publisher.flightrec = registry.get("flightrec")
                fobs.publisher.explain = registry.get("explain")
                fobs.publisher.slo = slo
            # fleet-scoped SLO objectives read the merged fleet counts
            if slo is not None:
                slo.fleet_source = fobs.aggregator.merged_registry
        else:
            if fobs is not None:
                # reload DISABLE: stop publishing, drop the published
                # keys, empty the slot — "off" must mean off
                try:
                    fobs.plane.remove_publisher(
                        fobs.publisher.maybe_publish)
                except Exception:
                    pass
                fobs.close()
                registry.swap(fleetobs=None)
                component_event("bootstrap", "fleetobs_detached")
            if slo is not None:
                slo.fleet_source = None
    except Exception as exc:
        component_event("bootstrap", "fleetobs_config_invalid",
                        error=str(exc)[:200], level="warning")
    try:
        # overload control (resilience.controller): bind the ladder to
        # THIS registry's sensors (event bus, SLO monitor, runtimestats)
        # and effect surfaces (tracer, explainer), configure the knobs,
        # and run the control loop.  The first subsystem where the
        # telemetry stack steers the data plane — and like every other
        # knob block, malformed config must never stop the server.
        res = registry.get("resilience")
        if res is not None:
            plane = registry.get("stateplane")
            share_fleet = plane is not None and \
                cfg.stateplane_config()["share"].get("fleet")
            res.bind(events=registry.get("events"),
                     slo=registry.get("slo"),
                     runtimestats=registry.get("runtimestats"),
                     tracer=registry.tracer,
                     explain=registry.get("explain"),
                     fleet=plane if share_fleet else None)
            if not share_fleet:
                # bind() only ever attaches; a reload that turned the
                # plane (or share.fleet) off must actually detach the
                # fleet sensor or the ladder keeps stepping from it
                res.fleet = None
            res.configure(cfg.resilience_config())
            # the tracer/explain knob blocks above just re-applied the
            # OPERATOR sampling values; if the ladder is degraded the L1
            # shed must win again (and remember the NEW values to
            # restore on recovery)
            res.resync_knob_effects()
            if res.enabled:
                res.start(res.interval_s)
            else:
                res.stop()
    except Exception as exc:
        component_event("bootstrap", "resilience_config_invalid",
                        error=str(exc)[:200], level="warning")


def apply_upstream_knobs(cfg: RouterConfig, registry, router) -> None:
    """Attach/configure/detach the upstream resilience plane
    (resilience/upstream.py) for a registry + router pair.  Called at
    boot and on config hot reload; ``resilience.upstream.enabled:
    false`` (the default) constructs NOTHING and detaches any previous
    plane — byte-identical routing posture.  Like every knob block,
    malformed upstream config must never stop the server."""
    try:
        up_cfg = cfg.upstream_config()
        if not up_cfg["enabled"]:
            old = registry.get("upstreams")
            if old is not None:
                registry.swap(upstreams=None)
                component_event("bootstrap", "upstreams_detached")
            if router is not None:
                router.upstream_health = None
            return
        from ..resilience.upstream import UpstreamHealth

        up = registry.get("upstreams")
        if up is None:
            up = UpstreamHealth(registry.metrics)
            registry.swap(upstreams=up)
            component_event("bootstrap", "upstreams_attached")
        up.bind(events=registry.get("events"),
                plane=registry.get("stateplane"),
                resilience=registry.get("resilience"))
        if not up_cfg["fleet_share"]:
            # bind() only ever attaches; a reload that turned
            # fleet_share off must actually detach the plane or open
            # circuits keep publishing
            up.plane = None
        up.configure(up_cfg)
        if router is not None:
            router.upstream_health = up
    except Exception as exc:
        component_event("bootstrap", "upstream_config_invalid",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                        level="warning")


def apply_packing_knobs(cfg: RouterConfig, engine) -> None:
    """Apply the engine.packing block (docs/PACKING.md) to a live
    engine: retunes the packing scheduler's composition knobs in place
    and starts/stops the shape auto-tuner's polling thread — the thread
    is bootstrap's to own (bare test engines drive step() directly).
    Called at boot and on config hot reload; ``enabled: false`` restores
    byte-identical fixed-batch composition without swapping the
    batcher.  Malformed packing config must never stop the server."""
    if engine is None or not hasattr(engine, "configure_packing"):
        return
    try:
        pk = cfg.engine.packing_config()
        engine.configure_packing(cfg.engine.packing)
        tuner = getattr(engine, "_autotuner", None)
        if tuner is not None:
            if pk["enabled"] and pk["autotune"]["enabled"]:
                tuner.start(pk["autotune"]["interval_s"])
            else:
                tuner.stop()
        # packed-path warmup (docs/PACKING.md): recompile the packed
        # shapes the engine's compiled-step census says are hot, so the
        # first packed step after this boot/retune is a warm execute
        # instead of an inline XLA compile on the dispatch worker
        warmed = 0
        if pk["enabled"] and hasattr(engine, "warmup_packed_hot"):
            warmed = engine.warmup_packed_hot()
        component_event("bootstrap", "packing_configured",
                        enabled=pk["enabled"],
                        autotune=pk["autotune"]["enabled"],
                        warmed_shapes=warmed)
    except Exception as exc:
        component_event("bootstrap", "packing_config_invalid",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                        level="warning")


def apply_mesh_knobs(cfg: RouterConfig, engine) -> None:
    """Apply the engine.mesh block (docs/PARALLEL.md) to a live
    engine: builds or tears down the dp×tp serving mesh and atomically
    swaps each trunk group's serving container (banks re-placed,
    program sets rebuilt) — in-flight batches finish on the snapshot
    they already read, so a hot mesh flip never corrupts a batch.
    Called at boot and on config hot reload; ``enabled: false`` (the
    default) keeps byte-identical single-device serving.  Malformed
    mesh config must never stop the server."""
    if engine is None or not hasattr(engine, "configure_mesh"):
        return
    try:
        mk = cfg.engine.mesh_config()
        engine.configure_mesh(cfg.engine.mesh)
        rep = engine.mesh_report() if hasattr(engine, "mesh_report") \
            else {}
        component_event("bootstrap", "mesh_configured",
                        enabled=mk["enabled"],
                        axes=rep.get("axes", {}),
                        devices=rep.get("mesh_devices", 0))
    except Exception as exc:
        component_event("bootstrap", "mesh_config_invalid",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                        level="warning")


def apply_kernel_knobs(cfg: RouterConfig, engine) -> None:
    """Apply the engine.quant + engine.kernels blocks (docs/KERNELS.md)
    to a live engine: quantizes trunk-group weights / flips the tuned
    kernel paths by atomically swapping each group's fused jit program
    set — in-flight batches finish on the programs they already hold.
    Called at boot and on config hot reload; all defaults are OFF
    (byte-identical serving).  After a flip rebuilt program sets, the
    packed-shape census re-warms so the first packed step afterward is
    not a cold compile.  Malformed kernel config must never stop the
    server."""
    if engine is None or not hasattr(engine, "configure_kernels"):
        return
    try:
        qk = cfg.engine.quant_config()
        kk = cfg.engine.kernels_config()
        engine.configure_quant(cfg.engine.quant)
        engine.configure_kernels(cfg.engine.kernels)
        warmed = 0
        if hasattr(engine, "warmup_packed_hot"):
            warmed = engine.warmup_packed_hot()
        component_event("bootstrap", "kernels_configured",
                        quant=qk["mode"],
                        epilogue=kk["epilogue"]["enabled"],
                        bgmv=kk["bgmv"]["enabled"],
                        warmed_shapes=warmed)
    except Exception as exc:
        component_event("bootstrap", "kernels_config_invalid",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                        level="warning")


def apply_flywheel_knobs(cfg: RouterConfig, registry, router) -> None:
    """Attach/configure/detach the learned-routing flywheel
    (flywheel/controller.py) for a registry + router pair.  Called at
    boot and on config hot reload; ``flywheel.enabled: false`` (the
    default) constructs NOTHING and detaches any previous controller —
    byte-identical routing posture.  Like every knob block, malformed
    flywheel config must never stop the server."""
    try:
        fw_cfg = cfg.flywheel_config()
        if not fw_cfg["enabled"]:
            old = registry.get("flywheel")
            if old is not None:
                try:
                    old.close()
                except Exception:
                    pass
                registry.swap(flywheel=None)
                component_event("bootstrap", "flywheel_detached")
            if router is not None:
                router.flywheel = None
            return
        from ..flywheel import FlywheelController

        fw = registry.get("flywheel")
        if fw is None:
            fw = FlywheelController(registry.metrics)
            registry.swap(flywheel=fw)
            component_event("bootstrap", "flywheel_attached")
        res = registry.get("resilience")
        fw.bind(explain=registry.get("explain"),
                events=registry.get("events"),
                cost_model=getattr(res, "cost_model", None)
                if res is not None else None,
                router=router)
        fw.configure(fw_cfg)
        if router is not None:
            router.flywheel = fw
    except Exception as exc:
        component_event("bootstrap", "flywheel_config_invalid",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                        level="warning")


def apply_cascade_knobs(cfg: RouterConfig, registry, router) -> None:
    """Attach/configure/detach the decision-aware signal cascade
    (engine/cascade, docs/CASCADE.md) on a router.  Called at boot and
    on config hot reload; ``engine.cascade.enabled: false`` (the
    default) detaches any previous evaluator — the pipeline falls back
    to the plain full fan-out, byte-identical routing.  Malformed
    cascade config must never stop the server."""
    try:
        ck = cfg.engine.cascade_config()
        if not ck["enabled"]:
            if registry.get("cascade") is not None:
                registry.swap(cascade=None)
                component_event("bootstrap", "cascade_detached")
            if router is not None:
                router.cascade = None
            return
        from ..engine.cascade import CascadeEvaluator

        casc = registry.get("cascade")
        if casc is None:
            casc = CascadeEvaluator(
                metrics=registry.metric_series(),
                runtime_stats=registry.get("runtimestats"))
            registry.swap(cascade=casc)
            component_event("bootstrap", "cascade_attached")
        # re-bound every apply: hot reload swaps the router (and with it
        # the flywheel handle the ordering discount reads)
        casc.flywheel_provider = lambda: getattr(router, "flywheel", None)
        casc.runtime_stats = registry.get("runtimestats")
        casc.configure(ck)
        if router is not None:
            router.cascade = casc
    except Exception as exc:
        component_event("bootstrap", "cascade_config_invalid",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                        level="warning")


def apply_ann_knobs(cfg: RouterConfig, registry, router) -> None:
    """Attach/configure/detach the on-device ANN plane (ann/,
    docs/ANN.md) for a registry + router pair.  Called at boot and on
    config hot reload; ``ann.enabled: false`` (the default) constructs
    NOTHING and detaches any previous plane — cache similarity and
    vector-store search stay byte-identical.  Malformed ann config must
    never stop the server."""
    try:
        ak = cfg.ann_config()
        cache = getattr(router, "cache", None) \
            if router is not None else None
        vsm = getattr(router, "vectorstores", None) \
            if router is not None else None
        if not ak["enabled"]:
            old = registry.get("ann")
            if old is not None:
                try:
                    old.close()
                except Exception:
                    pass
                registry.swap(ann=None)
                component_event("bootstrap", "ann_detached")
            if cache is not None and hasattr(cache, "detach_ann"):
                cache.detach_ann()
            if vsm is not None:
                vsm.ann = None
            return
        from ..ann import AnnPlane

        plane = registry.get("ann")
        if plane is None:
            plane = AnnPlane(registry.metrics,
                             programstats=registry.get("programstats"),
                             runtime_stats=registry.get("runtimestats"))
            registry.swap(ann=plane)
            component_event("bootstrap", "ann_attached")
        plane.configure(ak)
        # the semantic cache rides the "cache" index: similarity moves
        # onto the device bank and the in-proc mirror gates OFF — ONE
        # similarity interpretation point (cache.similarity_owner())
        if cache is not None and hasattr(cache, "attach_ann"):
            if ak["share"]["cache"]:
                sp = getattr(router, "stateplane", None)
                idx = plane.bind_cache_sync(sp) if sp is not None \
                    else plane.index("cache")
                cache.attach_ann(idx)
            else:
                cache.detach_ann()
        if vsm is not None:
            vsm.ann = plane if ak["share"]["vectorstore"] else None
        component_event("bootstrap", "ann_configured",
                        quant=ak["quant"],
                        mesh=ak["mesh"]["enabled"])
    except Exception as exc:
        component_event("bootstrap", "ann_config_invalid",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                        level="warning")


def serve(config_path: str, port: int = 8801,
          default_backend: str = "", mock_models: bool = False,
          status_path: Optional[str] = None,
          watch_config: bool = True,
          block: bool = True):
    """Full startup sequence; returns (server, tracker) when block=False."""
    tracker = StartupTracker(path=status_path)
    try:
        tracker.advance("loading_config", config_path)
        cfg = load_config(config_path)
        replace(cfg)

        tracker.advance("loading_models",
                        "mock" if mock_models else
                        f"{len(cfg.classifier_models or {})} configured")
        engine = build_engine(cfg, mock=mock_models)

        router = build_router(cfg, engine)
        server = RouterServer(router, cfg, default_backend=default_backend,
                              port=port, config_path=config_path)
        server.startup = tracker
        # the plane built in build_router (no registry yet on this
        # path) joins the server's registry so the knob wiring below —
        # fleet-aggregated resilience, the plane explain mirror — and
        # /debug/stateplane all see it
        if getattr(router, "stateplane", None) is not None:
            server.registry.swap(stateplane=router.stateplane)
    except Exception as exc:
        # explicit failStartup (runtime_bootstrap.go:170): readiness
        # monitors must see failed=true, not eternally-starting
        tracker.fail(f"{type(exc).__name__}: {exc}")
        raise

    tracker.advance("warming")
    if engine is not None:
        from .events import (
            ENGINE_FAILED,
            WARMUP_DONE,
            WARMUP_STARTED,
            default_bus,
        )

        def _warm() -> None:
            default_bus.emit(WARMUP_STARTED,
                             tasks=sorted(engine.tasks()))
            try:
                engine.warmup()
            except Exception as exc:
                # a program the compiler refused is a FAILED startup —
                # readiness monitors see failed=true with the compiler's
                # message, and the terminal stage lets wait_for
                # sequencers stop (requests stay fail-open regardless)
                error = f"{type(exc).__name__}: {exc}"
                tracker.fail(f"warmup: {error}")
                component_event("bootstrap", "warmup_failed",
                                level="error", error=error[:2000])
                default_bus.emit(ENGINE_FAILED, during="warmup",
                                 error=error[:2000])
                return
            default_bus.emit(WARMUP_DONE)

        threading.Thread(target=_warm, daemon=True,
                         name="warmup").start()

    # OTLP span export when configured (observability.tracing.otlp_endpoint)
    # — attached to the SERVER's tracer (registry slot), so an embedded
    # second router's spans go to its own exporter
    from ..observability.otlp import (
        build_exporter_from_config,
        build_log_exporter_from_config,
    )

    server.otlp_exporter = build_exporter_from_config(
        cfg.tracing_config(), server.registry.tracer)
    # decision records export as OTLP log records to the same collector
    # (audit pipelines read /v1/logs; the trace id links back to spans)
    server.otlp_log_exporter = build_log_exporter_from_config(
        cfg.tracing_config(), server.registry.get("explain"))

    # observability knobs: applied here AND on config hot-reload (edits
    # to sample_rate / exemplars / flight_recorder must not need a
    # restart)
    apply_observability_knobs(cfg, server.registry)
    # learned-routing flywheel: attached after the observability stack
    # so it can bind the explainer / event bus / cost model it feeds on
    apply_flywheel_knobs(cfg, server.registry, router)
    # early-exit signal cascade: after the flywheel so the ordering
    # discount can read the just-attached controller's value estimates
    apply_cascade_knobs(cfg, server.registry, router)
    # upstream resilience plane: after the degradation controller and
    # state plane exist, so the retry gate and fleet share bind live
    apply_upstream_knobs(cfg, server.registry, router)
    # on-device ANN plane: after the state plane + cache exist so the
    # cache index can bind its fleet sync and gate the in-proc mirror
    apply_ann_knobs(cfg, server.registry, router)
    # serving mesh (docs/PARALLEL.md): dp×tp placement of the trunk
    # groups — applied BEFORE packing/kernels so their packed-shape
    # warmups compile against the placed program sets
    apply_mesh_knobs(cfg, engine)
    # sequence-packed batching: scheduler knobs + the shape auto-tuner
    # thread (the engine survives hot reloads, so this retunes in place)
    apply_packing_knobs(cfg, engine)
    # quantized trunk + tuned-kernel toggles (docs/KERNELS.md): swap
    # each trunk group's fused program set per engine.quant/.kernels
    apply_kernel_knobs(cfg, engine)

    # startKubernetesControllerIfNeeded (cmd/main.go:50): live CRD watch
    # regenerating the config file the ConfigWatcher below hot-swaps
    server.kube_operator = None
    k8s_cfg = (cfg.raw or {}).get("kubernetes", {}) or {}
    if k8s_cfg.get("enabled"):
        from .kubewatch import KubeClient, KubeOperator

        try:
            if k8s_cfg.get("api_url"):
                client = KubeClient(
                    k8s_cfg["api_url"],
                    token=str(k8s_cfg.get("token", "")),
                    namespace=k8s_cfg.get("namespace", "default"),
                    ca_file=k8s_cfg.get("ca_file", ""))
            else:
                client = KubeClient.in_cluster()
            server.kube_operator = KubeOperator(
                client, config_path).start()
            # close the loop: SLO alerts + degradation-ladder moves
            # surface as IntelligentPool status conditions/scale hints
            server.kube_operator.attach_bus(server.registry.get("events"))
            component_event("bootstrap", "kube_operator_started",
                            namespace=client.namespace)
        except Exception as exc:
            # fail-open: a cluster problem must not block serving the
            # on-disk config (the reference's controller is optional too)
            component_event("bootstrap", "kube_operator_failed",
                            level="warning",
                            error=f"{type(exc).__name__}: {exc}"[:200])

    watcher = None
    if watch_config:
        def on_reload(new_cfg: RouterConfig) -> None:
            # atomic swap: rebuild routing logic, carry stateful subsystems,
            # keep engine + server (RouterService.Swap, server.go:213)
            old = server.router
            new_router = build_router(new_cfg, engine, carry_from=old)
            server.router = new_router
            server.cfg = new_cfg
            apply_observability_knobs(new_cfg, server.registry)
            apply_flywheel_knobs(new_cfg, server.registry, new_router)
            apply_cascade_knobs(new_cfg, server.registry, new_router)
            apply_upstream_knobs(new_cfg, server.registry, new_router)
            apply_ann_knobs(new_cfg, server.registry, new_router)
            apply_mesh_knobs(new_cfg, engine)
            apply_packing_knobs(new_cfg, engine)
            apply_kernel_knobs(new_cfg, engine)
            # grace period before tearing down the old dispatcher so
            # requests already inside old.route() finish their fan-out
            threading.Timer(30.0, old.dispatcher.shutdown).start()
            component_event("bootstrap", "config_reloaded")
            from .events import CONFIG_RELOADED, default_bus

            default_bus.emit(CONFIG_RELOADED,
                             decisions=len(new_cfg.decisions))

        watcher = ConfigWatcher(config_path, on_reload)
        watcher.start()
    server.watcher = watcher

    server.start()
    tracker.advance("ready", f"listening on :{server.port}")
    component_event("bootstrap", "ready", port=server.port)
    if block:
        try:
            threading.Event().wait()
        except KeyboardInterrupt:
            pass
        finally:
            if watcher:
                watcher.stop()
            if server.kube_operator is not None:
                server.kube_operator.stop()
            server.stop()
    return server, tracker
