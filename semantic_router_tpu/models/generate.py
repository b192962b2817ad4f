"""Generative serving: one token-at-a-time loop (``GreedyGenerator``) over
a model that owns its cache — the dense Qwen3 below, the hybrid
``models/lfm2_moe.py``, the latent-attention ``models/dots3_note.py`` — which
commits one or two tokens a step where the model drafts for itself
(``models/joyai_llm_flash.py``), and generation by diffusion over blocks
(``BlockDiffusionGenerator``, ``models/sdar_moe.py``).

Reference capabilities re-designed TPU-first:
- qwen3_guard.rs (safety generation: greedy short-generation + structured
  regex parse) and qwen3_multi_lora_classifier.rs:1-60 (multi-LoRA
  generative classification with per-request adapter selection).

Design notes of the Qwen3 decoder (XLA-native, no torch-style dynamic
shapes):
- The KV cache is an explicit pytree of fixed-shape arrays
  ``[B, KV_heads, M, head_dim]`` updated with ``lax.dynamic_update_slice``
  at a uniform column offset — prompt tokens fill columns ``0..S`` (padding
  columns are masked forever), decode step ``t`` writes column ``S+t``.
  Every step is a fixed-shape jitted program: two compilations total per
  (batch, prompt-bucket, cache-length) triple, then O(1) per token.
- RoPE uses per-row absolute positions (right-padded prompts keep their
  true lengths), gathered from the precomputed float32 tables.
- Multi-LoRA rides the same stacked-adapter LoRADense as the classifier
  trunk: ``task_index`` is a traced integer → switching adapters per
  request is a gather, never a recompile.
"""

from __future__ import annotations

import contextlib
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..ops.rope import RopeSpec, rotate_half
from .lora import LoRAConfig, LoRADense
from .qwen3 import Qwen3Config, RMSNorm

NEG_INF = -1e30
# tokens a guard's verdict takes: a generator's ``gen_length`` unless its
# task's ``generation.gen_length`` says otherwise
GEN_LENGTH = 32


def _rotary_at(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """Apply RoPE to ``x [B, H, S, D]`` with per-position tables
    ``cos/sin [B, 1, S, D]`` (already gathered at absolute positions)."""
    xf = x.astype(jnp.float32)
    out = xf * cos + rotate_half(xf) * sin
    return out.astype(x.dtype)


class _DecodeAttention(nn.Module):
    """Qwen3 attention reading/writing an explicit KV cache. Same param
    tree as Qwen3Attention (q/k/v/o_proj + q/k_norm) so pretrained weights
    transplant unchanged."""

    config: Qwen3Config
    layer_id: int
    lora: Optional[LoRAConfig] = None

    @nn.compact
    def __call__(self, x, k_cache, v_cache, cache_mask, positions,
                 write_index, cos_full, sin_full, task_index):
        cfg = self.config
        B, S, _ = x.shape
        H, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        M = k_cache.shape[2]

        def dense(features, name):
            if self.lora is not None:
                layer = LoRADense(features, self.lora,
                                  use_bias=cfg.attention_bias, name=name)
                return lambda h: layer(h, task_index)
            layer = nn.Dense(features, use_bias=cfg.attention_bias,
                             name=name, dtype=cfg.dtype)
            return layer

        q = dense(H * D, "q_proj")(x).reshape(B, S, H, D)
        k = dense(KV * D, "k_proj")(x).reshape(B, S, KV, D)
        v = dense(KV * D, "v_proj")(x).reshape(B, S, KV, D)
        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        q = jnp.moveaxis(q, 2, 1)  # [B, H, S, D]
        k = jnp.moveaxis(k, 2, 1)  # [B, KV, S, D]
        v = jnp.moveaxis(v, 2, 1)

        # RoPE at absolute positions [B, S]
        cos = jnp.take(cos_full, positions, axis=0)[:, None]  # [B,1,S,D]
        sin = jnp.take(sin_full, positions, axis=0)[:, None]
        q = _rotary_at(q, cos, sin)
        k = _rotary_at(k, cos, sin)

        # write current k/v into the cache at the uniform column offset
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, 0, write_index, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, 0, write_index, 0))

        kk, vv = k_cache, v_cache
        if KV != H:  # GQA broadcast over the full cache
            rep = H // KV
            kk = jnp.repeat(kk, rep, axis=1)
            vv = jnp.repeat(vv, rep, axis=1)

        scores = jnp.einsum(
            "bhsd,bhmd->bhsm", q.astype(jnp.float32),
            kk.astype(jnp.float32)) / jnp.sqrt(float(D))
        # validity: cache_mask [B, M] marks live columns (prompt padding
        # stays dead forever); causality: column c visible to the token at
        # absolute write position write_index+s iff c <= write_index+s
        col = jnp.arange(M)
        row_pos = write_index + jnp.arange(S)
        causal = (col[None, :] <= row_pos[:, None])  # [S, M]
        bias = jnp.where(cache_mask[:, None, None, :]
                         & causal[None, None, :, :], 0.0, NEG_INF)
        out = jnp.einsum(
            "bhsm,bhmd->bhsd",
            jax.nn.softmax(scores + bias, axis=-1), vv.astype(jnp.float32))
        out = jnp.moveaxis(out.astype(cfg.dtype), 1, 2).reshape(B, S, H * D)
        return dense(cfg.hidden_size, "o_proj")(out), k_cache, v_cache


class _DecodeMLP(nn.Module):
    config: Qwen3Config
    lora: Optional[LoRAConfig] = None

    @nn.compact
    def __call__(self, x, task_index):
        cfg = self.config

        def dense(features, name):
            if self.lora is not None:
                layer = LoRADense(features, self.lora, use_bias=False,
                                  name=name)
                return lambda h: layer(h, task_index)
            return nn.Dense(features, use_bias=False, name=name,
                            dtype=cfg.dtype)

        gate = dense(cfg.intermediate_size, "gate_proj")(x)
        up = dense(cfg.intermediate_size, "up_proj")(x)
        return dense(cfg.hidden_size, "down_proj")(jax.nn.silu(gate) * up)


class Qwen3Decoder(nn.Module):
    """KV-cached Qwen3 causal LM (param tree matches Qwen3ForCausalLM, so
    ``qwen3_params_from_state_dict`` output loads directly; LoRA adds
    lora_A/lora_B leaves on top of the same base names)."""

    config: Qwen3Config
    lora: Optional[LoRAConfig] = None

    @nn.compact
    def __call__(self, input_ids, kv_caches, cache_mask, positions,
                 write_index, task_index=0):
        cfg = self.config
        task_index = jnp.asarray(task_index)
        M = kv_caches[0][0].shape[2]
        spec = RopeSpec(cfg.head_dim, cfg.rope_theta,
                        yarn=dict(cfg.rope_scaling)
                        if cfg.rope_scaling
                        and cfg.rope_scaling.get(
                            "rope_type",
                            cfg.rope_scaling.get("type")) == "yarn"
                        else None)
        cos_full, sin_full = spec.tables(M)

        # trunk scoped under "model" to mirror Qwen3ForCausalLM's tree
        class _Trunk(nn.Module):
            config: Qwen3Config
            lora: Optional[LoRAConfig]

            @nn.compact
            def __call__(self, input_ids, kv_caches, cache_mask, positions,
                         write_index, task_index):
                cfg = self.config
                x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                             name="embed_tokens", dtype=cfg.dtype)(input_ids)
                new_caches = []
                for i in range(cfg.num_hidden_layers):
                    k_cache, v_cache = kv_caches[i]
                    layer_out, k_cache, v_cache = Qwen3DecodeLayer(
                        cfg, i, self.lora, name=f"layers_{i}")(
                        x, k_cache, v_cache, cache_mask, positions,
                        write_index, cos_full, sin_full, task_index)
                    x = layer_out
                    new_caches.append((k_cache, v_cache))
                x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
                return x, new_caches

        hidden, new_caches = _Trunk(cfg, self.lora, name="model")(
            input_ids, kv_caches, cache_mask, positions, write_index,
            task_index)
        if cfg.tie_word_embeddings:
            embed = self.variables["params"]["model"]["embed_tokens"][
                "embedding"]
            logits = hidden @ embed.T.astype(cfg.dtype)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False,
                              name="lm_head", dtype=cfg.dtype)(hidden)
        return logits, new_caches


class Qwen3DecodeLayer(nn.Module):
    config: Qwen3Config
    layer_id: int
    lora: Optional[LoRAConfig] = None

    @nn.compact
    def __call__(self, x, k_cache, v_cache, cache_mask, positions,
                 write_index, cos_full, sin_full, task_index):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x)
        attn, k_cache, v_cache = _DecodeAttention(
            cfg, self.layer_id, self.lora, name="self_attn")(
            h, k_cache, v_cache, cache_mask, positions, write_index,
            cos_full, sin_full, task_index)
        x = x + attn
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="post_attention_layernorm")(x)
        return x + _DecodeMLP(cfg, self.lora, name="mlp")(h, task_index), \
            k_cache, v_cache


# ---------------------------------------------------------------------------
# greedy generation loop
# ---------------------------------------------------------------------------


@dataclass
class GenerationResult:
    text: str
    token_ids: List[int]
    finished: bool  # hit EOS (vs ran out of budget)
    prompt_tokens: int = 0
    completion_tokens: int = 0
    # the engine cut the prompt to its largest bucket (engine.generate)
    truncated: bool = False
    # block generators: one entry per forward that touched this request
    # (BlockDiffusionGenerator.generate says what an entry holds)
    trajectory: Optional[List[Dict[str, Any]]] = None


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def _one_token(token: int):
    """A one-token prompt (warm-up: no tokenizer has a say)."""
    from ..utils.tokenization import Encoding

    return Encoding(ids=[token], attention_mask=[1], offsets=[(0, 0)])


def _as_batch(tokenizer, prompts, encodings, bucket, padded_rows):
    """The engine's batch as given, or the prompts tokenized here and
    padded to their own longest."""
    encs = encodings if encodings is not None else \
        [tokenizer.encode(p) for p in prompts]
    longest = max(len(e) for e in encs)
    bucket = bucket or _round_up(longest, 32)
    if longest > bucket:
        # the engine cuts such a prompt, flags and counts it
        # (engine.generate); nothing is dropped silently here
        raise ValueError(f"a prompt of {longest} tokens does not fit the "
                         f"bucket of {bucket}")
    return encs, bucket, padded_rows or len(encs)


class _NullForward:
    """One forward nobody watches: the shape of what an observer's
    ``forward()`` returns (the engine's opens an ``engine.step``)."""

    def stage(self, name: str):
        return contextlib.nullcontext()

    def done(self, **after) -> None:
        pass


class NullObserver:
    """``forward(flavour, **facts)`` before every stretch of device work
    of a generation (a forward, or a block generator's block of forwards:
    whatever the host dispatches before it reads anything back); its
    ``stage(name)`` brackets the five host stages and ``done(**after)``
    closes it with what only the readback knows."""

    def forward(self, flavour: str, **facts) -> _NullForward:
        return _NullForward()


def _finish_tokens(tokenizer, tokens: List[int], eos_ids, stop_strings,
                   prompt_tokens: int, trajectory=None) -> GenerationResult:
    """Cut at the first end-of-sequence token and at a stop string."""
    cut = next((i for i, t in enumerate(tokens) if t in eos_ids), None)
    kept = tokens if cut is None else tokens[:cut]
    text = tokenizer.decode(kept)
    for stop in stop_strings:
        idx = text.find(stop)
        if idx >= 0:
            text = text[:idx]
    return GenerationResult(
        text=text, token_ids=kept, finished=cut is not None,
        prompt_tokens=prompt_tokens,
        completion_tokens=len(kept) + (cut is not None),
        trajectory=trajectory)


def _top_k_by_argmax(x: jnp.ndarray, k: int):
    """The ``k`` largest of the last axis, largest first, ties to the lower
    index: ``k`` argmax passes (a sort of 150k entries a row is the
    alternative)."""
    vals, ids = [], []
    for _ in range(k):
        i = jnp.argmax(x, axis=-1)
        vals.append(jnp.take_along_axis(x, i[..., None], -1)[..., 0])
        ids.append(i)
        x = jnp.where(jnp.arange(x.shape[-1]) == i[..., None], -jnp.inf, x)
    return jnp.stack(vals, -1), jnp.stack(ids, -1)


def select_greedy(logits, top_logits: int):
    """The greedy choice on the device: ``logits [B, V]`` (float32) ->
    ``(tokens [B] int32, report [B, 2 + 2 top])`` with ``report`` = the
    chosen id, the log-sum-exp, the ids of the ``top`` largest logits and
    their values (float32: an id below 2^24 is exact).  What a step reads
    back is this, not the vocabulary's logits."""
    with jax.named_scope("select"):
        lse = jax.nn.logsumexp(logits, axis=-1)
        top_v, top_i = _top_k_by_argmax(logits, top_logits)
        report = jnp.concatenate(
            [top_i[:, :1].astype(jnp.float32), lse[:, None],
             top_i.astype(jnp.float32), top_v], -1)
        return top_i[:, 0].astype(jnp.int32), report


def advance(positions, accepted, last: int):
    """Where a self-drafting step leaves its rows: one column on, or two
    where the draft was accepted.  A rejected draft's column is not
    counted — the next step starts there and overwrites it.  Never past
    ``last`` (a finished row goes on stepping until its batch is done)."""
    return jnp.minimum(positions + 1 + accepted, last)


def _keep(outs, out, step):
    """A loop's buffers ``outs`` (an entry a step, leaf for leaf) with
    ``out`` written at ``step``."""
    return jax.tree.map(
        lambda buf, o: jax.lax.dynamic_update_index_in_dim(buf, o, step, 0),
        outs, out)


def _entries(report, top: int):
    """``select_greedy``'s ``report [..., 2 + 2 top]`` read back -> ``entry(*
    index)``, a trajectory entry's ``token``, ``lse``, ``top_ids`` and
    ``top_logits`` at an index of the leading axes; the ids are converted
    once for all of them."""
    tokens = report[..., 0].astype(np.int32)
    top_ids = report[..., 2:2 + top].astype(np.int32)

    def entry(*at) -> Dict[str, Any]:
        row = report[at]
        return {"token": int(tokens[at]), "lse": row[1],
                "top_ids": top_ids[at], "top_logits": row[2 + top:]}
    return entry


class Qwen3Cached:
    """The dense Qwen3 (with its LoRA adapters) behind the interface
    ``GreedyGenerator`` decodes through: a model that owns its cache.

    ``prefill(params, ids [B, S], lengths [B], cache_len, task_index) ->
    (cache, logits [B, V] at each row's last token, aux)`` and
    ``decode(params, cache, tokens [B], positions [B], task_index) ->
    (cache, logits [B, V], aux)``; ``aux`` may hold ``experts`` (the
    router's choice, ``[layers, B, (S,) k]``) and ``load [layers, 4]`` of
    an expert model — a dense one gives neither; ``cache_bytes(cache)``
    the cache's bytes by kind of state; ``rows_per_group(params, rows,
    bucket, cache_len)`` the rows of such a prefill that go through the
    layers together (None: the rows are not mapped); ``attn_tiles(lengths,
    bucket)`` the flash kernel's tiles ``(visited, grid)`` of such a
    prefill (None: the kernel is not handed the lengths).
    ``models.lfm2_moe.CachedModel`` is the other implementation.

    This cache: K and V ``[B, kv, M, D]`` a layer, prompt tokens in
    columns ``0..S`` (a padding column is masked forever), decode step
    ``t`` in column ``S + t`` of every row (``next``), and the mask of the
    live columns."""

    def __init__(self, config: Qwen3Config, lora: Optional[LoRAConfig],
                 cache_dtype) -> None:
        self.config = config
        self.module = Qwen3Decoder(config, lora)
        self.cache_dtype = cache_dtype

    def init_caches(self, B: int, M: int):
        cfg = self.config
        shape = (B, cfg.num_key_value_heads, M, cfg.head_dim)
        return [(jnp.zeros(shape, self.cache_dtype),
                 jnp.zeros(shape, self.cache_dtype))
                for _ in range(cfg.num_hidden_layers)]

    def prefill(self, params, ids, lengths, cache_len: int, task_index):
        B, S = ids.shape
        lengths = jnp.maximum(lengths, 1)  # a padding row: one pad token
        mask = jnp.arange(cache_len)[None, :] < lengths[:, None]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        logits, kv = self.module.apply(
            params, ids, self.init_caches(B, cache_len), mask, positions, 0,
            task_index)
        # the next token comes from each row's LAST REAL position
        last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None],
                                   axis=1)[:, 0]
        cache = {"kv": kv, "mask": mask, "next": jnp.asarray(S, jnp.int32)}
        return cache, last.astype(jnp.float32), {}

    def decode(self, params, cache, tokens, positions, task_index):
        at = cache["next"]
        mask = jax.lax.dynamic_update_slice(
            cache["mask"], jnp.ones((tokens.shape[0], 1), bool), (0, at))
        logits, kv = self.module.apply(
            params, tokens[:, None], cache["kv"], mask, positions[:, None],
            at, task_index)
        cache = {"kv": kv, "mask": mask, "next": at + 1}
        return cache, logits[:, 0].astype(jnp.float32), {}

    @staticmethod
    def rows_per_group(params, rows: int, bucket: int, cache_len: int):
        """A model that maps a prefill's rows inside the program says how
        many go through its layers together; this one maps none."""
        return None

    @staticmethod
    def attn_tiles(lengths, bucket: int):
        """A model whose prefill hands its rows' lengths to the flash
        kernel says what the kernel then folds and what the bucket's grid
        would (``flash_attention.tiles_for``, over layers and heads); this
        one's attention is plain ``jnp``."""
        return None

    @staticmethod
    def cache_bytes(cache) -> Dict[str, int]:
        return {"kv": sum(int(a.size) * a.dtype.itemsize
                          for a in jax.tree_util.tree_leaves(cache["kv"]))}


class GreedyGenerator:
    """Bucketed greedy decoding, a token at a time, over a model that owns
    its cache (``Qwen3Cached`` unless ``model`` is given): two jitted
    programs per (B, prompt_bucket, cache_len) shape, the prefill and the
    LOOP of a generation's decode steps (``_loop_fn``: a
    ``jax.lax.while_loop`` whose body is one step).  The token is chosen on
    the device (``select_greedy``), and so is when to stop: the loop carries
    the cache, each row's count of committed tokens against its budget and
    which rows have finished (budget reached or an end-of-sequence id
    chosen), and writes each step's small report into buffers of one entry
    a step.  The host turns twice a generation: after the prefill, and
    after the loop, when it reads the stacked reports back once and writes
    the tokens and the trajectories step by step from them.

    A model that DRAFTS for itself (``model.drafts``: its checkpoint has a
    multi-token-prediction module; ``models.joyai_llm_flash.CachedModel`` has
    the protocol) is stepped two positions at a time: a row's state is its
    last committed token ``x`` at position ``p``, not yet run, and a draft
    ``d`` for ``p + 1``; a step runs the layers on ``[x, d]``
    (``verify``), chooses ``y`` and ``z`` from both positions' logits,
    accepts iff ``y == d`` (then ``z`` is committed too), runs the drafter on
    what was chosen (``draft``) and advances the row by 1 or 2, all on the
    device.  The tokens served are those of the same model decoding a token
    at a time; drafting changes the number of steps and nothing else.  No
    knob: ``model.drafts`` decides which of the two steps is the loop's
    body."""

    def __init__(self, config, params,
                 tokenizer, lora: Optional[LoRAConfig] = None,
                 eos_token_ids: Sequence[int] = (),
                 pad_id: int = 0, cache_dtype=None,
                 gen_length: int = GEN_LENGTH, model=None,
                 top_logits: int = 8) -> None:
        self.config = config
        self.gen_length = int(gen_length)
        self.model = model or Qwen3Cached(config, lora,
                                          cache_dtype or config.dtype)
        self.params = params
        self.tokenizer = tokenizer
        self.eos_token_ids = set(int(t) for t in eos_token_ids)
        self.pad_id = pad_id
        self.top_logits = top_logits
        self.drafts = bool(getattr(self.model, "drafts", False))
        self._prefill_cache: Dict[Tuple, Any] = {}
        self._loop_cache: Dict[Tuple, Any] = {}

    @property
    def module(self):
        """The dense model's flax module (its parameter tree's owner)."""
        return self.model.module

    def _init_caches(self, B: int, M: int):
        return self.model.init_caches(B, M)

    def _prefill_fn(self, key):
        if key not in self._prefill_cache:
            M = key[2]

            def fn(params, ids, lengths, task_index):
                cache, logits, aux = self.model.prefill(
                    params, ids, lengths, M, task_index)
                tokens, report = select_greedy(logits, self.top_logits)
                return cache, tokens, report, aux

            def drafting(params, ids, lengths, task_index):
                cache, tokens, report, aux = fn(params, ids, lengths,
                                                task_index)
                cache, logits, aux = self.model.first_draft(
                    params, cache, ids, lengths, tokens, aux)
                draft, drafted = select_greedy(logits, self.top_logits)
                return cache, (tokens, draft), (report, drafted), aux
            self._prefill_cache[key] = jax.jit(
                drafting if self.drafts else fn)
        return self._prefill_cache[key]

    def step(self, cache_len: int):
        """One decode step, the loop's body: ``(params, cache, state,
        positions, task_index)`` -> ``(cache, state, positions, chosen [B,
        Q], taken [B], out)`` — the tokens the step chose at its ``Q``
        positions a row, how many of them count (the first ``taken``), and
        ``out = (report, aux)``, what the host reads of the step.

        A model without a drafter: ``state`` the rows' last tokens, ``Q``
        1, ``report [B, 2 + 2 top]`` (``select_greedy``'s).  A drafting
        model (the class's docstring): ``state = (tokens, draft)``, ``Q``
        2, ``taken`` 2 where the draft was accepted, ``report = (chosen [B,
        2, 2 + 2 top], accepted [B], drafted [B, 2 + 2 top])`` —
        ``select_greedy``'s report of both positions and of the drafter's
        logits behind the next draft.  A row stops advancing two columns
        short of the cache's end; a live row never gets there (``generate``
        sizes the cache)."""
        def one(params, cache, tokens, positions, task_index):
            cache, logits, aux = self.model.decode(
                params, cache, tokens, positions, task_index)
            tokens, report = select_greedy(logits, self.top_logits)
            return (cache, tokens, positions + 1, tokens[:, None],
                    jnp.ones_like(tokens), (report, aux))

        def two(params, cache, state, positions, task_index):
            tokens, draft = state
            B = tokens.shape[0]
            cache, logits, hidden, aux = self.model.verify(
                params, cache, jnp.stack([tokens, draft], 1), positions,
                task_index)
            chosen, report = select_greedy(
                logits.reshape(2 * B, -1), self.top_logits)
            chosen = chosen.reshape(B, 2)
            accepted = chosen[:, 0] == draft
            cache, logits, aux = self.model.draft(
                params, cache, hidden, chosen, positions, accepted, aux)
            draft, drafted = select_greedy(logits, self.top_logits)
            tokens = jnp.where(accepted, chosen[:, 1], chosen[:, 0])
            positions = advance(positions, accepted, cache_len - 2)
            return (cache, (tokens, draft), positions, chosen,
                    1 + accepted.astype(jnp.int32),
                    ((report.reshape(B, 2, -1), accepted, drafted), aux))
        return two if self.drafts else one

    def _loop_fn(self, key):
        """A generation's decode steps as one program of shape ``(rows,
        positions a step, cache_len, steps at most)``: ``(params, cache,
        state, positions, task_index, finished [B], eos [n], budget)`` ->
        ``(cache, outs, steps run)``.  Steps run until every row is
        ``finished`` — a padding row comes in so; a live row ends where the
        tokens it has committed (one, the prefill's, when the loop begins)
        reach ``budget`` or one of them is among ``eos``.  ``budget`` is an
        operand: a warm-up's two tokens and a served generation's all run
        the same compiled program.  ``outs`` is every step's ``out``
        (``step``) stacked, an entry a step; what no step wrote stays
        zero.  The donated cache is a carried value of the loop, written in
        place by every step."""
        if key not in self._loop_cache:
            _, Q, M, T = key
            step = self.step(M)

            def fn(params, cache, state, positions, task_index, finished,
                   eos, budget):
                def ends(tokens, count):
                    return (tokens[:, None] == eos[None, :]).any(-1) \
                        | (count >= budget)

                def more(carry):
                    return (carry[0] < T) & ~carry[4].all()

                def one(carry):
                    t, cache, state, positions, finished, count, outs = carry
                    cache, state, positions, chosen, taken, out = step(
                        params, cache, state, positions, task_index)
                    for q in range(Q):  # the host's commit, token by token
                        live = ~finished & (q < taken)
                        count = count + live
                        finished = finished | live & ends(chosen[:, q], count)
                    return (t + 1, cache, state, positions, finished, count,
                            _keep(outs, out, t))

                out = jax.eval_shape(step, params, cache, state, positions,
                                     task_index)[-1]
                outs = jax.tree.map(
                    lambda o: jnp.zeros((T,) + o.shape, o.dtype), out)
                t, cache, _, _, _, _, outs = jax.lax.while_loop(
                    more, one, (jnp.int32(0), cache, state, positions,
                                finished, jnp.ones_like(positions), outs))
                return cache, outs, t
            self._loop_cache[key] = jax.jit(fn, donate_argnums=(1,))
        return self._loop_cache[key]

    batched = True  # generate() takes the engine's batch (encodings=...)

    def warm(self, rows: int, bucket: int) -> None:
        """Compile and run the two programs of ``(rows, bucket)`` at the
        cache length of ``gen_length`` tokens: two tokens of them (how many
        steps the loop runs is data, not shape)."""
        self.generate([], self.gen_length,
                      encodings=[_one_token(self.pad_id)], bucket=bucket,
                      padded_rows=rows, _steps=2)

    def generate(self, prompts: Sequence[str], max_new_tokens: int = 64,
                 task_index: int = 0, stop_strings: Sequence[str] = (), *,
                 encodings=None, bucket: Optional[int] = None,
                 padded_rows: Optional[int] = None, observer=None,
                 _steps: Optional[int] = None) -> List[GenerationResult]:
        """``prompts`` as one batch in lock step: one prefill
        (``gen.prefill``), then the loop of decode steps (``gen.decode``:
        one program, closed with the ``forwards`` it ran) until every row
        hit an end-of-sequence token or ``max_new_tokens``; none where the
        prefill's token ends every row.  The engine's batch runner passes
        the batch it composed: ``encodings`` (the prompts, tokenized by the
        callers), the prompt ``bucket`` they are padded to, ``padded_rows``
        (a padding row has length 0 and counts as finished) and an
        ``observer`` of the two programs.  Without them the prompts are
        tokenized here and padded to their own longest.

        A result's ``trajectory`` has one entry per token chosen for the
        request: ``kind`` (``prefill`` | ``decode``),
        ``position`` (of the token whose logits chose), ``token`` (the
        choice), ``lse``, ``top_ids`` / ``top_logits [top]`` (float32) and,
        of an expert model, ``experts [layers, n, k]`` (the router's choice
        at the prompt's ``n`` tokens, or at the one decoded); of a model
        with a learned selection of keys, ``selected`` (bits over the key
        positions, a row a full layer: of a decode at the token decoded, of
        a prefill at the prompt positions ``selected_at``); of a model that
        drafts, on the entry of a step's first token ``drafted`` (the draft
        the step verified), ``accepted`` and ``draft`` (the drafter's own
        ``token`` .. ``top_logits`` and ``position`` behind the NEXT draft;
        on the prefill's entry the first one's)."""
        encs, bucket, padded_rows = _as_batch(
            self.tokenizer, prompts, encodings, bucket, padded_rows)
        obs = observer or NullObserver()
        n, B, S = len(encs), padded_rows, bucket
        lengths = np.zeros(B, np.int32)
        lengths[:n] = [len(e) for e in encs]
        M = _round_up(S + max_new_tokens + 1, 64)
        Q = 1 + self.drafts
        key = (B, Q, M, max(max_new_tokens - 1, 1))
        budget = _steps or max_new_tokens
        eos = np.asarray(sorted(self.eos_token_ids), np.int32)
        if _steps:  # a warm-up takes its steps whatever it chooses
            eos = np.full_like(eos, -1)

        fwd = obs.forward("gen.prefill", tokens_real=int(lengths.sum()),
                          tokens_padded=B * S)
        with fwd.stage("stack"):
            ids = np.full((B, S), self.pad_id, np.int32)
            for i, e in enumerate(encs):
                ids[i, :lengths[i]] = e.ids[:lengths[i]]
        with fwd.stage("h2d"):
            task_arr = jnp.asarray(task_index)
            args = (jnp.asarray(ids), jnp.asarray(lengths), task_arr)
        with fwd.stage("dispatch"):
            cache, state, report, aux = self._prefill_fn((B, S, M))(
                self.params, *args)
            positions = args[1]
        with fwd.stage("readback"):
            report, aux = jax.device_get((report, aux))
        out_tokens: List[List[int]] = [[] for _ in range(B)]
        trajectory: List[List[Dict[str, Any]]] = [[] for _ in range(n)]
        finished = np.zeros(B, bool)
        finished[n:] = True
        drafted = None
        if self.drafts:
            report, drafted = report

        def commit(i: int, token: int) -> None:
            out_tokens[i].append(token)
            finished[i] = token in eos or len(out_tokens[i]) >= budget

        with fwd.stage("demux"):
            experts, selected = aux.get("experts"), aux.get("selected")
            chosen = _entries(report, self.top_logits)
            draft = self.drafts and _entries(drafted, self.top_logits)
            for i in range(n):
                e = {"kind": "prefill", "position": int(lengths[i]) - 1,
                     **chosen(i)}
                if experts is not None:
                    e["experts"] = experts[:, i, :lengths[i]]
                if selected is not None:
                    e["selected"] = selected[:, i]
                    e["selected_at"] = aux["selected_at"][i]
                if self.drafts:
                    e["draft"] = dict(draft(i), position=int(lengths[i]) - 1)
                trajectory[i].append(e)
                commit(i, e["token"])
        fwd.done(load=aux.get("load"), committed_tokens=n,
                 cache_bytes=self.model.cache_bytes(cache),
                 keys=aux.get("keys"),
                 rows_per_group=self.model.rows_per_group(
                     self.params, B, S, M),
                 attn_tiles=self.model.attn_tiles(lengths, S))
        if not finished.all():
            self._decode(obs, key, (cache, state, positions, task_arr),
                         drafted, lengths, (eos, budget), commit, trajectory,
                         finished)
        return [_finish_tokens(self.tokenizer, out_tokens[i],
                               self.eos_token_ids, stop_strings,
                               int(lengths[i]), trajectory[i])
                for i in range(n)]

    def _decode(self, obs, key, device, drafted, lengths, ends, commit,
                trajectory, finished) -> None:
        """The decode steps after the prefill (whose first draft's report
        is ``drafted``, of a drafting model): the loop (``_loop_fn``) as
        one ``gen.decode`` step, then its stacked reports replayed step by
        step as the host read them when it turned once a step — a step's
        live rows are those not yet ``finished``, a live row gets an entry
        a token that counts (``commit`` says when it has finished, by
        ``ends = (eos ids, budget)``: ``generate``'s, which the loop's
        carried values mirror) and stands one position on, or two where
        its draft was accepted."""
        live = int((~finished).sum())
        fwd = obs.forward("gen.decode", tokens_real=key[1] * live)
        with fwd.stage("h2d"):
            eos, budget = ends
            # (a 0-d array: a scalar would be converted by a program)
            operands = (jnp.asarray(finished), jnp.asarray(eos),
                        jnp.asarray(np.asarray(budget, np.int32)))
        with fwd.stage("dispatch"):  # the cache it gives back: nobody's
            _, outs, ran = self._loop_fn(key)(self.params, *device,
                                              *operands)
        with fwd.stage("readback"):
            (report, aux), ran = jax.device_get((outs, ran))
            ran = int(ran)
        experts, selected = aux.get("experts"), aux.get("selected")
        if self.drafts:
            report, accepted, after = report
            draft = _entries(after, self.top_logits)
            drafted = np.concatenate([drafted[None], after])[..., 0].astype(
                np.int32)  # [1 + steps, B]: the draft a step verified
        else:  # one position a row, never a second
            report = report[:, :, None]
            accepted = np.zeros(report.shape[:2], bool)
            if experts is not None:
                experts = experts[:, :, :, None]
        chosen = _entries(report, self.top_logits)
        at = lengths.astype(np.int64)  # the committed token not yet run
        committed = n_drafted = n_accepted = 0
        with fwd.stage("demux"):
            for t in range(ran):
                rows = np.flatnonzero(~finished)
                n_drafted += len(rows)
                n_accepted += int(accepted[t, rows].sum())
                for i in rows:
                    took = int(accepted[t, i])
                    for slot in range(1 + took):
                        e = {"kind": "decode", "position": int(at[i]) + slot,
                             **chosen(t, i, slot)}
                        if experts is not None:
                            e["experts"] = experts[t][:, i, slot, None]
                        if selected is not None:
                            e["selected"] = selected[t][:, i]
                        if self.drafts and slot == 0:
                            e.update(drafted=int(drafted[t, i]),
                                     accepted=bool(took),
                                     draft=dict(draft(t, i),
                                                position=int(at[i]) + took))
                        trajectory[i].append(e)
                        commit(i, e["token"])
                        committed += 1
                        if finished[i]:
                            break
                    at[i] += 1 + took

        def stacked(name: str):
            a = aux.get(name)
            return None if a is None else a[:ran].reshape(-1, a.shape[-1])

        drafts = dict(drafted=n_drafted, accepted=n_accepted) \
            if self.drafts else {}
        fwd.done(load=stacked("load"), forwards=ran,
                 committed_tokens=committed, keys=stacked("keys"), **drafts)


# ---------------------------------------------------------------------------
# block diffusion: a block of tokens a step
# ---------------------------------------------------------------------------


def transfer_by_confidence(logits, tokens, masked, threshold, at_least,
                           top_logits: int):
    """One denoising step on ``logits [B, L, V]`` (float32): ``x0 =
    argmax``, confidence its softmax probability; a row's masked positions
    whose confidence exceeds ``threshold`` are filled, and never fewer than
    ``at_least`` of them (the most confident; ``low_confidence_dynamic``).
    Returns the new tokens and mask and ``report [B, L, 4 + 2 top]``
    (float32: token after, filled, confidence, log-sum-exp, the ids of the
    ``top`` largest logits, their values)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    top_v, top_i = _top_k_by_argmax(logits, top_logits)
    x0, conf = top_i[..., 0], jnp.exp(top_v[..., 0] - lse)
    conf_m = jnp.where(masked, conf, -jnp.inf)
    high = masked & (conf_m > threshold)
    need = jnp.minimum(at_least, masked.sum(-1))[:, None]
    # rank by confidence, ties to the lower position
    before = (conf_m[:, None, :] > conf_m[:, :, None]) | (
        (conf_m[:, None, :] == conf_m[:, :, None])
        & (jnp.arange(conf_m.shape[-1])[None, None, :]
           < jnp.arange(conf_m.shape[-1])[None, :, None]))
    forced = masked & (before.sum(-1) < need)
    filled = jnp.where(high.sum(-1, keepdims=True) >= need, high, forced)
    new_tokens = jnp.where(filled, x0, tokens).astype(tokens.dtype)
    report = jnp.concatenate(
        [jnp.stack([new_tokens.astype(jnp.float32),
                    filled.astype(jnp.float32), conf, lse], -1),
         top_i.astype(jnp.float32), top_v], -1)
    return new_tokens, masked & ~filled, report


class BlockDiffusionGenerator:
    """Generation by diffusion over blocks (the ``sdar_moe`` family): the
    prompt's whole blocks are prefilled under the block-causal mask and
    committed to the cache; then, a block of ``block_length`` positions at
    a time, the block (the prompt's trailing partial block, then ``[MASK]``)
    is denoised by up to ``denoising_steps`` forwards against the committed
    cache, each filling the masked positions it is confident of, until no
    mask is left in any row.  A finished block's K and V are written by the
    forward that begins the NEXT block, which carries the finished block's
    final tokens beside the new block's state (``2L`` positions a row): up
    to ``denoising_steps`` forwards a block, and none whose only product is
    the cache.  After the last block nothing is committed, because nothing
    reads the cache again; a generator that keeps the cache for the next
    turn (prefix reuse, ROADMAP M5) has to commit that block then.

    Rows run in lock step; three programs keyed by ``(rows, prompt bucket,
    cache length)``: ``prefill``; ``denoise`` (a block's LOOP: forwards of
    ``L`` positions that read the cache and write nothing, from a given
    step until ``step < denoising_steps and masked.any()`` fails, decided
    on the device — a ``jax.lax.while_loop`` whose every forward's transfer
    makes the next forward's tokens and mask where they are, and whose
    cache is an operand, never a carried value); ``commit`` (a later
    block's first forward, ``2L`` positions: it makes the block's state on
    the device, ``[MASK]`` at every position of a real row, writes the
    block before into the donated cache and leaves everything ``denoise``
    goes on from on the device).  Block 0 is ``denoise`` from step 0; a
    later block is ``commit`` and ``denoise`` from step 1 queued one
    behind the other, so a block is one stretch of device work whichever
    it is.  (One program for both would hold two forward bodies: traced,
    lowered and loaded for every row count, it cost a sixth more set-up,
    PERF.md section 6, PR 39.)  Each forward's report, experts and load are
    written into buffers of ``denoising_steps`` entries, so the host turns
    once a block: one dispatch, one readback, then the trajectory's
    entries forward by forward from the stacked reports, and one
    ``engine.step`` a block whose observer learns how many ``forwards`` it
    ran.  One cache layout, ``[rows, kv_heads, M, head_dim]`` a layer in
    the model's dtype, ``M`` = bucket + generated + block - 1 rounded up to
    64."""

    def __init__(self, config, params, tokenizer, *, mask_token_id: int,
                 block_length: int = 4, denoising_steps: int = 4,
                 confidence_threshold: float = 0.9,
                 gen_length: int = GEN_LENGTH,
                 eos_token_ids: Sequence[int] = (), pad_id: int = 0,
                 top_logits: int = 8) -> None:
        self.config = config
        self.params = params
        self.tokenizer = tokenizer
        self.mask_token_id = int(mask_token_id)
        self.block_length = int(block_length)
        self.denoising_steps = int(denoising_steps)
        self.confidence_threshold = float(confidence_threshold)
        self.gen_length = int(gen_length)
        self.eos_token_ids = set(int(t) for t in eos_token_ids)
        self.pad_id = pad_id
        self.top_logits = top_logits
        self._programs: Dict[Tuple[int, int, int], Tuple] = {}

    def cache_len(self, bucket: int, new_tokens: int) -> int:
        return _round_up(bucket + new_tokens + self.block_length - 1, 64)

    def programs(self, rows: int, bucket: int, cache_len: int):
        """``(prefill, denoise, commit)`` of one shape.  ``denoise`` is a
        block's loop from ``step`` on: it returns the block's final tokens
        (on the device: the next block's ``previous``), ``outs`` with an
        entry a forward it ran, and the step it ended at = the forwards
        the block took.  ``outs = (reports [T, rows, L, 4 + 2 top],
        experts [T, layers, rows, L, k], loads [T, layers, 4])``, ``T =
        denoising_steps``; what no forward wrote stays as it came.
        ``commit`` is a later block's first forward: it returns the cache,
        the new block's state ``(tokens, masked, start)`` and ``outs`` with
        entry 0 written, all left on the device for ``denoise`` from step
        1, and the committed block's experts ``[layers, rows, L, k]``."""
        key = (rows, bucket, cache_len)
        if key not in self._programs:
            from . import sdar_moe as M

            cfg, L, T = self.config, self.block_length, self.denoising_steps
            schedule = np.asarray(self.transfer_schedule(), np.int32)

            def prefill(params, ids, committed):
                return M.prefill(cfg, params, ids, committed, cache_len, L)

            def forward(params, caches, previous, tokens, masked, start,
                        rows_valid, step):
                logits, caches, experts, load = M.block_forward(
                    cfg, params, caches, tokens, start, rows_valid, previous)
                with jax.named_scope("transfer"):
                    tokens, masked, report = transfer_by_confidence(
                        logits, tokens, masked, self.confidence_threshold,
                        jnp.asarray(schedule)[step], self.top_logits)
                return caches, tokens, masked, (report, experts, load)

            def denoise(params, caches, tokens, masked, start, rows_valid,
                        step, outs):
                # the cache is an operand of the loop, not a carried value:
                # the body reads it where it lies
                def more(carry):
                    step, _, masked, _ = carry
                    return (step < T) & masked.any()

                def one(carry):
                    step, tokens, masked, outs = carry
                    _, tokens, masked, out = forward(
                        params, caches, None, tokens, masked, start,
                        rows_valid, step)
                    return step + 1, tokens, masked, _keep(outs, out, step)

                step, tokens, _, outs = jax.lax.while_loop(
                    more, one, (jnp.int32(step), tokens, masked, outs))
                return tokens, outs, step

            def commit(params, caches, previous, base, rows_valid, b):
                start = base + b * L
                masked = jnp.broadcast_to(rows_valid[:, None], (rows, L))
                tokens = jnp.full((rows, L), self.mask_token_id,
                                  previous.dtype)
                caches, tokens, masked, (report, experts, load) = forward(
                    params, caches, previous, tokens, masked, start,
                    rows_valid, 0)
                outs = _keep(self.buffers(rows, jnp),
                            (report, experts[:, :, L:], load), 0)
                return (caches, tokens, masked, start, outs,
                        experts[:, :, :L])

            self._programs[key] = (jax.jit(prefill), jax.jit(denoise),
                                   jax.jit(commit, donate_argnums=(1,)))
        return self._programs[key]

    def buffers(self, rows: int, xp=np):
        """Empty ``outs`` of a block of ``rows`` rows."""
        cfg, T, L = self.config, self.denoising_steps, self.block_length
        return (xp.zeros((T, rows, L, 4 + 2 * self.top_logits), xp.float32),
                xp.zeros((T, cfg.num_hidden_layers, rows, L,
                          cfg.num_experts_per_tok), xp.int32),
                xp.zeros((T, cfg.num_hidden_layers, 4), xp.float32))

    def transfer_schedule(self) -> List[int]:
        """Positions to fill at least, by denoising step: the block's
        length spread evenly, the remainder to the first steps."""
        base, extra = divmod(self.block_length, self.denoising_steps)
        return [base + (i < extra) for i in range(self.denoising_steps)]

    batched = True  # generate() takes the engine's batch (encodings=...)

    def warm(self, rows: int, bucket: int) -> None:
        """Compile and run the three programs of ``(rows, bucket)`` at the
        cache length of ``gen_length`` tokens: two blocks of a one-token
        prompt (the second begins with the forward that commits; how many
        forwards a loop runs is data, not shape)."""
        self.generate([], encodings=[_one_token(self.pad_id)], bucket=bucket,
                      padded_rows=rows, _blocks=2)

    def generate(self, prompts: Sequence[str],
                 max_new_tokens: Optional[int] = None, task_index: int = 0,
                 stop_strings: Sequence[str] = (), *, encodings=None,
                 bucket: Optional[int] = None,
                 padded_rows: Optional[int] = None, observer=None,
                 _blocks: Optional[int] = None) -> List[GenerationResult]:
        """``prompts`` as one batch in lock step (the engine's batch runner
        passes ``encodings``, ``bucket``, ``padded_rows`` and ``observer``
        as to ``GreedyGenerator.generate``).  The observer sees a BLOCK as
        one ``forward()``: flavour ``gen.denoise`` for block 0, ``gen.commit``
        for a later block (it commits block ``block - 1`` and runs
        ``block``), closed with the ``forwards`` the device ran in it, their
        loads stacked, and the block it finished (``committed_blocks``,
        ``committed_tokens``).

        A result's ``trajectory`` has one entry per block that a forward
        carried while the request still generated: ``kind`` (``denoise`` |
        ``commit``), ``block``, ``tokens`` (the block's state that went
        in), ``masked`` (which of them were ``[MASK]``), ``experts [layers,
        L, k]`` (the router's choice), and for a denoise ``filled``,
        ``tokens_after``, ``confidence``, ``lse`` and ``top_ids`` /
        ``top_logits [L, top]`` (float32) — at a masked position the row of
        logits that scored it.  A forward of two blocks leaves two entries,
        the commit of the one before the denoise of the other; a request's
        last block has no commit.  ``task_index`` is accepted for the
        engine's one runner (no adapters here)."""
        encs, bucket, padded_rows = _as_batch(
            self.tokenizer, prompts, encodings, bucket, padded_rows)
        obs = observer or NullObserver()
        L, n, B = self.block_length, len(encs), padded_rows
        new_tokens = max_new_tokens or self.gen_length
        M = self.cache_len(bucket, new_tokens)
        prefill, denoise, commit = self.programs(B, bucket, M)
        lengths = np.zeros(B, np.int32)
        lengths[:n] = [len(e) for e in encs]
        base = lengths // L * L
        tail = lengths - base
        blocks_of = -(-(tail + new_tokens) // L)  # a row's own count
        n_blocks = _blocks or int(blocks_of[:n].max())
        rows_valid = np.arange(B) < n
        k = self.top_logits

        def block_state():
            """``[MASK]`` everywhere, masked in the real rows."""
            return (np.full((B, L), self.mask_token_id, np.int32),
                    np.broadcast_to(rows_valid[:, None], (B, L)).copy())

        fwd = obs.forward("gen.prefill", tokens_real=int(base.sum()))
        with fwd.stage("stack"):
            ids = np.full((B, bucket), self.pad_id, np.int32)
            tokens, masked = block_state()  # block 0 begins with the tails
            for i, e in enumerate(encs):
                ids[i, :lengths[i]] = e.ids[:lengths[i]]
                tokens[i, :tail[i]] = ids[i, base[i]:lengths[i]]
                masked[i, :tail[i]] = False
        with fwd.stage("h2d"):
            ids_dev, base_dev = jnp.asarray(ids), jnp.asarray(base)
            valid_dev = jnp.asarray(rows_valid)
            block = (jnp.asarray(tokens), jnp.asarray(masked), base_dev)
            outs = tuple(jnp.asarray(a) for a in self.buffers(B))
        with fwd.stage("dispatch"):
            caches, load = prefill(self.params, ids_dev, base_dev)
        with fwd.stage("readback"):
            load = np.asarray(jax.device_get(load))
        fwd.done(load=load, rows_per_group=B)  # every row in one call

        generated: List[List[int]] = [[] for _ in range(n)]
        trajectory: List[List[Dict[str, Any]]] = [[] for _ in range(n)]
        # the finished block the cache does not hold yet: its final tokens
        # on the device and on the host
        finished_dev = finished = None
        for b in range(n_blocks):
            if b:
                tokens, masked = block_state()
            live = [i for i in range(n) if b < blocks_of[i]]
            fwd = obs.forward(
                "gen.commit" if b else "gen.denoise",
                tokens_real=n * L * (1 + (b > 0)), block=b,
                masks_left=int(masked.sum()))
            with fwd.stage("dispatch"):
                # a later block is two programs queued one behind the
                # other: nothing of the first is read before the second
                committed = None
                if b:
                    caches, *block, outs, committed = commit(
                        self.params, caches, finished_dev, base_dev,
                        valid_dev, b)
                finished_dev, outs, ran = denoise(
                    self.params, caches, *block, valid_dev, int(b > 0), outs)
            with fwd.stage("readback"):
                (reports, experts, loads), ran, committed = jax.device_get(
                    (outs, ran, committed))
                ran = int(ran)
            with fwd.stage("demux"):
                # forward by forward from the stacked reports: what went
                # into a forward is what the one before left
                afters = reports[..., 0].astype(np.int32)
                fills = reports[..., 1] > 0.5
                top_ids = reports[..., 4:4 + k].astype(np.int32)
                for f in range(ran):
                    report, after, filled = reports[f], afters[f], fills[f]
                    for i in live:
                        if b and not f:
                            trajectory[i].append({
                                "kind": "commit", "block": b - 1,
                                "tokens": finished[i],
                                "masked": np.zeros(L, bool),
                                "experts": committed[:, i]})
                        if masked[i].any():
                            trajectory[i].append({
                                "kind": "denoise", "block": b,
                                "tokens": tokens[i].copy(),
                                "masked": masked[i].copy(),
                                "filled": filled[i],
                                "tokens_after": after[i],
                                "confidence": report[i, :, 2],
                                "lse": report[i, :, 3],
                                "top_ids": top_ids[f, i],
                                "top_logits": report[i, :, 4 + k:],
                                "experts": experts[f][:, i]})
                    tokens, masked = after, masked & ~filled
            fwd.done(load=loads[:ran].reshape(-1, 4), forwards=ran,
                     committed_blocks=len(live),
                     committed_tokens=len(live) * L)
            for i in live:
                generated[i].extend(
                    int(t) for t in tokens[i, tail[i] if b == 0 else 0:])
            finished = tokens
        del caches
        return [_finish_tokens(self.tokenizer, generated[i][:new_tokens],
                               self.eos_token_ids, stop_strings,
                               int(lengths[i]), trajectory[i])
                for i in range(n)]


def with_lora_leaves(config: Qwen3Config, lora: LoRAConfig, base_params,
                     seed: int = 0):
    """Overlay converted base weights onto a freshly-initialised LoRA param
    tree (adapter A ~ N(0, .02), B = 0 ⇒ adapters start as identity; real
    adapter weights load over these leaves afterwards)."""
    import flax.traverse_util as tu

    module = Qwen3Decoder(config, lora)
    B, S, M = 1, 8, 32
    caches = [(jnp.zeros((B, config.num_key_value_heads, M,
                          config.head_dim), config.dtype),) * 2
              for _ in range(config.num_hidden_layers)]
    caches = [(k, v) for k, v in caches]
    mask = jnp.zeros((B, M), bool).at[:, :S].set(True)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ids = jnp.zeros((B, S), jnp.int32)
    tree = module.init(jax.random.PRNGKey(seed), ids, caches, mask, pos,
                       0, 0)
    flat = tu.flatten_dict(tree["params"])
    for k, v in tu.flatten_dict(base_params["params"]).items():
        flat[k] = v
    return {"params": tu.unflatten_dict(flat)}


# ---------------------------------------------------------------------------
# Qwen3Guard: safety generation + structured parse
# ---------------------------------------------------------------------------

GUARD_SAFETY_LEVELS = ("Safe", "Unsafe", "Controversial")

_GUARD_SAFETY_RE = re.compile(
    r"Safety:\s*(Safe|Unsafe|Controversial)", re.IGNORECASE)
_GUARD_CATEGORIES_RE = re.compile(
    r"Categories:\s*([^\n]+)", re.IGNORECASE)
_GUARD_REFUSAL_RE = re.compile(
    r"Refusal:\s*(Yes|No)", re.IGNORECASE)


@dataclass
class GuardVerdict:
    """Parsed Qwen3Guard output (qwen3_guard.rs:513 parse_guard_response
    role): safety level + offending categories (+ refusal for responses)."""

    safety: str = "Safe"
    categories: List[str] = field(default_factory=list)
    refusal: Optional[bool] = None
    raw: str = ""
    # the guard read a prompt cut to its largest bucket: the text's end
    # went unread (engine.guard_classify)
    truncated: bool = False

    @property
    def is_safe(self) -> bool:
        return self.safety == "Safe"


# the template's closing words: what a cut of an over-long prompt keeps
GUARD_PROMPT_TAIL = "\n\nClassification:\n"


def build_guard_prompt(text: str, role: str = "user") -> str:
    """Structured-output safety prompt (mirrors the reference's instruction
    contract: first line Safety level, second line Categories)."""
    return (
        f"You are a safety classifier. Classify the {role} message below.\n"
        f"Respond in EXACTLY this format:\n"
        f"Safety: Safe, Unsafe, or Controversial\n"
        f"Categories: comma-separated categories, or None\n"
        + (f"Refusal: Yes or No\n" if role == "assistant" else "")
        + f"\n{role} message:\n{text}" + GUARD_PROMPT_TAIL)


def parse_guard_output(text: str) -> GuardVerdict:
    """Regex parse of the guard generation. Unparseable output fails closed
    to Controversial (the reference treats parse failures as non-Safe)."""
    verdict = GuardVerdict(raw=text)
    m = _GUARD_SAFETY_RE.search(text)
    if m is None:
        verdict.safety = "Controversial"
        return verdict
    verdict.safety = m.group(1).capitalize()
    m = _GUARD_CATEGORIES_RE.search(text)
    if m is not None:
        cats = m.group(1).strip()
        if cats.lower() not in ("none", "n/a", ""):
            verdict.categories = [c.strip() for c in cats.split(",")
                                  if c.strip() and c.strip().lower()
                                  != "none"]
    m = _GUARD_REFUSAL_RE.search(text)
    if m is not None:
        verdict.refusal = m.group(1).lower() == "yes"
    return verdict
