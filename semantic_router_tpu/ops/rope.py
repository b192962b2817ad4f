"""Rotary position embeddings with optional YaRN long-context scaling.

TPU-native reimplementation of the RoPE math used by the reference's
classifier encoders: default RoPE for ModernBERT global/local layers
(candle-binding/src/model_architectures/traditional/modernbert.rs) and
YaRN-scaled RoPE for the mmBERT-32K variants (SURVEY.md §5 "long-context";
reference init fns candle-binding/semantic-router.go:58-64). The YaRN
parameterization matches the published formula (NTK-by-parts interpolation +
attention-temperature mscale), so checkpoints trained with HF/torch YaRN load
bit-compatibly.

Everything here is shape-static and jit-friendly; tables are computed in
float32 and cast at application time (rounding behavior matches the HF
implementation, which forces float32 for the cos/sin tables).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


def default_inv_freq(head_dim: int, base: float) -> np.ndarray:
    return 1.0 / base ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)


def yarn_inv_freq(
    head_dim: int,
    base: float,
    factor: float,
    original_max_position_embeddings: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    attention_factor: Optional[float] = None,
    mscale: Optional[float] = None,
    mscale_all_dim: Optional[float] = None,
    truncate: bool = True,
) -> Tuple[np.ndarray, float]:
    """YaRN NTK-by-parts inverse frequencies + attention scaling factor.

    Numerically equivalent to HF `_compute_yarn_parameters`
    (transformers/modeling_rope_utils.py) so converted mmBERT-32K
    checkpoints reproduce reference logits.
    """

    def get_mscale(scale: float, m: float = 1.0) -> float:
        if scale <= 1.0:
            return 1.0
        return 0.1 * m * math.log(scale) + 1.0

    if attention_factor is None:
        if mscale and mscale_all_dim:
            attention_factor = float(
                get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim))
        else:
            attention_factor = get_mscale(factor)

    def find_correction_dim(num_rotations: float) -> float:
        return (head_dim * math.log(
            original_max_position_embeddings / (num_rotations * 2 * math.pi))
        ) / (2 * math.log(base))

    low = find_correction_dim(beta_fast)
    high = find_correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001

    pos_freqs = base ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip(
        (np.arange(head_dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolation_factor = 1.0 - ramp
    inv_freq = (inv_freq_interpolation * (1.0 - extrapolation_factor)
                + inv_freq_extrapolation * extrapolation_factor)
    return inv_freq, float(attention_factor)


def rope_tables(inv_freq: np.ndarray, seq_len: int,
                attention_scaling: float = 1.0,
                dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables of shape [seq_len, head_dim] (freqs duplicated across
    both halves, matching the rotate-half convention)."""
    positions = np.arange(seq_len, dtype=np.float64)
    freqs = np.outer(positions, inv_freq)  # [S, D/2]
    emb = np.concatenate([freqs, freqs], axis=-1)  # [S, D]
    cos = np.cos(emb) * attention_scaling
    sin = np.sin(emb) * attention_scaling
    return jnp.asarray(cos, dtype=dtype), jnp.asarray(sin, dtype=dtype)


def rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rotary(q: jnp.ndarray, k: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply RoPE. q/k: [..., S, D]; cos/sin: [S, D] (broadcast over leading
    dims). Rotation is performed in float32 and cast back — the float32
    table path is what the reference implementations use for stability."""
    orig_dtype = q.dtype
    cos = cos.astype(jnp.float32)
    sin = sin.astype(jnp.float32)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    q_out = qf * cos + rotate_half(qf) * sin
    k_out = kf * cos + rotate_half(kf) * sin
    return q_out.astype(orig_dtype), k_out.astype(orig_dtype)


def apply_rotary_front(q: jnp.ndarray, k: jnp.ndarray, cos: jnp.ndarray,
                       sin: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RoPE over the FIRST ``cos.shape[-1]`` dims of q's and k's last axis
    (a ``partial_rotary_factor`` below 1, or a head whose leading dims alone
    rotate): ``rotate_half`` pairs dim ``d`` with ``d + n / 2`` inside those
    ``n`` dims, the dims from ``n`` on pass through as they are.  Tables as
    wide as the head are ``apply_rotary``."""
    n = cos.shape[-1]
    if n == q.shape[-1]:
        return apply_rotary(q, k, cos, sin)
    q_front, k_front = apply_rotary(q[..., :n], k[..., :n], cos, sin)
    return (jnp.concatenate([q_front, q[..., n:]], -1),
            jnp.concatenate([k_front, k[..., n:]], -1))


@lru_cache(maxsize=256)
def _cached_spec(head_dim: int, base: float,
                 yarn_key: Optional[Tuple[Tuple[str, object], ...]]
                 ) -> Tuple[Tuple[float, ...], float]:
    if yarn_key is not None:
        yarn = dict(yarn_key)
        inv_freq, scaling = yarn_inv_freq(
            head_dim, base,
            factor=float(yarn["factor"]),
            original_max_position_embeddings=int(
                yarn.get("original_max_position_embeddings",
                         yarn.get("original_max_positions", 8192))),
            beta_fast=float(yarn.get("beta_fast", 32.0)),
            beta_slow=float(yarn.get("beta_slow", 1.0)),
            attention_factor=yarn.get("attention_factor"),
            mscale=yarn.get("mscale"),
            mscale_all_dim=yarn.get("mscale_all_dim"),
            truncate=bool(yarn.get("truncate", True)),
        )
        return tuple(inv_freq.tolist()), scaling
    return tuple(default_inv_freq(head_dim, base).tolist()), 1.0


@lru_cache(maxsize=512)
def _cached_tables(inv_freq_key: Tuple[float, ...], seq_len: int,
                   attention_scaling: float, dtype_name: str):
    # Cache NUMPY arrays, never jnp: a jnp array built while tracing under
    # jit would cache a tracer and leak it into later traces
    # (UnexpectedTracerError). As numpy constants they embed cleanly into
    # every trace.
    inv_freq = np.asarray(inv_freq_key, dtype=np.float64)
    positions = np.arange(seq_len, dtype=np.float64)
    freqs = np.outer(positions, inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)
    dtype = np.dtype(dtype_name) if dtype_name != "bfloat16" else np.float32
    cos = (np.cos(emb) * attention_scaling).astype(dtype)
    sin = (np.sin(emb) * attention_scaling).astype(dtype)
    return cos, sin


class RopeSpec:
    """Precomputed RoPE spec for one attention flavour (global or local).

    Spec and cos/sin tables are process-cached: every local layer shares one
    spec and every global layer another, and each (spec, seq_len) table is
    built exactly once per process (they are rebuilt per layer per trace
    otherwise — measurable in eager/parity paths)."""

    def __init__(self, head_dim: int, base: float,
                 yarn: Optional[dict] = None) -> None:
        self.head_dim = head_dim
        self.base = base
        yarn_key = tuple(sorted(yarn.items())) if yarn else None
        inv_freq_key, self.attention_scaling = _cached_spec(
            head_dim, float(base), yarn_key)
        self._inv_freq_key = inv_freq_key
        self.inv_freq = np.asarray(inv_freq_key, dtype=np.float64)

    def tables(self, seq_len: int, dtype=jnp.float32):
        return _cached_tables(self._inv_freq_key, int(seq_len),
                              float(self.attention_scaling),
                              jnp.dtype(dtype).name)

    def tables_scaled(self, seq_len: int, factor: float, dtype=jnp.float32):
        """Linear (position-interpolation) scaling: positions ÷ factor —
        Gemma3's global-layer rope scaling."""
        key = tuple(f / factor for f in self._inv_freq_key)
        return _cached_tables(key, int(seq_len),
                              float(self.attention_scaling),
                              jnp.dtype(dtype).name)
