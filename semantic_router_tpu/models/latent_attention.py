"""Latent attention (DeepSeek-V2's MLA) as every decoder here that has it
runs it: ``models/dots3_note.py`` (two geometries, a learned selection, a
gate a head) and ``models/joyai_llm_flash.py`` (every layer, two query
positions a decode step).  One implementation; what differs between the
families is in ``Geometry``.

``c_q = Norm(W_qa h)``, ``q_i = W_qb,i c_q = [q_i^nope ; RoPE(q_i^rope)]``;
``[c_kv' ; k^r'] = W_kva h``, ``c_kv = Norm(c_kv')``, ``k^r = RoPE(k^r')``
(one for all heads); ``[k_i^nope ; v_i] = W_kvb,i c_kv``; softmax over the
keys a query sees at scale ``1 / sqrt(nope + rope)``.  ``Norm`` is an
RMSNorm, times ``sqrt(rescale_to / rank)`` where the family rescales its
latents.

What a token leaves in the cache is ``[c_kv ; k^r]`` (``latents``).  A
prefill expands it to K and V a head (``keys_values``) for the flash
kernel; a decode step is the absorbed form (``absorbed``): ``q_i^nope``
goes through ``W_kvb,i``'s key half into the latent space, scores and the
weighted sum are taken against the cached latents, and the value half comes
last.  The same mathematics.

RoPE pairing.  ``interleave`` pairs dims ``(2j, 2j + 1)`` (DeepSeek-V3's
``rope_interleave``), else ``(j, j + rope / 2)`` (``rotate_half``).  The
interleaved form is computed as DeepSeek-V3's own code does: the rope dims
of q and of k are brought into the order evens-then-odds and rotated there
by ``rotate_half``, and STAY in that order — one fixed permutation of both
sides of every ``q^r . k^r``, which no score can see.  So a cache written
under ``interleave`` holds ``k^r`` in that order.

Precision: the matrices and the cache in ``dtype``; the norms, RoPE and
the softmax in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.rope import RopeSpec, apply_rotary
from .decoder_parts import NEG_INF


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The latent attention's numbers of one kind of layer, and what
    differs between the families that run it."""
    heads: int
    r_q: int
    r_kv: int
    nope: int
    rope: int
    v: int
    theta: float
    eps: float = 1e-6           # the latent norms'
    rescale_to: int = 0         # H: the normed latents times sqrt(H / rank)
    interleave: bool = False    # RoPE pairs (2j, 2j + 1)
    dtype: Any = jnp.bfloat16

    @property
    def scale(self) -> float:
        return float((self.nope + self.rope) ** -0.5)


def latent_norm(g: Geometry, x, w, rank: int):
    """``RMSNorm(x)`` (times the rescale), rounded once."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                             + g.eps) * w.astype(jnp.float32)
    if g.rescale_to:
        out = out * float(np.sqrt(g.rescale_to / rank))
    return out.astype(g.dtype)


def tables(g: Geometry, positions, table_len: int):
    """cos and sin ``[..., rope]`` at ``positions``."""
    cos_t, sin_t = RopeSpec(g.rope, g.theta).tables(table_len)
    return (jnp.take(cos_t, positions, axis=0),
            jnp.take(sin_t, positions, axis=0))


def rotate_back(x, cos, sin, n: int, interleave: bool = False):
    """RoPE on the dims of ``x``'s last axis from ``n`` on (under
    ``interleave`` they come back evens first, then odds)."""
    back = x[..., n:]
    if interleave:
        back = jnp.concatenate([back[..., 0::2], back[..., 1::2]], -1)
    back, _ = apply_rotary(back, back, cos, sin)
    return jnp.concatenate([x[..., :n], back], -1)


def queries(g: Geometry, p, h, cos, sin):
    """``h [B, S, H]``, ``cos``/``sin [B, S, rope]`` -> ``(c_q [B, S,
    r_q], q [B, heads, S, nope + rope])``."""
    with jax.named_scope("q"):
        c_q = latent_norm(g, h @ p["q_a"], p["q_a_norm"], g.r_q)
        q = jnp.einsum("bsr,rhd->bhsd", c_q,
                       p["q_b"].reshape(g.r_q, g.heads, g.nope + g.rope))
        return c_q, rotate_back(q, cos[:, None], sin[:, None], g.nope,
                                g.interleave)


def latents(g: Geometry, p, h, cos, sin):
    """What a token leaves in the cache: ``[c_kv ; k^r] [..., r_kv +
    rope]``."""
    kv = h @ p["kv_a"]
    c_kv = latent_norm(g, kv[..., :g.r_kv], p["kv_a_norm"], g.r_kv)
    return jnp.concatenate(
        [c_kv, rotate_back(kv[..., g.r_kv:], cos, sin, 0, g.interleave)], -1)


def keys_values(g: Geometry, p, lat):
    """``lat [B, S, r_kv + rope]`` -> ``k [B, heads, S, nope + rope]``,
    ``v [B, heads, S, v]``."""
    kvb = jnp.einsum("bsr,rhd->bhsd", lat[..., :g.r_kv],
                     p["kv_b"].reshape(g.r_kv, g.heads, g.nope + g.v))
    k_r = jnp.broadcast_to(lat[:, None, :, g.r_kv:],
                           kvb.shape[:3] + (g.rope,))
    return jnp.concatenate([kvb[..., :g.nope], k_r], -1), kvb[..., g.nope:]


def absorbed(g: Geometry, p, q, lat, seen):
    """A decode step's queries in the latent space: ``q [B, heads, Q, nope
    + rope]`` (``Q`` positions a row) against ``lat [B, M, r_kv + rope]``
    under ``seen [B, Q, M]`` -> ``[B, heads, Q, v]``."""
    kv_b = p["kv_b"].reshape(g.r_kv, g.heads, g.nope + g.v)
    q_lat = jnp.einsum("bhqn,rhn->bhqr", q[..., :g.nope], kv_b[..., :g.nope])
    qq = jnp.concatenate([q_lat.astype(g.dtype), q[..., g.nope:]], -1)
    s = jnp.einsum("bhqc,bmc->bhqm", qq, lat,
                   preferred_element_type=jnp.float32) * g.scale
    s = s + jnp.where(seen, 0.0, NEG_INF)[:, None]
    o_lat = jnp.einsum("bhqm,bmr->bhqr",
                       jax.nn.softmax(s, axis=-1).astype(g.dtype),
                       lat[..., :g.r_kv])
    return jnp.einsum("bhqr,rhv->bhqv", o_lat, kv_b[..., g.nope:])


def out_proj(g: Geometry, p, out):
    """``out [B, heads, S, v]`` -> ``W_o`` over the heads ``[B, S, H]``."""
    return jnp.einsum("bhsv,hvo->bso", out,
                      p["o_proj"].reshape(g.heads, g.v, -1))


def put_latents(cache, new, at):
    """``new [B, Q, C]`` into ``cache [B, M, C]`` at columns ``at [B]``
    .. ``at + Q``, a row at a time."""
    return jax.vmap(lambda c, n, a: jax.lax.dynamic_update_slice(
        c, n, (a, 0)))(cache, new, at)
