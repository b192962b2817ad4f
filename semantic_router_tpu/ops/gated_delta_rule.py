"""The gated delta rule: a linear-attention layer's recurrence over a matrix
state a head, as a chunked scan (a prefill) and as one step (a decode).

Per head, with ``q_t, k_t [d_k]``, ``v_t [d_v]``, a log decay ``g_t <= 0``
and a write strength ``beta_t`` (``k_t`` of unit length; ``beta_t`` may
exceed 1: the transition then has negative eigenvalues), the state ``S
[d_k, d_v]`` moves a token at a time:

    S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;   o_t = S_t^T q_t

``gated_delta_step`` is that line.  ``chunk_gated_delta_rule`` computes the
same ``o`` and final ``S`` a CHUNK of ``C`` tokens at a time.  With ``gamma_i
= sum_{j<=i} g_j`` inside a chunk and ``S`` the state that enters it:

    A = strict_lower(diag(beta) (K K^T * exp(gamma_i - gamma_j)))
    T = (I + A)^-1 diag(beta);  W = T (K * exp(gamma));  U = T V
    V_new = U - W S
    O = (Q * exp(gamma)) S + lower(Q K^T * exp(gamma_i - gamma_j)) V_new
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V_new

Everything but the three lines with ``S`` depends on no state and is
computed for ALL chunks at once in plain ``jax.numpy``
(``_chunk_operands``); the pass over chunks is sequential and does four
small matrix products a chunk (``_scan_chunks`` on the CPU; on a TPU the
Pallas kernel ``_scan_kernel``: grid rows x heads, chunks innermost and
sequential, ``S`` in VMEM scratch from the first chunk to the last, never
in HBM between two).  ``(I + A)^-1``: ``A`` is strictly lower triangular,
so the inverse is a forward substitution — written for the diagonal blocks
of 16 row by row and merged upwards (``[[X11, 0], [-X22 A21 X11, X22]]``);
the product form ``(I - A)(I + A^2)(I + A^4)...`` is the same matrix in
exact arithmetic and loses every digit where keys repeat and ``beta`` nears
2 (``A^32`` of a chunk of equal keys has entries of 1e27).  A decay factor
is always ``exp`` of a difference of two ``gamma`` of one chunk with the
later one first, so it never exceeds 1.

Padding: rows are RIGHT-padded.  At positions ``>= lengths[row]`` the op
sets ``g = 0`` and ``beta = 0``: a padding token's row of ``T`` is zero, so
its ``U``, ``W`` and ``V_new`` are exact zeros whatever its q, k, v hold,
the state passes through and ``final_state`` IS the state at the row's true
last token, bit for bit whatever the padding holds.  Outputs there are
unspecified.

Precision: q, k, v come in the model's dtype (bfloat16 as served); ``g``,
``beta``, the cumulative decays, ``T``, ``W``, ``U``, the state and every
product with it are float32.  A matrix product of float32 operands is at
``Precision.HIGHEST`` (a TPU's default would round them to bfloat16: the
state would be float32 in name only); one whose operands are the bfloat16
inputs themselves (``K K^T``, ``Q K^T``) goes through the matrix unit once
and is exact (``_chunk_operands`` ``pairs``).  (Cutting a float32 matrix
into three bfloat16 pieces by ``astype`` for three exact passes against a
bfloat16 input does NOT work on a TPU: the compiler is allowed excess
precision, drops the round trip through bfloat16, and the two lower pieces
are zeros — the state read 3e-3 off, and the op was slower besides; my
chip run, PR 47.)  ``o`` comes back in v's dtype.

Layout on the TPU (looked at in the program compiled for a v5e, not before):
the kernel's operands are ``[rows * heads, chunks, C, d]`` float32 with a
block of one chunk, ``(1, 1, C, d)`` — the last two dims are the arrays'
own, which is what lets 96 (three quarters of a lane tile) and 192 (one
and a half) through Mosaic without padding them in HBM: the compiled
program holds them as ``f32[30,128,64,96]`` and ``f32[30,128,64,192]``
(``tests/test_tpu_compile.py``), tiled ``(8, 128)`` on the last two dims,
and the state ``[96, 192]`` float32 is VMEM scratch with no copy between
chunks.  ``(K * decay)^T`` is handed over already transposed (``[d_k,
C]``), so every product in the kernel is a plain ``[m, k] x [k, n]``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _flash

CHUNK = 64
_BASE = 16  # the diagonal blocks inverted row by row
_HI = jax.lax.Precision.HIGHEST


def gated_delta_step(state, q, k, v, g, beta):
    """One token a row: ``state [B, H, d_k, d_v]`` float32, ``q, k [B, H,
    d_k]``, ``v [B, H, d_v]``, ``g, beta [B, H]`` -> ``(o [B, H, d_v]
    float32, state)``.  Plain ``jax.numpy``, the two readings of the state
    along a vector written as products and sums (a matrix product of ONE
    row would go to the matrix unit in six passes: 0.6 ms a layer of 8 rows
    on a v5e where the state's bytes take 0.07)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s = state * jnp.exp(g.astype(f32))[..., None, None]
    u = beta.astype(f32)[..., None] * (v - jnp.sum(s * k[..., None], -2))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.sum(s * q[..., None], -2), s


def _inv_unit_lower(a):
    """``(I + a)^-1`` of strictly lower triangular ``a [..., C, C]``, ``C``
    a power-of-two multiple of ``_BASE`` (or below it).  Worked with the
    matrices' many (a chunk a head a row: 3,840 a served row) LAST, on the
    lanes, and every product written as products and sums: blocks of 16
    are a tenth of a lane tile and no work for the matrix unit (batched
    matrix products of them took 2.6 ms a served row-layer on a v5e, this
    form 1.1), the rows of a block in a loop of 16 steps (written out, the
    16 steps of twelve linear layers were 20 of a 16-layer prefill's 34 s
    of compiling)."""
    lead, C = a.shape[:-2], a.shape[-1]
    a = jnp.moveaxis(a.reshape((-1, C, C)), 0, -1)  # [C, C, n]
    b = min(_BASE, C)
    eye = jnp.eye(b, dtype=a.dtype)[:, None, :, None]  # [b, 1, b, 1]
    # the diagonal blocks, all at once: row i of X is e_i - a[i, :i] X[:i];
    # the rows not yet written are zeros, so the sum may run over all
    d = jnp.stack([a[p:p + b, p:p + b] for p in range(0, C, b)], 1)

    def row(i, x):  # x [b, C / b, b, n]
        d_i = jax.lax.dynamic_index_in_dim(d, i, 0, keepdims=False)
        e_i = jax.lax.dynamic_index_in_dim(eye, i, 0, keepdims=False)
        new = e_i - jnp.sum(jnp.moveaxis(d_i, 1, 0)[:, :, None] * x, 0)
        return jax.lax.dynamic_update_index_in_dim(x, new, i, 0)

    x = jax.lax.fori_loop(0, b, row, jnp.zeros_like(d))
    x = jnp.moveaxis(x, 0, 1)  # [C / b, b, b, n]

    def matmul(p, q):  # [m, i, j, n] x [m, j, k, n]
        return jnp.sum(p[:, :, :, None] * q[:, None], 2)

    while b < C:
        a21 = jnp.stack([a[p + b:p + 2 * b, p:p + b]
                         for p in range(0, C, 2 * b)])
        x11, x22 = x[0::2], x[1::2]
        x21 = -matmul(x22, matmul(a21, x11))
        x = jnp.concatenate([
            jnp.concatenate([x11, jnp.zeros_like(x11)], 2),
            jnp.concatenate([x21, x22], 2)], 1)
        b *= 2
    return jnp.moveaxis(x[0], -1, 0).reshape(lead + (C, C))


def _chunk_operands(q, k, v, g, beta, chunk: int):
    """What a chunk's three lines with the state need and no state decides,
    for every chunk at once: ``q, k [B, H, S, d_k]``, ``v [B, H, S, d_v]``,
    ``g, beta [B, H, S]`` float32 (``S`` a multiple of ``chunk``) ->
    ``(qg, w [.., N, C, d_k], kdT [.., N, d_k, C], u [.., N, C, d_v], p
    [.., N, C, C], decay [.., N])``, float32."""
    f32 = jnp.float32
    B, H, S, dk = q.shape
    N, C = S // chunk, chunk
    cut = lambda a: a.reshape((B, H, N, C) + a.shape[3:])  # noqa: E731

    exact = q.dtype == k.dtype == v.dtype == jnp.bfloat16

    def pairs(x, y):
        """``x y^T`` a chunk.  bfloat16 operands go to the matrix unit as
        they are, ONE pass with float32 sums: their products are exact in
        float32, and widening them first only buys ``HIGHEST``'s six."""
        if exact:
            return jnp.einsum("...ik,...jk->...ij", x, y,
                              preferred_element_type=f32)
        return jnp.einsum("...ik,...jk->...ij", x.astype(f32),
                          y.astype(f32), precision=_HI)

    q, k, v, g, beta = (cut(a) for a in (q, k, v, g, beta))
    kk, qk = pairs(k, k), pairs(q, k)
    g, beta = g.astype(f32), beta.astype(f32)
    gamma = jnp.cumsum(g, -1)  # [B, H, N, C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    # exp of a difference, the later position first: never above 1
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay_ij = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    a = beta[..., :, None] * kk * decay_ij * jnp.tril(
        jnp.ones((C, C), f32), -1)
    t = _inv_unit_lower(a) * beta[..., None, :]
    eg = jnp.exp(gamma)
    q, k, v = (x.astype(f32) for x in (q, k, v))
    w = jnp.einsum("...ij,...jk->...ik", t, k * eg[..., None], precision=_HI)
    u = jnp.einsum("...ij,...jk->...ik", t, v, precision=_HI)
    last = gamma[..., -1:]
    kdT = jnp.swapaxes(k * jnp.exp(last - gamma)[..., None], -1, -2)
    return (q * eg[..., None], w, kdT, u, qk * decay_ij,
            jnp.exp(last[..., 0]))


def _scan_chunks(qg, w, kdT, u, p, decay, state):
    """The sequential pass in plain ``jax.numpy``: ``lax.scan`` over the
    chunk axis.  Returns ``(o [B, H, N, C, d_v], final_state)``."""
    def chunk(s, xs):
        qg, w, kdT, u, p, decay = xs
        v_new = u - jnp.einsum("bhck,bhkv->bhcv", w, s, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", qg, s, precision=_HI) \
            + jnp.einsum("bhij,bhjv->bhiv", p, v_new, precision=_HI)
        s = decay[..., None, None] * s \
            + jnp.einsum("bhkc,bhcv->bhkv", kdT, v_new, precision=_HI)
        return s, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (qg, w, kdT, u, p, decay))
    state, o = jax.lax.scan(chunk, state, xs)
    return jnp.moveaxis(o, 0, 2), state


def _scan_kernel(qg_ref, w_ref, kdT_ref, u_ref, p_ref, decay_ref, s0_ref,
                 o_ref, sT_ref, s_scr):
    """One chunk of one (row, head); ``s_scr`` is the state, on the core
    from the head's first chunk to its last."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0]

    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=_HI)
    s = s_scr[...]
    v_new = u_ref[0, 0] - dot(w_ref[0, 0], s)
    o = dot(qg_ref[0, 0], s) + dot(p_ref[0, 0], v_new)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    s = decay_ref[0, 0] * s + dot(kdT_ref[0, 0], v_new)
    s_scr[...] = s

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        sT_ref[0] = s


def _scan_pallas(qg, w, kdT, u, p, decay, state, out_dtype,
                 interpret: bool = False):
    """The sequential pass as the kernel.  Same operands and results as
    ``_scan_chunks``."""
    B, H, N, C, dk = qg.shape
    dv = u.shape[-1]
    BH = B * H
    flat = lambda a: a.reshape((BH,) + a.shape[2:])  # noqa: E731
    # a chunk's decay as a row of the state's width: a (1, d_v) block
    decay = jnp.broadcast_to(decay.reshape(BH, N, 1, 1), (BH, N, 1, dv))

    def per_chunk(*dims):
        return pl.BlockSpec((1, 1) + dims, lambda bh, c: (bh, c, 0, 0))

    per_head = pl.BlockSpec((1, dk, dv), lambda bh, c: (bh, 0, 0))
    o, final = pl.pallas_call(
        _scan_kernel,
        grid=(BH, N),
        in_specs=[per_chunk(C, dk), per_chunk(C, dk), per_chunk(dk, C),
                  per_chunk(C, dv), per_chunk(C, C), per_chunk(1, dv),
                  per_head],
        out_specs=[per_chunk(C, dv), per_head],
        out_shape=[jax.ShapeDtypeStruct((BH, N, C, dv), out_dtype),
                   jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(flat(qg), flat(w), flat(kdT), flat(u), flat(p), decay, flat(state))
    return o.reshape(B, H, N, C, dv), final.reshape(B, H, dk, dv)


def chunk_gated_delta_rule(q, k, v, g, beta,
                           lengths: Optional[jnp.ndarray] = None,
                           initial_state: Optional[jnp.ndarray] = None,
                           chunk: int = CHUNK,
                           kernel: Optional[str] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``q, k [B, H, S, d_k]``, ``v [B, H, S, d_v]``, ``g, beta [B, H, S]``
    -> ``(o [B, H, S, d_v]`` in v's dtype, ``final_state [B, H, d_k, d_v]``
    float32``)``; ``lengths [B]``: the real tokens of each right-padded row
    (None: every token is real); ``initial_state``: the state that enters
    (None: zeros).  ``kernel``: None = the Pallas kernel on a TPU platform,
    plain ``jax.numpy`` elsewhere; ``"interpret"`` the kernel in the Pallas
    interpreter (the tests' oracle of the kernel's arithmetic), ``"jnp"``
    the plain pass."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    g, beta = g.astype(f32), beta.astype(f32)
    if lengths is not None:
        real = (jnp.arange(S)[None, :] < lengths[:, None])[:, None, :]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    pad = (-S) % chunk
    if pad:  # padding positions: g = beta = 0
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 3))
            for a in (q, k, v, g, beta))
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), f32)
    operands = _chunk_operands(q, k, v, g, beta, chunk)
    if kernel is None:
        # (the flash kernel's reading of the platform: one place to steer)
        kernel = "pallas" if _flash._platform_of(q) == "tpu" else "jnp"
    if kernel == "jnp":
        o, final = _scan_chunks(*operands, initial_state.astype(f32))
        o = o.astype(v.dtype)
    else:
        o, final = _scan_pallas(*operands, initial_state.astype(f32),
                                v.dtype, interpret=kernel == "interpret")
    return o.reshape(B, H, S + pad, dv)[:, :, :S], final
