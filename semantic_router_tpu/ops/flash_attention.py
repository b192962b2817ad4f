"""Pallas TPU flash attention with native sliding-window support.

The role of the reference's two long-context attention kernels in one
TPU-native kernel (SURVEY.md N8/N12):

- chunked_sdpa.rs (N8): O(n) memory via query-block streaming — here the
  standard flash online-softmax over K/V blocks.
- ort-ck-flash-attn (N12, C++/HIP Composable-Kernel FMHA): tiled MXU
  attention with *native sliding-window* masking for ModernBERT's local
  layers (no dense [1,1,S,S] mask materialisation) — here the window
  bounds the grid's K axis: a query block only ever visits the K/V blocks
  its window can touch, partial blocks are masked in-register.

Layout: q/k/v reshaped to [B*H, S, D]; grid = (B*H, Sq/block_q, n_kv)
with the K axis innermost.  K and V stream through VMEM one
(block_k, D) block per grid step (never a whole sequence — 32K tokens of
K+V would not fit v5e's 16 MiB scoped VMEM); the online-softmax state
(m/l/acc, fp32) lives in VMEM scratch across the K steps of one query
block.  Padding arrives as a per-(B) additive key bias [B, 1, Sp],
indexed by bh // H.  A caller that knows its rows are RIGHT-padded hands
their real ``lengths [B]`` too (their ``_row_ends``, by row and head, are
a scalar-prefetch operand that the index maps and the kernel read at
``bh``): the grid stays the bucket's, but a query block past its row's
end and a K block past it are never folded nor copied in — ``tiles_for``
counts what that leaves.  Without ``lengths`` the call is the program it
was before they existed.

Blocks: ``blocks_for`` picks (block_q, block_k) from the call's padded
sequence length and its window — constants of this module, each from a
v5e measurement; no file, environment variable or config key has a say.

``flash_attention`` is the public entry: the Pallas kernel on TPU, the
chunked JAX path on CPU (same semantics; interpret mode on CPU is the
numerics oracle in tests).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, chunked_sdpa, padding_bias, sdpa, \
    sliding_window_bias

# Blocks the rule may pick, largest first.  Every time below is one call
# on a TPU v5e at the served geometry, [B*12, S, 64] float32, B = 1 unless
# said, by benchmarks/flash_bench.py's block sweep (PERF.md section 6,
# PR 26).  bfloat16 operands are widened on arrival and rank the pairs
# alike: the pair chosen here is within 8% of their best at every shape.
LISTED_BLOCKS = (1024, 512, 256, 128)
# window == 0 and causal calls.  The kernel pays per grid step, not per
# operation: S = 8192 takes 25.97 ms at 128x128, 5.43 at 512x512, 3.08 at
# 512x1024, 2.90 at 1024x1024 (B = 8: 227.9 / 46.7 / 27.1 / 25.1; S = 32768:
# 449 / 89.9 / 50.7 / 47.1; S = 2048: 1.52 / 0.357 / 0.213 / 0.201).
# 1024x2048 is no faster (3.15) and is refused inside larger programs: it
# needs 14 MiB of the 16 MiB of scoped VMEM, 1024x1024 needs 10.
GLOBAL_BLOCKS = (1024, 1024)
# window > 0.  The band already bounds the K axis and a wide block_k
# visits keys the band excludes, yet two wide steps a query block beat six
# narrow ones: S = 8192 takes 1.50 ms at 128x128, 1.96 at 512x128, 1.10 at
# 512x512, 0.863 at 256x512, 0.862 at 512x1024, 1.18 at 1024x1024 (B = 8:
# 14.2 / 17.5 / 10.4 / 9.08 / 8.50 / 11.1; S = 32768: 7.06 / 8.75 / 5.18 /
# 4.55 / 4.25 / 5.59).  256x512 over 512x1024: 1% of a trunk forward
# slower, and a 22-layer program compiles 2 s sooner (9.7 s against 11.6).
WINDOW_BLOCKS = (256, 512)


def blocks_for(seq_len: int, window: int = 0) -> tuple:
    """(block_q, block_k) of a call, from its shape alone.  The sequence
    is padded to the next multiple of 128 and never further: one no longer
    than the table's block is ONE block of that padded length, a longer
    one takes the largest listed block that divides it."""
    padded = -(-seq_len // 128) * 128

    def fit(want: int) -> int:
        if padded <= want:
            return padded
        return next(b for b in LISTED_BLOCKS
                    if b <= want and padded % b == 0)

    want_q, want_k = WINDOW_BLOCKS if window > 0 else GLOBAL_BLOCKS
    return fit(want_q), fit(want_k)


def _kv_start(qi, *, block_q: int, block_k: int, window: int, xp=jnp):
    """First K block a query block attends to (0 unless windowed)."""
    if window > 0:
        return xp.maximum(qi * block_q - window // 2, 0) // block_k
    return 0


def _row_ends(lengths, *, block_q: int, block_k: int, xp=jnp):
    """Of right-padded rows of ``lengths`` real tokens: ``(the last query
    block that holds one, one past the last K block that does)``."""
    return (xp.maximum(lengths - 1, 0) // block_q,
            (lengths + block_k - 1) // block_k)


def _kv_stop(qi, *, block_q: int, block_k: int, n_kb: int, window: int,
             causal: bool, ends=None, xp=jnp):
    """One past the last K block a query block attends to.  (Block-causal
    calls need no rule of their own: ``block_q`` is a multiple of
    ``causal_block``, so a query block's last position ends its group.)
    A causal call's band ends at the diagonal, windowed or not.
    ``ends``: the ``_row_ends`` of the query block's row — no K block past
    its real tokens, and none at all for a query block past them."""
    last_q = qi * block_q + block_q - 1
    if causal:
        stop = last_q // block_k + 1
    elif window > 0:
        stop = xp.minimum((last_q + window // 2) // block_k + 1, n_kb)
    else:
        stop = n_kb
    if ends is None:
        return stop
    q_last, kv_end = ends  # (an empty row's kv_end is 0)
    return xp.where(qi <= q_last, xp.minimum(stop, kv_end), 0)


def _kv_steps(n_kb: int, *, block_q: int, block_k: int, window: int,
              causal: bool) -> int:
    """K steps per query block (the grid's innermost extent): every K
    block when global/causal, only the blocks one window can straddle
    when windowed."""
    if window <= 0:
        return n_kb
    return min(n_kb, (block_q - 1 + (1 if causal else 2) * (window // 2))
               // block_k + 2)


def tiles_for(seq_len: int, window: int, causal: bool, lengths) -> tuple:
    """``(visited, grid)`` of a call at the rule's blocks over right-padded
    rows of ``seq_len`` positions: the (query block, K block) pairs a head
    folds when the call is handed the rows' real ``lengths``, summed over
    the rows, and those it folds without them (what the bucket's grid
    makes of rows taken as full).  On the host, by the kernel's own
    ``_kv_start`` / ``_kv_stop``."""
    block_q, block_k = blocks_for(seq_len, window)
    padded = -(-seq_len // math.lcm(block_q, block_k)) \
        * math.lcm(block_q, block_k)
    geom = dict(block_q=block_q, block_k=block_k, window=window, xp=np)
    n_kb = padded // block_k
    steps = _kv_steps(n_kb, block_q=block_q, block_k=block_k, window=window,
                      causal=causal)
    qi = np.arange(padded // block_q)[None, :]

    def folds(lengths) -> int:
        stop = _kv_stop(qi, n_kb=n_kb, causal=causal, **geom,
                        ends=_row_ends(lengths, block_q=block_q,
                                       block_k=block_k, xp=np))
        return int(np.clip(stop - _kv_start(qi, **geom), 0, steps).sum())

    lengths = np.asarray(lengths, np.int64).reshape(-1, 1)
    return folds(lengths), lengths.size * folds(np.int64(padded))


def causal_tiles(seq_len: int, lengths, layers) -> tuple:
    """``tiles_for`` summed over a prefill's causal attention layers, each
    ``(heads, window)``: the tiles of a head times the layer's heads."""
    tiles = [[heads * t for t in tiles_for(seq_len, window, True, lengths)]
             for heads, window in layers]
    return tuple(map(sum, zip(*tiles)))


def _flash_kernel(*refs, scale: float, block_q: int, block_k: int,
                  n_kb: int, window: int, causal: bool, causal_block: int,
                  selected: bool, ragged: bool = False):
    """One (bh, q-block, kv-step) program: fold one K/V block into the
    query block's online-softmax state.  ``selected``: a
    ``(block_q, block_k)`` block of the per-query selection comes before
    the output (0 = this query does not see this key).  ``ragged``: the
    call was handed its rows' lengths, whose ``_row_ends [2, BH]`` then
    come first (scalar prefetch); a query block past its row's end folds
    nothing and writes zeros."""
    ends = None
    if ragged:
        ends = (refs[0][0, pl.program_id(0)], refs[0][1, pl.program_id(0)])
        refs = refs[1:]
    q_ref, k_ref, v_ref, bias_ref, *rest = refs
    sel_ref = rest[0] if selected else None
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    qi = pl.program_id(1)
    j = pl.program_id(2)
    kb = _kv_start(qi, block_q=block_q, block_k=block_k,
                   window=window) + j
    stop = _kv_stop(qi, block_q=block_q, block_k=block_k, n_kb=n_kb,
                    window=window, causal=causal, ends=ends)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(kb < stop)
    def _fold():
        q = q_ref[0].astype(jnp.float32) * scale           # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                   # [Bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [Bq, Bk]
        s = s + bias_ref[0]                                # [1, Bk]
        if selected:
            s = jnp.where(sel_ref[0] != 0, s, NEG_INF)
        shape = (block_q, block_k)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                        0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                        1)
        if window > 0:
            s = jnp.where(jnp.abs(q_pos - k_pos) <= window // 2, s,
                          NEG_INF)
        if causal:
            # causal_block > 1: causal across groups of that many
            # positions, bidirectional inside one (block diffusion)
            s = jnp.where(q_pos // causal_block >= k_pos // causal_block,
                          s, NEG_INF)
        m = m_ref[...]                                     # [Bq, 1]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * correction + p.sum(axis=1,
                                                     keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)  # fully-masked rows stay finite
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           key_padding_mask: Optional[jnp.ndarray] = None,
                           window: int = 0, causal: bool = False,
                           causal_block: int = 1,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           select: Optional[jnp.ndarray] = None,
                           lengths: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """q/k: [B, H, S, D], v: [B, H, S, Dv] (the output's head size; a
    latent-attention layer's differs from D); key_padding_mask: [B, S]
    (1 = real token).
    ``window``: ModernBERT-style full window width (0 = global); with
    ``causal`` the band's upper half is gone, so a query sees the
    ``window // 2 + 1`` latest keys, itself among them.
    ``select``: [B, S, S] int8, shared by the heads: query i sees key j
    only where ``select[b, i, j]`` is not 0 (a learned sparse selection),
    on top of every other rule.
    ``causal_block``: with ``causal``, key j is visible to query i iff
    ``j // causal_block <= i // causal_block`` (1 = plain causal).
    ``lengths``: [B] int32, the real tokens of each RIGHT-padded row (what
    ``key_padding_mask``, which still goes, says position by position): a
    query block past a row's end is not folded and comes back as zeros, a
    K block past it is never visited; every real position's output is bit
    for bit the call's without them.
    ``block_q`` / ``block_k``: None = the shape's own (``blocks_for``).
    ``interpret``: None = the Pallas interpreter on a CPU platform (so the
    same call site runs in tests), the compiled kernel everywhere else."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = D ** -0.5
    if block_q is None or block_k is None:
        rule_q, rule_k = blocks_for(S, window)
        block_q, block_k = block_q or rule_q, block_k or rule_k
    if block_q % causal_block:
        raise ValueError(f"block_q {block_q} is no multiple of "
                         f"causal_block {causal_block}")
    pad = (-S) % math.lcm(block_q, block_k)
    Sp = S + pad
    if pad:
        zq = ((0, 0), (0, 0), (0, pad), (0, 0))
        q = jnp.pad(q, zq)
        k = jnp.pad(k, zq)
        v = jnp.pad(v, zq)
        if select is not None:
            select = jnp.pad(select, ((0, 0), (0, pad), (0, pad)))
    if key_padding_mask is None:
        bias = jnp.zeros((B, Sp), jnp.float32)
        if pad:
            bias = bias.at[:, S:].set(NEG_INF)
    else:
        mask = key_padding_mask
        if pad:
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        bias = (1.0 - mask.astype(jnp.float32)) * NEG_INF

    BH = B * H
    qf = q.reshape(BH, Sp, D)
    kf = k.reshape(BH, Sp, D)
    vf = v.reshape(BH, Sp, Dv)
    # [B, 1, Sp]: a (1, 1, BLOCK_K) block then satisfies the TPU tiling
    # rule (second-to-last block dim equals the array's) at any batch
    bias = bias[:, None, :]

    n_kb = Sp // block_k
    geom = dict(block_q=block_q, block_k=block_k, window=window)
    n_kv = _kv_steps(n_kb, causal=causal, **geom)
    kernel = functools.partial(
        _flash_kernel, scale=scale, n_kb=n_kb, causal=causal,
        causal_block=causal_block, selected=select is not None,
        ragged=lengths is not None, **geom)

    # index maps: (bh, qi, j), then the prefetched row ends when the call
    # has them
    def q_block(bh, qi, *ends):
        # a query block past its row's end re-names the last real one
        # (the OUTPUT's block is always its own: it is written, as zeros)
        return jnp.minimum(qi, ends[0][0, bh]) if ends else qi

    def kv_block(bh, qi, j, *ends):
        # steps past the query block's last K block re-name that block:
        # Pallas skips the copy when the block index does not change.
        # Every step of a query block past its row's end re-names the
        # last K block of the last real one: nothing is copied in for it
        row = (ends[0][0, bh], ends[0][1, bh]) if ends else None
        real = jnp.minimum(qi, row[0]) if ends else qi
        last = _kv_stop(real, n_kb=n_kb, causal=causal, ends=row,
                        **geom) - 1
        at = jnp.minimum(_kv_start(real, **geom) + j, last)
        if not ends:
            return at
        return jnp.maximum(jnp.where(qi == real, at, last), 0)

    operands = [qf, kf, vf, bias]
    in_specs = [
        pl.BlockSpec((1, block_q, D),
                     lambda bh, qi, j, *ends: (bh, q_block(bh, qi, *ends),
                                               0)),
        pl.BlockSpec((1, block_k, D),
                     lambda bh, qi, j, *ends: (
                         bh, kv_block(bh, qi, j, *ends), 0)),
        pl.BlockSpec((1, block_k, Dv),
                     lambda bh, qi, j, *ends: (
                         bh, kv_block(bh, qi, j, *ends), 0)),
        pl.BlockSpec((1, 1, block_k),
                     lambda bh, qi, j, *ends: (
                         bh // H, 0, kv_block(bh, qi, j, *ends))),
    ]
    if select is not None:
        operands.append(select.astype(jnp.int8))
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda bh, qi, j, *ends: (bh // H, q_block(bh, qi, *ends),
                                      kv_block(bh, qi, j, *ends))))
    grid = dict(
        grid=(BH, Sp // block_q, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, Dv),
                               lambda bh, qi, j, *ends: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ])
    if lengths is not None:
        # per (row, head), so that no map divides by H at every grid step
        operands.insert(0, jnp.repeat(jnp.stack(_row_ends(
            lengths.astype(jnp.int32), block_q=block_q, block_k=block_k)),
            H, axis=1))
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **grid))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((BH, Sp, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        **grid,
    )(*operands)
    return out.reshape(B, H, Sp, Dv)[:, :, :S, :]


def _platform_of(x) -> str:
    """Platform the call will run on.  Under jit ``x`` is a tracer (whose
    ``devices()`` raises), and jit compiles for the default backend."""
    if isinstance(x, jax.core.Tracer) or not hasattr(x, "devices"):
        return jax.default_backend()
    return x.devices().pop().platform


def flash_attention_sharded(q: jnp.ndarray, k: jnp.ndarray,
                            v: jnp.ndarray, key_padding_mask: jnp.ndarray,
                            mesh, batch_axis: str = "dp",
                            head_axis: Optional[str] = "tp",
                            lengths: Optional[jnp.ndarray] = None,
                            **kernel_kw) -> jnp.ndarray:
    """The kernel under a serving mesh.  GSPMD cannot partition a Mosaic
    kernel ("wrap the call in a shard_map"), so each (batch, head) shard
    runs it on its own rows and heads — attention needs no collective
    across either axis.  B must divide by mesh[batch_axis]; heads shard
    over ``head_axis`` only when it divides H (else every tensor rank
    computes all heads); ``lengths [B]`` shard with the rows."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    h_axis = head_axis if (head_axis in mesh.shape
                           and mesh.shape[head_axis] > 1
                           and q.shape[1] % mesh.shape[head_axis] == 0) \
        else None
    qspec = P(batch_axis, h_axis, None, None)
    operands, specs = [key_padding_mask], [P(batch_axis, None)]
    if lengths is not None:
        operands.append(lengths)
        specs.append(P(batch_axis))
    fn = shard_map(
        lambda q, k, v, m, *lens: flash_attention_pallas(
            q, k, v, m, lengths=lens[0] if lens else None, **kernel_kw),
        mesh=mesh, in_specs=(qspec, qspec, qspec, *specs),
        out_specs=qspec, check_vma=False)
    return fn(q, k, v, *operands)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    key_padding_mask: Optional[jnp.ndarray] = None,
                    window: int = 0, causal: bool = False,
                    causal_block: int = 1,
                    scale: Optional[float] = None, mesh=None,
                    batch_axis: str = "dp",
                    head_axis: Optional[str] = "tp",
                    select: Optional[jnp.ndarray] = None,
                    lengths: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Dispatch: the Pallas kernel on a TPU platform (per shard when the
    model serves under ``mesh``); the chunked JAX path on CPU.  ``select``
    (``flash_attention_pallas`` says what it is) goes with ``causal`` and
    no mesh.  ``lengths`` (there too) bound the kernel's work; the CPU
    paths need none, the mask says the same."""
    if select is not None and (mesh is not None or not causal):
        raise ValueError("a per-query selection is served for causal "
                         "calls without a mesh")
    if _platform_of(q) == "tpu":
        kw = dict(window=window, causal=causal, causal_block=causal_block,
                  scale=scale, lengths=lengths)
        if mesh is None:
            if select is not None:
                kw["select"] = select
            return flash_attention_pallas(q, k, v, key_padding_mask, **kw)
        if key_padding_mask is None:
            key_padding_mask = jnp.ones((q.shape[0], q.shape[2]),
                                        jnp.int32)
        return flash_attention_sharded(q, k, v, key_padding_mask, mesh,
                                       batch_axis, head_axis, **kw)
    if causal:
        S = q.shape[2]
        group = jnp.arange(S) // causal_block
        bias = jnp.where(group[None, :] <= group[:, None], 0.0,
                         NEG_INF)[None, None]
        if key_padding_mask is not None:
            bias = bias + padding_bias(key_padding_mask)
        if window > 0:
            bias = bias + sliding_window_bias(S, window)
        if select is not None:
            bias = bias + jnp.where(select != 0, 0.0, NEG_INF)[:, None]
        return sdpa(q, k, v, bias=bias, scale=scale)
    return chunked_sdpa(q, k, v, key_padding_mask=key_padding_mask,
                        window=window, scale=scale)
