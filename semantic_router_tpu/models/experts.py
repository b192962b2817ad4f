"""The expert half of a decoder layer, written once for every sparse
decoder (``sdar_moe``, ``lfm2_moe``, ``dots3_note``, ``joyai_llm_flash``,
``laguna``): the two routers' choices, the ONE expert layer behind either
(``routed_experts``: sort, grouped matmuls, combine, ``load``), the dense
SwiGLU, and the second half of a layer around them (``feed_forward``).

A model keeps what is its own — its ``route`` (which router, which
numbers), its ``moe`` (what stands beside the routed experts: nothing, a
shared expert, a gated one) — and hands its ``moe`` to ``feed_forward``.

The expert layer is told which experts it holds (``held = (first,
count)``): the router keeps its published width and its experts per token,
the routed (token, expert) pairs are sorted by expert and only the pairs of
experts held here go through the grouped matmul; what an expert that lives
elsewhere would add is left out (on one chip there is no exchange, and no
stand-in for one).  No token is dropped and nothing is padded to a
capacity.  The experts are stacked ``[experts held, H, 2I]`` / ``[experts
held, I, H]`` arrays that a grouped matmul indexes by group.

Scopes on the device timeline, under a layer's: ``mlp`` | ``moe``
(``moe/router`` and, of a model with one, ``moe/shared`` are the model's;
``moe/sort``, ``moe/gmm``, ``moe/combine`` are here).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .decoder_parts import rms_norm


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# (k, n) of an expert matrix -> (k tile, n tile) where a sweep on the chip
# beat the rule below: whole-K tiles at the lfm2_moe widths
# (benchmarks/lfm2_gmm_sweep.py, PERF.md section 6, PR 32: a prefill row's
# gate+up 3.23 -> 2.51 ms, down 2.30 -> 1.33 ms; a decode forward the same)
_SWEPT_TILES = {(2048, 3072): (2048, 1024), (1536, 2048): (1536, 1024)}


def _megablox(lhs, rhs, group_sizes):
    """The Pallas megablox kernel (interpreted on the CPU, for tests).
    Rows a tile: 128 reads a touched expert's matrices once for its handful
    of rows (256: 1.55 ms a layer of a block forward, 512: 3.06); from
    8192 rows on, 256 (a prefill: 11.2 against 11.7 ms)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[-1]
    tk, tn = _SWEPT_TILES.get((k, n), (min(1024, k), min(768, n)))
    tiling = (min(256 if m >= 8192 else 128, m), tk, tn)
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tiling, interpret=_on_cpu())


def _grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [m, k]`` rows sorted by group times ``rhs [groups, k, n]``.
    On a TPU the megablox kernel: at the published widths on a v5e
    (benchmarks/moe_gmm_bench.py, PERF.md section 6, PR 28) a block
    forward's 512 pairs over 90 touched experts take 1.30 ms a layer
    against 2.89 for ``jax.lax.ragged_dot`` (the experts' matrices alone
    are 1.04 ms of HBM), a 16 x 512 prefill 11.2 against 12.9.  On the CPU
    ``ragged_dot``, the same sums."""
    if _on_cpu():
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return _megablox(lhs, rhs, group_sizes)


def routed_experts(p, x, valid, top_e, top_w, held: Tuple[int, int], dtype):
    """The expert layer behind any router: ``x [T, H]``, ``valid [T]`` (a
    padding token routes nowhere), the router's choice ``top_e [T, k]``
    with its weights ``top_w [T, k]`` (float32), ``held = (first, count)``
    the experts whose matrices ``p["gate_up"] [count, H, 2I]`` and
    ``p["down"] [count, I, H]`` are.  The routed pairs are sorted by
    expert, the pairs of held experts go through the grouped matmuls, and
    each token's results come back weighted and summed.

    Each pair is moved once each way, in ``dtype``: ``x``'s rows are
    gathered into sorted order, and the grouped matmuls' rows are gathered
    back with the k-th choices of all tokens together (``[k, T, H]``: k
    on the major axis is a view; on a tiled axis it would be a copy), so
    that ONE fusion reads them and writes ``y``: drop, convert to float32,
    times the float32 weight, summed over k in index order, one rounding
    to ``dtype``.  A value converts the same before or after it is moved,
    so no float32 array of ``pairs`` rows is ever written.  Both gathers'
    indices are in range by construction (``mode="clip"``: no select
    against a fill value).  The rows past the held groups are DROPPED by a
    ``where``, not weighted by zero: the grouped matmul never wrote them,
    and whatever stands there may be NaN.

    Returns ``(y [T, H], load [4])`` with ``load`` = the busiest held
    expert's pairs, the pairs computed here, the number of held experts
    that got any, and the busiest's pairs over the mean."""
    T, H = x.shape
    k, I = top_e.shape[-1], p["down"].shape[-2]
    first, count = held
    with jax.named_scope("sort"):
        local = top_e - first
        here = (local >= 0) & (local < count) & valid[:, None]  # [T, k]
        group = jnp.where(here, local, count).reshape(-1)  # elsewhere: last
        order = jnp.argsort(group, stable=True)
        group_sizes = jnp.bincount(group, length=count + 1)[:count] \
            .astype(jnp.int32)
        xs = jnp.take(x, order // k, axis=0, mode="clip")
    with jax.named_scope("gmm"):
        gu = _grouped_matmul(xs, p["gate_up"], group_sizes)
        h = (jax.nn.silu(gu[:, :I].astype(jnp.float32))
             * gu[:, I:].astype(jnp.float32)).astype(dtype)
        ys = _grouped_matmul(h, p["down"], group_sizes)
    with jax.named_scope("combine"):
        # where pair (t, j) stands among the sorted rows, j-major
        back = jnp.argsort(order).reshape(T, k).T
        ys = jnp.take(ys, back.reshape(-1), axis=0, mode="clip") \
            .reshape(k, T, H)
        w = jnp.where(here, top_w, 0.0)
        y = sum(jnp.where(here[:, j, None], ys[j].astype(jnp.float32), 0.0)
                * w[:, j, None] for j in range(k))
    busiest, pairs = group_sizes.max(), group_sizes.sum()
    load = jnp.stack([busiest, pairs, (group_sizes > 0).sum(),
                      busiest * count / jnp.maximum(pairs, 1)]
                     ).astype(jnp.float32)
    return y.astype(dtype), load


def sum_loads(loads):
    """Per-group ``load [groups, layers, 4]`` of a mapped prefill
    (``models/mapped_prefill.py``) as one ``[layers, 4]``: every group's
    grouped matmul reads its own touched experts once, so pairs and experts
    touched add up over the groups; the busiest is the busiest of any
    group, the ratio the groups' mean."""
    return jnp.stack([loads[..., 0].max(0), loads[..., 1].sum(0),
                      loads[..., 2].sum(0), loads[..., 3].mean(0)], -1)


def softmax_route(x, router, k: int, norm: bool):
    """The softmax choice of every decoder that has one (``sdar_moe``,
    ``laguna``): ``x [T, H]`` -> ``(top_e [T, k], weights [T, k]
    float32)``.  ``p = softmax(x W)`` in float32 over ALL experts, the ``k``
    largest are chosen, their weights ``p`` there, over their sum if
    ``norm``."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if norm:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_e, top_p


def sigmoid_route(x, router, bias, k: int, norm: bool, scaling: float,
                  eps: float):
    """The sigmoid-and-bias choice of every decoder that has one
    (``lfm2_moe``, ``dots3_note`` and with it ``joyai_llm_flash``): ``x [T,
    H]`` -> ``(top_e [T, k], weights [T, k] float32)``.  ``s = sigmoid(x
    W)`` in float32; the ``k`` largest of ``s + bias`` are chosen (``bias``
    None: of ``s``); the weights are the UNBIASED ``s`` there, over ``their
    sum + eps`` if ``norm``, times ``scaling``.  The bias moves the choice
    and never the weights."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    pick = s if bias is None else s + bias
    _, top_e = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(s, top_e, -1)
    if norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + eps)
    return top_e, w * scaling


def swiglu(cfg, p, x):
    """``down(silu(gate x) * up x)`` of ``x [..., H]``, the product in
    float32, with ``p["gate_up"] [H, 2I]`` and ``p["down"] [I, H]``."""
    gu = x @ p["gate_up"]
    I = p["down"].shape[0]
    h = jax.nn.silu(gu[..., :I].astype(jnp.float32)) \
        * gu[..., I:].astype(jnp.float32)
    return h.astype(cfg.dtype) @ p["down"]


def expert_ids(top_e, n_experts: int):
    """A router's choice as the trajectory carries it: a byte an id where
    the ids fit one."""
    return top_e.astype(jnp.uint8 if n_experts <= 256 else jnp.int32)


def feed_forward(cfg, sparse: bool, p, x, valid, moe,
                 as_one_row: bool = False):
    """The second half of a layer on ``x [B, S, H]``, ``valid [B, S]``:
    ``x + FF(RMSNorm(x))`` (``p["norm2"]``, ``cfg.rms_norm_eps``) with
    ``FF`` the dense SwiGLU of ``p`` (scope ``mlp``) or, ``sparse``, the
    model's own ``moe(cfg, p, h [T, H], valid [T]) -> (y, top_e [T, k],
    load [4])`` (scope ``moe``).  Returns ``(x, top_e [B, S, k], load)``; a
    dense layer reports no experts (None, None).

    ``as_one_row`` (a prefill's group ``x [G, S, H]``): the norm in the
    rows' shape (it rides the epilogue of the matmul before it), then the
    group's tokens as ONE row of ``G * S`` through the layer and the
    residual sum — a token's feed-forward does not know its row.  With
    the sum in the rows' shape the compiler cuts the expert layer's
    combine at the reshape between them and writes the float32 copies of
    all ``k`` gathered slices (1.07 GB a layer at four rows; 54 ms of a
    757 ms prefill on a v5e, PERF.md section 6, PR 37).  One row is its
    own shape: nothing is reshaped."""
    B, S, H = x.shape
    h = rms_norm(x, p["norm2"], cfg.rms_norm_eps, cfg.dtype)
    if as_one_row:
        h, valid = h.reshape(1, B * S, H), valid.reshape(1, B * S)
    if sparse:
        with jax.named_scope("moe"):
            y, top_e, load = moe(cfg, p, h.reshape(B * S, H),
                                 valid.reshape(-1))
        y = y.reshape(h.shape)
    else:
        with jax.named_scope("mlp"):
            y, top_e, load = swiglu(cfg, p, h), None, None
    x = (x.reshape(h.shape) + y).reshape(B, S, H)
    return x, None if top_e is None else top_e.reshape(B, S, -1), load
