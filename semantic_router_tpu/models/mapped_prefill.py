"""A prefill bounded in tokens: the rows of a long-prompt decoder's prefill
(``lfm2_moe``, ``dots3_note``, ``laguna``, ``olmo_hybrid``) are mapped
INSIDE the program a GROUP at a time, so the layers' temporaries exist for
one group whatever the batch and an expert layer's grouped matmuls read
each touched expert once a group.  Here: how many rows a group holds
(``rows_per_group`` has the rule and the table behind it, ``prefill_group``
reckons it at a model's sizes), the map (``map_row_groups``) and the driver
around a model's own ``_prefill_rows`` (``prefill_in_groups``).  A model
keeps what IS the model: its ``_prefill_rows``, its ``_row_bytes`` and
``_cache_bytes``.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .experts import sum_loads


def device_bytes() -> Optional[int]:
    """What the device a program is traced for may allocate; None where
    the backend reports no limit (the CPU)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def tree_bytes(tree) -> int:
    """The bytes of a tree's arrays (or of their shapes, under a trace)."""
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree))


SPARE_BYTES = 1_500_000_000
# half a v5e core's own memory (VMEM, 128 MiB): what a group's normed
# activations may take for the compiler to keep them there
GATHER_SOURCE_BYTES = 64 * 2**20


def rows_per_group(rows: int, row_bytes: int, resident_bytes: int,
                   source_bytes: int) -> int:
    """How many of a prefill's ``rows`` run through the layers together
    (``map_row_groups``).  More rows a group feed each touched expert's
    matrices more pairs a read.  Two things bound a group: its
    temporaries, ``row_bytes`` a row beside ``resident_bytes`` of weights
    and cache on a device of ``device_bytes()``, must leave ``SPARE_BYTES``
    free (the allocator's fragments, the decode program's temporaries);
    and its normed activations ``[G * S, H]``, ``source_bytes`` a row —
    which the expert layer's first gather reads k times a token — must
    stay within ``GATHER_SOURCE_BYTES``, where the compiler keeps them in
    the core's own memory and the gather costs 13 ns a row, not 33.  The
    rule: the largest divisor of ``rows`` within both; 1 where not even
    one row is; where the backend reports no limit (the CPU), every row.

    Set from ``benchmarks/results/lfm2_prefill_groups.json`` (one v5e, the
    lfm2_moe guard's 8 x 8192 prefill, PERF.md section 6, PR 37), rows a
    group -> whole prefill ms / grouped matmuls ms a row-layer / the
    gathers' scope ms / the compiler's temporaries GB: 1 -> 752 / 4.05 /
    83 / 0.75; 2 -> 642 / 3.27 / 34 / 1.32 (activations 67 MB, in the
    core's memory); 4 -> 685 / 2.77 / 91 / 2.60 (134 MB: not); 8 -> 670 /
    2.51 / 91 / 5.13 (leaves 1.1 GB of a 16.9 GB device beside 10.6).  So
    the guard's cell runs 2 rows a group, the dots3_note cell (a row's
    temporaries 5.8 GB reckoned, 4.2 by the compiler; activations 84 MB a
    row) 1."""
    limit = device_bytes()
    if limit is None:
        return rows
    fit = min((limit - resident_bytes - SPARE_BYTES) // row_bytes,
              GATHER_SOURCE_BYTES // source_bytes)
    return max(g for g in range(1, rows + 1)
               if rows % g == 0 and g <= max(fit, 1))


def prefill_group(cfg, params, rows: int, S: int, cache_len: int,
                  row_bytes: Callable, cache_bytes: Callable) -> int:
    """The rows a group of a prefill of ``rows`` x ``S``: ``rows_per_group``
    at a model's sizes — its ``row_bytes(cfg, S)`` of temporaries a row,
    its ``cache_bytes(cfg, rows, cache_len)`` beside the weights, and a
    row's normed activations ``[S, hidden_size]`` in ``cfg.dtype``."""
    return rows_per_group(
        rows, row_bytes(cfg, S),
        tree_bytes(params) + cache_bytes(cfg, rows, cache_len),
        S * cfg.hidden_size * jnp.dtype(cfg.dtype).itemsize)


def map_row_groups(rows_fn, group: int, ids, lengths):
    """``rows_fn(ids [G, S], lengths [G]) -> (per_row, per_group)`` over
    the batch ``ids [B, S]``, ``lengths [B]``, ``group`` rows a call, one
    call at a time INSIDE the program (``jax.lax.map``: the temporaries
    are one group's whatever the batch).  Every leaf of ``per_row`` has
    the group's rows on its leading axis and comes back ``[B, ...]`` in
    the batch's order; ``per_group`` comes back stacked ``[B / group,
    ...]``.  ``group`` divides ``B``.  Not ``jax.lax.map(batch_size=)``:
    that ``vmap``s a one-row body, and a ``vmap`` of the grouped matmul is
    one grouped matmul a row under one more grid axis, each reading every
    expert; here a group's rows are ONE call's tokens."""
    B = ids.shape[0]

    def split(a):
        return a.reshape((B // group, group) + a.shape[1:])

    per_row, per_group = jax.lax.map(lambda g: rows_fn(*g),
                                     (split(ids), split(lengths)))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((B,) + a.shape[2:]), per_row), per_group


def prefill_in_groups(prefill_rows, names: Sequence[str],
                      layer_major: Sequence[str], group: int, ids, lengths):
    """A model's ``prefill`` at ``group`` rows a call of its
    ``prefill_rows(ids [G, S], lengths [G])``, whose tuple ``names`` names
    in order: the kinds of cache (every leaf's leading axis the rows),
    then ``"logits"``, then the entries of ``aux``, ``"load"`` (``[layers,
    4]``, a group's) among them.  The entries in ``layer_major`` come
    ``[layers, rows, ...]`` and go through the map rows first.  Returns
    ``(cache, logits, aux)`` over the batch: the cache with ``lengths``,
    ``aux["load"]`` summed over the groups (``experts.sum_loads``)."""
    kept = [n for n in names if n != "load"]

    def rows(ids, lengths):
        out = dict(zip(names, prefill_rows(ids, lengths)))
        return tuple(jnp.moveaxis(out[n], 1, 0) if n in layer_major
                     else out[n] for n in kept), out["load"]

    mapped, loads = map_row_groups(rows, group, ids, lengths)
    out = dict(zip(kept, mapped))
    split = names.index("logits")
    cache = {n: out[n] for n in names[:split]}
    cache["lengths"] = lengths.astype(jnp.int32)
    aux = {}
    for n in names[split + 1:]:
        aux[n] = sum_loads(loads) if n == "load" else \
            jnp.moveaxis(out[n], 0, 1) if n in layer_major else out[n]
    return cache, out["logits"], aux
