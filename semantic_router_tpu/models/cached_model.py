"""A decoder that owns its cache, behind the interface
``models.generate.GreedyGenerator`` decodes through
(``generate.Qwen3Cached`` says what it is): the ONE class of every
functional decoder (``lfm2_moe``, ``dots3_note``, ``joyai_llm_flash``,
``laguna``, ``olmo_hybrid``).  A model file binds its own functions to it
under the name ``CachedModel``; no adapters here, ``task_index`` is
accepted and unused.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

from ..ops.flash_attention import causal_tiles
from .mapped_prefill import prefill_group, tree_bytes


class CachedDecoder:
    """``config`` and the model's ``prefill(cfg, params, ids, lengths,
    cache_len)`` and ``decode(cfg, params, cache, tokens, positions)``.

    ``cache_kinds``: the cache's entries that are state (``cache_bytes``
    reports each that a cache holds).  ``group_sizes = (row_bytes,
    cache_bytes)``: the model maps its prefill's rows
    (``models/mapped_prefill.py``) and these are its sizes; None: its rows
    go through the layers together.  ``attn_layers``: ``(heads, window)``
    of each flash call of a prefill that hands the kernel its rows'
    lengths; None: it hands none.  ``drafter = (first_draft, verify,
    draft)``: the checkpoint has a multi-token-prediction module, and the
    generator then steps through these and not ``decode``
    (``models/joyai_llm_flash.py`` has the protocol)."""

    def __init__(self, config, prefill: Callable, decode: Callable, *,
                 cache_kinds: Sequence[str],
                 group_sizes: Optional[Tuple[Callable, Callable]] = None,
                 attn_layers: Optional[Sequence[Tuple[int, int]]] = None,
                 drafter: Optional[Tuple[Callable, Callable, Callable]] = None
                 ) -> None:
        self.config = config
        self._prefill, self._decode = prefill, decode
        self._cache_kinds = tuple(cache_kinds)
        self._group_sizes = group_sizes
        self._attn_layers = attn_layers
        self.drafts = drafter is not None
        self._first_draft, self._verify, self._draft = drafter or (None,) * 3

    def prefill(self, params, ids, lengths, cache_len: int, task_index):
        return self._prefill(self.config, params, ids, lengths, cache_len)

    def decode(self, params, cache, tokens, positions, task_index):
        return self._decode(self.config, params, cache, tokens, positions)

    def first_draft(self, params, cache, ids, lengths, tokens, aux):
        return self._first_draft(self.config, params, cache, ids, lengths,
                                 tokens, aux)

    def verify(self, params, cache, tokens, positions, task_index):
        return self._verify(self.config, params, cache, tokens, positions)

    def draft(self, params, cache, hidden, chosen, positions, accepted, aux):
        return self._draft(self.config, params, cache, hidden, chosen,
                           positions, accepted, aux)

    def rows_per_group(self, params, rows: int, bucket: int,
                       cache_len: int) -> Optional[int]:
        """How many rows of such a prefill go through the layers together;
        None: they are not mapped."""
        if self._group_sizes is None:
            return None
        return prefill_group(self.config, params, rows, bucket, cache_len,
                             *self._group_sizes)

    def attn_tiles(self, lengths, bucket: int):
        """``(visited, grid)`` of such a prefill's flash calls
        (``flash_attention.tiles_for``) over its layers and their heads;
        None: the kernel is not handed the rows' lengths."""
        if self._attn_layers is None:
            return None
        return causal_tiles(bucket, lengths, self._attn_layers)

    def cache_bytes(self, cache) -> Dict[str, int]:
        """The cache's bytes by kind of state."""
        return {k: tree_bytes(cache[k]) for k in self._cache_kinds
                if k in cache}
