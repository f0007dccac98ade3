"""Paged SPLS-aware serving subsystem.

Block-pool KV cache (:mod:`pager`), paged model execution
(:mod:`paged_model`), continuous-batching scheduler with chunked prefill
and preemption (:mod:`scheduler`), and the engine (:mod:`engine`).
See README.md in this directory for the page lifecycle and the SPLS
page-pruning semantics.
"""

from .pager import (NULL_PAGE, POS_SENTINEL, PagedKVCache, PagePool,
                    PredKCache, init_paged_cache, init_pos_pages,
                    init_pred_cache, spls_token_keep, spls_token_votes)
from .paged_model import (compact_slots, paged_decode_step,
                          paged_prefill_chunk, paged_prefill_chunk_spls)
from .scheduler import Scheduler, SchedulerConfig, SeqState
from .engine import PagedServingEngine, Request, ServeConfig

__all__ = [
    "NULL_PAGE", "POS_SENTINEL", "PagedKVCache", "PagePool", "PredKCache",
    "init_paged_cache", "init_pos_pages", "init_pred_cache",
    "spls_token_keep", "spls_token_votes",
    "compact_slots", "paged_decode_step", "paged_prefill_chunk",
    "paged_prefill_chunk_spls",
    "Scheduler", "SchedulerConfig", "SeqState",
    "PagedServingEngine", "Request", "ServeConfig",
]
