"""Model execution against the block-pool paged KV cache.

Mirrors the dense serving path in :mod:`repro.models.model` (scan over
periods, one lowered period body) but threads :class:`PagedKVCache` pages,
a shared block table, and original-position ids instead of a dense
``(B, KV, max_len, Dh)`` slab:

* :func:`paged_decode_step` -- one batched decode tick.  Each layer writes
  the new token's K/V into the slot the block table names (inactive rows
  write nothing) and attends through the paged decode backends
  (``xla_paged_decode`` / ``pallas_paged_decode``).
* :func:`paged_prefill_chunk` -- chunked prefill: one prompt chunk (padded
  to a static chunk size) is projected at its original positions, written
  into freshly allocated slots, and attends over *all* slots written so far
  -- cross-chunk causal attention, which is what lets the scheduler
  interleave long prefills with decode ticks.  Every prompt, however
  short, prefills this way (:func:`paged_prefill_chunk_spls` under SPLS).
* :func:`compact_slots` -- the end-of-prefill SPLS page pruning: the kept
  columns move to the front of the sequence's own pages.

All functions are functional: caches/pos_pages go in, updated ones come
out; the engine owns jit boundaries and the host-side pool bookkeeping.

The steps cast no weight.  Their floating parameters arrive at
``cfg.compute_dtype``, an MoE router's as stored (float32): the engine
casts them once, when it is built (``cast_compute``).  Each step checks
this when it is traced and raises ``ValueError`` on parameters at
another width.

The decode and dense chunk steps keep the stacked pool (``(n_periods, KV,
N, ps, Dh)`` per period block) in the layer scan's *carry*; the scan's
inputs are the period parameters and the period index ``l``.  A layer
writes its new rows straight into the carried pool a page at a time: the
pages ``[l, :, page]`` its rows fall in are read, the rows laid over
their slots and the pages written back, each page one (KV, ps, Dh)
window -- one page a row for a decode token, the pages a chunk spans for
a chunk.  It reads layer ``l`` from the pool too: the decode backends
take ``layer=l`` and the chunk step gathers ``pool[l, :, table]``.  No op
reads or writes a whole layer's pool, and the donated pool is updated in
place.  (Scanned as ``xs``/``ys``, XLA would slice each layer's pool
out, write it back whole into a new stack and copy around the loop.)
The SPLS chunk step still scans the pool as ``xs``.

Every op of the steps runs under one of the :data:`STAGES` names
(``jax.named_scope``), which the compiled program keeps as op metadata and
a device trace shows beside each op; an op's stage is the innermost stage
name in its name stack.  ``kv_pool`` covers every op that moves page-pool
data: the token or chunk K/V writes, the ``pos_pages`` update, the page
gathers and, in the SPLS step, the per-layer pool slices and write-backs
of its scan (so that scan runs under ``kv_pool``, its body's compute
under the compute stages).  The decode and dense chunk scans move no pool
data and run under no stage: the per-layer parameter slices they feed
each layer are ``unscoped``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import resolve_backend, get_backend
from repro.models.attention import output_proj, project_kv, project_qkv
from repro.models.common import (dtype_of, off_compute_width, rms_norm,
                                 softcap as _softcap)
from repro.models.model import embed_inputs, head_logits
from repro.models.moe import ffn_forward, moe_held_forward

from .pager import POS_SENTINEL, PagedKVCache

__all__ = ["paged_decode_step", "paged_prefill_chunk",
           "paged_prefill_chunk_spls", "compact_slots",
           "STAGES"]

# the stages of a model step, as its ops' named scopes; ``plan`` is the
# SPLS prediction and plan, in the SPLS chunk step only; ``moe`` is the
# held-expert layer (router, pair layout, the ``moe_gmm`` kernel and the
# combine), inside ``ffn``
STAGES = ("embed", "qkv", "attention", "kv_pool", "ffn", "lm_head", "plan",
          "moe")


def _check_compute_width(cfg: ArchConfig, params):
    """``cfg``'s compute dtype, once the floating parameters are checked to
    arrive at it (an MoE router's in float32): the steps compute with the
    weights as given and cast none of them.  A trace-time check, no op."""
    dtype = dtype_of(cfg.compute_dtype)
    off = off_compute_width(params, dtype)
    if off:
        raise ValueError(
            f"the paged steps take their floating parameters at the "
            f"compute dtype {cfg.compute_dtype} (an MoE router's as "
            f"stored); {len(off)} are not, e.g. {', '.join(off[:3])}: "
            f"cast them once with cast_compute, as PagedServingEngine "
            f"does")
    return dtype


def _with_moe(out: tuple, stats: Optional[jax.Array]) -> tuple:
    """A step's outputs, with the held-expert layer's counters appended
    where a layer holds experts: per-layer (..., 3)
    :data:`~repro.models.moe.MOE_STATS` -> (3,), pairs and touched
    experts summed over layers, the peak the largest."""
    if stats is None:
        return out
    s = stats.reshape(-1, 3)
    return out + (jnp.stack([s[:, 0].sum(), s[:, 1].sum(),
                             s[:, 2].max()]),)


def _lay_pages(kc: PagedKVCache, layer, page: jax.Array, mask: jax.Array,
               k_rows: jax.Array, v_rows: jax.Array) -> PagedKVCache:
    """Lay K/V rows over whole pages ``[layer, :, page]`` (n,) of the
    stacked (L, KV, N, ps, Dh) pool, in place: each page is read, its
    slots where ``mask`` holds take the rows, and it is written back.
    ``mask`` and the rows broadcast against the pages (n, KV, ps, Dh).
    Nothing of the pool but those pages is read or written, and each
    page moves as one (KV, ps, Dh) window.  The null page (id 0) is
    never written: it becomes a distinct out-of-bounds id, so the
    indices stay unique and its write is dropped."""
    N = kc.k_pages.shape[2]
    page = jnp.where(page == 0, N + jnp.arange(page.shape[0]), page)
    at = (layer, slice(None), page)

    def lay(pool, rows):
        old = pool.at[at].get(mode="clip", unique_indices=True)
        return pool.at[at].set(jnp.where(mask, rows, old), mode="drop",
                               unique_indices=True)

    with jax.named_scope("kv_pool"):
        return PagedKVCache(lay(kc.k_pages, k_rows), lay(kc.v_pages, v_rows))


def _write_token(kc: PagedKVCache, layer, k_new: jax.Array,
                 v_new: jax.Array, flat: jax.Array) -> PagedKVCache:
    """Write one token's K/V (B, KV, 1, Dh) per row at flat page slot
    ``flat`` (B,) of layer ``layer`` of the stacked pool.  Rows whose
    slot lies in the null page (the inactive rows) write nothing."""
    ps = kc.k_pages.shape[3]
    at_slot = (jnp.arange(ps) == (flat % ps)[:, None])[:, None, :, None]
    return _lay_pages(kc, layer, flat // ps, at_slot, k_new, v_new)


def _decode_flat_slots(tables: jax.Array, kv_len: jax.Array,
                       page_size: int) -> jax.Array:
    """(B,) flat page-slot index for each row's next write (slot kv_len).
    Inactive rows (all-null tables, kv_len 0) resolve to the null page."""
    page = jnp.take_along_axis(tables, (kv_len // page_size)[:, None],
                               axis=1)[:, 0]
    return page * page_size + kv_len % page_size


def _chunk_slots(table: jax.Array, pos_pages: jax.Array, start: jax.Array,
                 valid: jax.Array, CS: int):
    """Chunk destination slots + pos_pages update, shared by both chunked
    prefill paths (slot == original position during prefill).

    Padded rows (idx >= valid) all scatter to null-page slot 0 and write
    POS_SENTINEL -- not their would-be position -- so the null page stays
    inert: a real id there could pass a ``pos - id < window`` test on a
    row that reads the null page through an unallocated table entry.
    Returns ``(sl (CS,) slot ids, flat (CS,) scatter targets,
    new_pos_pages)``.
    """
    N, ps = pos_pages.shape
    with jax.named_scope("kv_pool"):
        idx = jnp.arange(CS, dtype=jnp.int32)
        sl = start + idx
        page = table[sl // ps]
        flat = jnp.where(idx < valid, page * ps + sl % ps, 0)
        pos_pages = pos_pages.reshape(N * ps).at[flat].set(
            jnp.where(idx < valid, sl, POS_SENTINEL)).reshape(N, ps)
    return sl, flat, pos_pages


def _scatter_rows(kc: PagedKVCache, k_new: jax.Array, v_new: jax.Array,
                  flat: jax.Array) -> PagedKVCache:
    """Scatter a chunk's K/V rows (1, KV, CS, Dh) into flat page slots of
    one layer's (KV, N, ps, Dh) pool."""
    KV, N, ps, Dh = kc.k_pages.shape
    with jax.named_scope("kv_pool"):
        kf = kc.k_pages.reshape(KV, N * ps, Dh).at[:, flat].set(k_new[0])
        vf = kc.v_pages.reshape(KV, N * ps, Dh).at[:, flat].set(v_new[0])
        return PagedKVCache(kf.reshape(KV, N, ps, Dh),
                            vf.reshape(KV, N, ps, Dh))


def _write_chunk_kv(kc: PagedKVCache, layer, k_new: jax.Array,
                    v_new: jax.Array, table: jax.Array, start: jax.Array,
                    valid: jax.Array) -> PagedKVCache:
    """Write a chunk's K/V rows (1, KV, CS, Dh), the sequence's slots
    ``start .. start + valid - 1``, into layer ``layer`` of the stacked
    pool a whole page at a time: each page the chunk touches is read, the
    chunk's rows are laid over its slots, and it is written back.  The
    static page count covers an unaligned start; a page with no slot of
    the chunk is not written."""
    _, KV, N, ps, Dh = kc.k_pages.shape
    CS = k_new.shape[2]
    P = table.shape[0]
    n_pg = (CS + 2 * ps - 2) // ps            # most pages CS slots can span
    with jax.named_scope("kv_pool"):
        lp = start // ps + jnp.arange(n_pg)            # logical pages
        row = lp[:, None] * ps + jnp.arange(ps) - start  # chunk row a slot
        live = (row >= 0) & (row < valid)               # (n_pg, ps)
        page = jnp.where(live.any(-1), table[jnp.minimum(lp, P - 1)], 0)

        def rows(new):  # slot (j, s) <- chunk row j*ps + s - start % ps
            x = jnp.pad(new[0], ((0, 0), (ps, n_pg * ps - CS), (0, 0)))
            x = jax.lax.dynamic_slice_in_dim(x, ps - start % ps, n_pg * ps,
                                             axis=1)
            return jnp.swapaxes(x.reshape(KV, n_pg, ps, Dh), 0, 1)

        k_rows, v_rows = rows(k_new), rows(v_new)
    return _lay_pages(kc, layer, page, live[:, None, :, None], k_rows, v_rows)


def _residual_ffn(cfg: ArchConfig, blk, bp, x: jax.Array, h: jax.Array,
                  ffn_leader: jax.Array = None, ffn_comp=None,
                  compute_backend: str = "dense",
                  row_valid: Optional[jax.Array] = None):
    """Attention residual + optional post-norms + FFN residual, shared by
    the decode and chunked-prefill scan bodies.  ``ffn_leader`` (local row
    ids) enables simulation-mode sparse FFN: similar tokens copy their MFI
    leader's output.  ``ffn_comp`` (a :class:`~repro.core.sparse_exec.Compaction`)
    switches to *packed* sparse FFN through the compute-backend registry:
    only critical rows are computed, leaders broadcast to followers.
    Returns ``(x, moe_stats)``: the held-expert layer's counters
    (``row_valid`` False rows route to nothing), None for other FFNs."""
    if cfg.use_post_norm:
        h = rms_norm(h, bp["post_ln1"], cfg.norm_eps)
    x = x + h
    stats = None
    if blk.has_ffn:
        xn2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
        if ffn_comp is not None and not blk.use_moe:
            from repro.sparse_compute import packed_mlp
            h2 = packed_mlp(cfg, bp["ffn"], xn2, ffn_comp, compute_backend)
        else:
            if blk.use_moe and cfg.moe_held is not None:
                h2, stats = moe_held_forward(cfg, bp["ffn"], xn2, row_valid)
            else:
                h2 = ffn_forward(cfg, blk.use_moe, bp["ffn"], xn2)
            if ffn_leader is not None:
                h2 = jnp.take_along_axis(h2, ffn_leader[..., None], axis=-2)
        if cfg.use_post_norm:
            h2 = rms_norm(h2, bp["post_ln2"], cfg.norm_eps)
        x = x + h2
    return x, stats


def _stack_stats(stats) -> Optional[jax.Array]:
    """A period's blocks' MoE counters, (n_moe_blocks, 3), or None."""
    stats = [s for s in stats if s is not None]
    return jnp.stack(stats) if stats else None


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def paged_decode_step(cfg: ArchConfig, params, cache, pos_pages: jax.Array,
                      tables: jax.Array, kv_len: jax.Array,
                      cur_pos: jax.Array, tokens: jax.Array,
                      backend: Optional[str] = None):
    """One batched decode tick over the paged cache.

    tokens: (B, 1) int32; tables: (B, P); kv_len: (B,) written slots;
    cur_pos: (B,) original position of this token.  Every layer writes the
    token's K/V at slot ``kv_len`` (whose page the engine has already
    ensured) and attends over ``kv_len + 1`` slots.  Returns
    ``(logits (B, 1, V), new_cache, new_pos_pages)``, and where a layer
    holds experts a fourth output: the held-expert layer's (3,) counters
    over every layer (rows with ``kv_len == 0``, the inactive slots,
    route to nothing).
    """
    _check_compute_width(cfg, params)
    ps = pos_pages.shape[1]
    N = pos_pages.shape[0]
    with jax.named_scope("kv_pool"):
        flat = _decode_flat_slots(tables, kv_len, ps)
        pos_pages = pos_pages.reshape(N * ps).at[flat].set(cur_pos) \
            .reshape(N, ps)
    n_valid = kv_len + 1
    name = resolve_backend(backend or cfg.attn_backend, cfg, L=N * ps,
                           decode=True, paged=True)
    fn = get_backend(name)
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, tokens)

    row_valid = (kv_len > 0)[:, None]

    def scan_body(carry, inp):
        x, pcache = carry
        pparams, l = inp
        new_caches, stats = [], []
        for blk, bp, kc in zip(cfg.period, pparams, pcache):
            with jax.named_scope("qkv"):
                xn = rms_norm(x, bp["ln1"], cfg.norm_eps)
                q, k_new, v_new = project_qkv(cfg, bp["attn"], xn,
                                              cur_pos[:, None], "structured")
            kc = _write_token(kc, l, k_new, v_new, flat)
            with jax.named_scope("attention"):
                o = fn(cfg, q[:, :, :, 0], kc.k_pages, kc.v_pages, layer=l,
                       pos_pages=pos_pages, tables=tables, kv_len=n_valid,
                       pos=cur_pos, window=blk.window)
                h = output_proj(cfg, bp["attn"], o[:, :, :, None],
                                "structured")
            with jax.named_scope("ffn"):
                x, st = _residual_ffn(cfg, blk, bp, x, h,
                                      row_valid=row_valid)
            new_caches.append(kc)
            stats.append(st)
        return (x, tuple(new_caches)), _stack_stats(stats)

    # no stage scope: the scan moves no pool data, only each layer's
    # parameters, and its body's ops carry their own stages
    (x, new_cache), moe = jax.lax.scan(
        scan_body, (x, cache),
        (params["periods"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    with jax.named_scope("lm_head"):
        logits = head_logits(cfg, params, x)
    return _with_moe((logits, new_cache, pos_pages), moe)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

def paged_prefill_chunk(cfg: ArchConfig, params, cache,
                        pos_pages: jax.Array, table: jax.Array,
                        start: jax.Array, tokens: jax.Array,
                        valid: jax.Array):
    """Process one prompt chunk for a single sequence (B = 1).

    tokens: (1, CS) chunk padded to the static chunk size; start: ()
    written slots so far (== original position base: the chunked path never
    prunes, so slot index == position); valid: () real tokens in this
    chunk; table: (P,) the sequence's block table (pages for
    ``start + valid`` slots already allocated).  Chunk queries attend over
    every slot written so far *plus* this chunk (cross-chunk causal
    attention by original position ids).  Returns
    ``(logits (1, 1, V) for the chunk's last valid position, new_cache,
    new_pos_pages)``; only the final chunk's logits are meaningful (they
    seed the first decoded token) -- the LM head is not run for the other
    ``CS - 1`` rows.  Where a layer holds experts a fourth output: the
    held-expert layer's (3,) counters over every layer (padded rows route
    to nothing).
    """
    assert cfg.causal, "chunked prefill needs causal attention"
    _, CS = tokens.shape
    N, ps = pos_pages.shape
    S = table.shape[0] * ps
    _check_compute_width(cfg, params)

    sl, _, pos_pages = _chunk_slots(table, pos_pages, start, valid, CS)
    positions = sl[None, :]                            # original ids
    n_valid = start + valid
    with jax.named_scope("kv_pool"):
        pg = pos_pages[table].reshape(S)               # slot -> original id
    slot_idx = jnp.arange(S)

    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, tokens)

    def attend(blk, q, kc, l):
        KV = kc.k_pages.shape[1]
        with jax.named_scope("kv_pool"):
            # one gather of the table's pages of layer l: (P, KV, ps, Dh)
            kg = jnp.moveaxis(kc.k_pages[l, :, table], 0, 1)
            vg = jnp.moveaxis(kc.v_pages[l, :, table], 0, 1)
            kg = kg.reshape(1, KV, S, -1)
            vg = vg.reshape(1, KV, S, -1)
        Dh = q.shape[-1]
        s = jnp.einsum("bkgqd,bkld->bkgql", q, kg) * (Dh ** -0.5)
        s = _softcap(s, cfg.attn_softcap)
        m = slot_idx[None, :] < n_valid
        m = m & (pg[None, :] <= positions[0][:, None])
        if blk.window is not None:
            m = m & (positions[0][:, None] - pg[None, :] < blk.window)
        s = jnp.where(m[None, None, None], s, jnp.asarray(-1e30, s.dtype))
        a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bkgql,bkld->bkgqd", a, vg)

    row_valid = (jnp.arange(CS) < valid)[None, :]

    def scan_body(carry, inp):
        x, pcache = carry
        pparams, l = inp
        new_caches, stats = [], []
        for blk, bp, kc in zip(cfg.period, pparams, pcache):
            with jax.named_scope("qkv"):
                xn = rms_norm(x, bp["ln1"], cfg.norm_eps)
                q, k_new, v_new = project_qkv(cfg, bp["attn"], xn,
                                              positions, "structured")
            kc = _write_chunk_kv(kc, l, k_new, v_new, table, start, valid)
            with jax.named_scope("attention"):
                o = attend(blk, q, kc, l)
                h = output_proj(cfg, bp["attn"], o, "structured")
            with jax.named_scope("ffn"):
                x, st = _residual_ffn(cfg, blk, bp, x, h,
                                      row_valid=row_valid)
            new_caches.append(kc)
            stats.append(st)
        return (x, tuple(new_caches)), _stack_stats(stats)

    # no stage scope: the scan moves no pool data, only each layer's
    # parameters, and its body's ops carry their own stages
    (x, new_cache), moe = jax.lax.scan(
        scan_body, (x, cache),
        (params["periods"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    with jax.named_scope("lm_head"):
        x_last = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
        logits = head_logits(cfg, params, x_last)
    return _with_moe((logits, new_cache, pos_pages), moe)


# ---------------------------------------------------------------------------
# SPLS chunked prefill (the paper's progressive generation scheme, Sec. IV-C)
# ---------------------------------------------------------------------------

def paged_prefill_chunk_spls(cfg: ArchConfig, params, cache, pred_cache,
                             pos_pages: jax.Array, table: jax.Array,
                             start: jax.Array, tokens: jax.Array,
                             valid: jax.Array, topk_k: jax.Array,
                             q_capacity: Optional[int] = None,
                             ffn_capacity: Optional[int] = None,
                             kv_capacity: Optional[int] = None,
                             compute_backend: str = "dense",
                             live: Optional[jax.Array] = None,
                             last_keep: Optional[jax.Array] = None,
                             kv_vote_need: int = 1):
    """One SPLS prompt chunk for a single sequence (B = 1).

    The streaming driver of the unified planner
    (:class:`repro.core.planner.PlanContext`): every layer (1) extends its
    paged *predictor* cache with the chunk's predicted K heads as int8
    codes + per-token scale (``PlanContext.encode_pred_qk``; dequantized
    on read, bit-for-bit), (2) emits a plan block for the chunk's rows
    against every column seen so far (``PlanContext.plan_block``:
    bisection top-k with a *traced* ``topk_k``, so one jit covers every
    prompt length; O(chunk * S) memory, never a full PAM), and (3)
    executes the chunk rows in simulation-mode SPLS over all written KV
    slots.  The math is row-for-row identical to the progressive
    full-prefill path (``prefill(..., plan_mode="progressive")``), which
    is what makes chunked and whole-prompt serving agree bit-for-bit.

    Chunks must be window-aligned (``start`` and the chunk size multiples
    of ``cfg.spls.window``) so similarity windows coincide with the
    unchunked pipeline's.

    **End-to-end sparse compute** (``compute_backend`` ``"packed_xla"`` /
    ``"packed_pallas"``, static capacities ``q_capacity`` /
    ``ffn_capacity``): the Q projection and attention run only on the
    *cross-head union* of critical rows packed to ``q_capacity`` (leaders
    broadcast to their followers through the compaction's read slots), and
    the FFN runs only on FFN-critical rows packed to ``ffn_capacity``.
    At full capacities the packed path is bit-for-bit the dense
    (``"dense"``) path; below them, overflow rows fall back to their
    window leader (:func:`repro.core.sparse_exec.compact_rows`).

    **Horizon-finalized column votes** (``live`` / ``kv_capacity`` /
    ``last_keep``; see :mod:`repro.core.planner`): ``live`` (S,) marks
    columns the engine's finite ``vote_horizon`` already finalized as
    pruned -- they are denied attention (masked out of every layer's
    score block), while the prediction/vote pipeline itself stays
    horizon-independent so the vote trajectory matches the
    end-of-prefill path's (the monotonicity the tests pin).  With ``kv_capacity`` set (the
    ``vote_horizon == 1`` mode), layer 0's plan block additionally
    decides which of the chunk's *own* columns won the cross-head
    keep vote (``kv_vote_need`` agreeing heads -- the engine passes
    ``ceil(spls_prune_vote * H)``, the same bar the end-of-prefill vote
    applies) **before** formal K/V generation; only those (packed to
    ``kv_capacity``, plus the forced ``last_keep`` anchor) are projected
    and written -- the K/V-projection share of the paper's end-to-end
    sparsity.  All layers share layer 0's decision (a page slot is shared
    by every layer, exactly like the end-of-prefill prune vote).  With
    ``live=None`` and ``kv_capacity=None`` the path is bit-for-bit
    today's end-of-prefill vote: every column materializes until the vote
    finalizes with the last chunk, after which the engine runs
    :func:`compact_slots`.

    Returns ``(logits (1, 1, V), new_cache, new_pred_cache, new_pos_pages,
    kv_any, crit_counts)`` with ``kv_any (1, KV, G, S)`` layer 0's per-head
    column-keep contribution for the engine's vote accumulator and
    ``crit_counts (n_periods, 3)`` the per-period max of (union-critical
    rows, FFN-critical rows, vote-surviving own columns) -- the capacity
    controllers' observations.
    """
    assert cfg.causal, "chunked prefill needs causal attention"
    from repro.core.planner import (PlanContext, own_column_keep,
                                    pack_within_capacity)
    from repro.core.sparse_exec import (_masked_softmax, compact_rows,
                                        gather_rows, pack_by_mask)
    from repro.sparse_compute import is_packed, packed_project_q

    from .pager import PredKCache

    _, CS = tokens.shape
    if CS % cfg.spls.window:
        raise ValueError(
            f"prefill_chunk ({CS}) must be a multiple of the SPLS "
            f"similarity window ({cfg.spls.window}): chunk boundaries must "
            f"align with similarity windows for chunked prefill to "
            f"reproduce the full-prefill plan (set "
            f"ServeConfig.auto_align_chunk=True to round up automatically)")
    packed = is_packed(compute_backend)
    if kv_capacity is not None:
        assert packed, "kv_capacity rides on a packed compute backend"
        assert live is not None and last_keep is not None, \
            "kv_capacity needs the liveness mask and the decode anchor"
    Cq = min(q_capacity or CS, CS)
    Cf = min(ffn_capacity or CS, CS)
    Ckv = min(kv_capacity, CS) if kv_capacity is not None else None
    N, ps = pos_pages.shape
    S = table.shape[0] * ps
    KV, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    scfg = cfg.spls
    dtype = _check_compute_width(cfg, params)
    ctx = PlanContext.for_config(cfg, mode="structured")

    sl, flat, pos_pages = _chunk_slots(table, pos_pages, start, valid, CS)
    positions = sl[None, :]
    n_valid = start + valid
    slot_idx = jnp.arange(S)

    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, tokens)

    def scan_body(carry, inp):
        if Ckv is not None:
            x, kv_written_c, live_all_c, n_kv_c = carry
        else:
            x = carry
            kv_written_c = live_all_c = n_kv_c = None
        pparams, pcache, ppred, p_idx = inp
        new_caches, new_preds = [], []
        kv_any0 = None
        counts = jnp.zeros((3,), jnp.int32)
        ridx = jnp.arange(CS, dtype=jnp.int32)
        for bi, (blk, bp, kc, pk) in enumerate(
                zip(cfg.period, pparams, pcache, ppred)):
            with jax.named_scope("qkv"):
                xn = rms_norm(x, bp["ln1"], cfg.norm_eps)
            # -- prediction: extend the predictor code pages, emit the
            # plan block (all plan math lives in core.planner)
            with jax.named_scope("plan"):
                qh, k_codes, k_scale = ctx.encode_pred_qk(bp["attn"], xn)
            with jax.named_scope("kv_pool"):
                codes_pg = pk.codes.reshape(KV, N * ps, Dh).at[:, flat] \
                    .set(k_codes).reshape(KV, N, ps, Dh)
                scale_pg = pk.scale.reshape(N * ps).at[flat].set(k_scale) \
                    .reshape(N, ps)
                pk = PredKCache(codes=codes_pg, scale=scale_pg)
                codes_seq = codes_pg[:, table].reshape(KV, S, Dh)
                scale_seq = scale_pg[table].reshape(S)
            with jax.named_scope("plan"):
                kh_all = ctx.decode_pred_k(codes_seq, scale_seq,
                                           dtype=dtype)[None]
                # the prediction/vote pipeline is deliberately horizon-
                # independent (no col_live): finalized columns are denied
                # materialization and attention below, but still occupy their
                # top-k candidacy -- this keeps the vote trajectory identical
                # to the end-of-prefill path's, which is what makes the kept
                # set monotone in the horizon
                pb = ctx.plan_block(qh, kh_all, k=topk_k, row0=start,
                                    n_valid_rows=valid, n_cols=n_valid)
                if kv_any0 is None:
                    kv_any0 = pb.kv_any
                lead_local = pb.q_leader - start
                # capacity-controller observations: union of per-head critical
                # rows (the Q pack) and valid FFN-critical rows (padded rows
                # report FFN-critical but never count)
                crit_any = jnp.any(pb.q_critical, axis=(1, 2))     # (1, CS)
                n_ffn = (pb.ffn_critical[0] & (ridx < valid)).sum()
                if Ckv is not None and bi == 0:
                    # layer 0 decides which of this chunk's own columns get a
                    # K/V projection at all (vote_horizon == 1: the chunk's
                    # own plan votes are final); later layers and periods
                    # reuse the carried decision -- lax.cond runs the
                    # decision exactly once per chunk
                    def _decide(_):
                        ok = own_column_keep(
                            pb.kv_any, start=start, chunk=CS, valid=valid,
                            last_keep=last_keep, vote_need=kv_vote_need)
                        anchor = start + ridx == last_keep
                        w = pack_within_capacity(ok, Ckv, anchor=anchor)
                        live_new = jax.lax.dynamic_update_slice(
                            jnp.pad(live, (0, CS)), w, (start,))[:S]
                        return w, live_new, ok.sum().astype(jnp.int32)

                    kv_written_c, live_all_c, n_kv_c = jax.lax.cond(
                        p_idx == 0, _decide,
                        lambda _: (kv_written_c, live_all_c, n_kv_c), None)
                if Ckv is not None:
                    counts = jnp.maximum(counts, jnp.stack(
                        [crit_any.sum(), n_ffn, n_kv_c]).astype(jnp.int32))
                else:
                    counts = jnp.maximum(counts, jnp.stack(
                        [crit_any.sum(), n_ffn,
                         jnp.zeros((), jnp.int32)]).astype(jnp.int32))
            with jax.named_scope("qkv"):
                # -- formal K/V at original positions.  Dense for every chunk
                # row by default (columns must materialize until the prune
                # vote finalizes); under vote_horizon == 1 the project_kv
                # seam runs packed over only the vote-surviving columns.
                if packed:
                    if Ckv is not None:
                        # pack order over the anchor-reserved written set: at
                        # most Ckv True rows, so every written column lands
                        # in the perm (filler slots scatter to the null page)
                        kv_perm, _ = pack_by_mask(kv_written_c, Ckv)
                        k_new, v_new = project_kv(
                            cfg, bp["attn"], xn, positions, "structured",
                            perm=kv_perm, compute_backend=compute_backend)
                        flat_kv = jnp.where(jnp.take(kv_written_c, kv_perm),
                                            jnp.take(flat, kv_perm), 0)
                        kc = _scatter_rows(kc, k_new, v_new, flat_kv)
                    else:
                        k_new, v_new = project_kv(cfg, bp["attn"], xn,
                                                  positions, "structured")
                        kc = _scatter_rows(kc, k_new, v_new, flat)
                else:
                    q, k_new, v_new = project_qkv(cfg, bp["attn"], xn,
                                                  positions, "structured")
                    kc = _scatter_rows(kc, k_new, v_new, flat)
            with jax.named_scope("kv_pool"):
                kg = kc.k_pages[:, table][None].reshape(1, KV, S, Dh)
                vg = kc.v_pages[:, table][None].reshape(1, KV, S, Dh)
            with jax.named_scope("attention"):
                mask = pb.mask
                if blk.window is not None:
                    mask = mask & (positions[0][:, None] - slot_idx[None, :]
                                   < blk.window)
                if Ckv is not None:
                    # columns finalized dead (earlier chunks) or dropped by
                    # the kv pack (this chunk's own) were never projected /
                    # are pruned: deny them to every layer's attention
                    mask = mask & live_all_c
                elif live is not None:
                    # finite horizon without K/V packing: earlier-finalized
                    # columns are pruned; this chunk's own columns always
                    # materialize
                    mask = mask & live
                # row selection: the two modes differ only in *which* q/mask
                # rows the shared score/softmax/AV block sees.
                if packed:
                    # packed SPLS attention: compute only the union rows'
                    # scores (every head's leaders are in the union), then
                    # every row reads its leader's packed slot.  Bit-for-bit
                    # the simulation-mode path at Cq == CS; overflow rows
                    # fall back to their window leader.
                    qcomp = compact_rows(crit_any, Cq, leader=lead_local,
                                         window=scfg.window)
                    with jax.named_scope("qkv"):
                        q_sel = packed_project_q(cfg, bp["attn"], xn, sl,
                                                 qcomp.perm[0],
                                                 compute_backend)
                    perm_idx = qcomp.perm[:, None, None, :, None]
                    mask_sel = jnp.take_along_axis(mask, perm_idx, axis=-2)
                else:
                    # simulation-mode SPLS attention over all written slots:
                    # similar rows use their leader's Q row and mask row
                    # (leaders are window-local, hence chunk-local)
                    q_sel = gather_rows(q, lead_local)
                    mask_sel = jnp.take_along_axis(mask, lead_local[..., None],
                                                   axis=-2)
                s = jnp.einsum("bkgqd,bkld->bkgql", q_sel, kg) * (Dh ** -0.5)
                if cfg.attn_softcap is not None:
                    s = jnp.tanh(s / cfg.attn_softcap) * cfg.attn_softcap
                a = _masked_softmax(s, mask_sel)
                o = jnp.einsum("bkgql,bkld->bkgqd", a, vg)
                if packed:
                    o = jnp.take_along_axis(o, qcomp.src_slot[..., None],
                                            axis=-2)
                h = output_proj(cfg, bp["attn"], o, "structured")
            with jax.named_scope("ffn"):
                ffn_comp = None
                if packed and scfg.ffn_sparsity and not blk.use_moe:
                    ffn_comp = compact_rows(pb.ffn_critical, Cf,
                                            leader=pb.ffn_leader - start,
                                            window=scfg.window)
                x, _ = _residual_ffn(cfg, blk, bp, x, h,
                                     ffn_leader=(pb.ffn_leader - start
                                                 if scfg.ffn_sparsity
                                                 else None),
                                     ffn_comp=ffn_comp,
                                     compute_backend=compute_backend)
            new_caches.append(kc)
            new_preds.append(pk)
        carry_out = ((x, kv_written_c, live_all_c, n_kv_c)
                     if Ckv is not None else x)
        return carry_out, (tuple(new_caches), tuple(new_preds), kv_any0,
                           counts)

    if Ckv is not None:
        carry0 = (x, jnp.zeros((CS,), bool), live, jnp.zeros((), jnp.int32))
    else:
        carry0 = x
    with jax.named_scope("kv_pool"):
        carry, (new_cache, new_pred, kv_any, counts) = jax.lax.scan(
            scan_body, carry0,
            (params["periods"], cache, pred_cache,
             jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    x = carry[0] if Ckv is not None else carry
    with jax.named_scope("lm_head"):
        x_last = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
        logits = head_logits(cfg, params, x_last)
    return (logits, new_cache, new_pred, pos_pages,
            jax.tree.map(lambda a: a[0], kv_any), counts)


def compact_slots(cache, pos_pages: jax.Array, table: jax.Array,
                  keep: jax.Array) -> Tuple[tuple, jax.Array]:
    """End-of-prefill SPLS compaction, in place within a sequence's pages.

    keep: (S,) bool over the sequence's logical slots (slot == original
    position during prefill; slots past the prompt are False).  Kept
    slots move, in original order, to the first ``n_kept`` slots of the
    sequence's *own* pages; the freed tail is sentinel-filled so window
    masks never admit a stale id.  No transient page allocation: the
    engine frees the pages past ``ceil(n_kept / ps)`` afterwards.
    """
    N, ps = pos_pages.shape
    S = table.shape[0] * ps
    with jax.named_scope("kv_pool"):
        sl = jnp.arange(S)
        flat = table[sl // ps] * ps + sl % ps
        perm = jnp.argsort(~keep, stable=True)
        n_kept = keep.sum()
        src = flat[perm]
        pos_flat = pos_pages.reshape(N * ps)
        # unallocated table tails alias null-page slots: every such
        # collision writes POS_SENTINEL (j >= n_kept), so the scatter
        # stays deterministic
        vals = jnp.where(sl < n_kept, pos_flat[src], POS_SENTINEL)
        pos_pages = pos_flat.at[flat].set(vals).reshape(N, ps)

        new_blocks = []
        for pc in cache:
            nP, KV, N_, ps_, Dh = pc.k_pages.shape
            kf = pc.k_pages.reshape(nP, KV, N_ * ps_, Dh)
            vf = pc.v_pages.reshape(nP, KV, N_ * ps_, Dh)
            kf = kf.at[:, :, flat].set(kf[:, :, src])
            vf = vf.at[:, :, flat].set(vf[:, :, src])
            new_blocks.append(PagedKVCache(kf.reshape(nP, KV, N_, ps_, Dh),
                                           vf.reshape(nP, KV, N_, ps_, Dh)))
    return tuple(new_blocks), pos_pages
