"""The serving engine: continuous batching over a block-pool paged KV cache.

:class:`PagedServingEngine` keeps KV in a shared
:class:`~repro.serving.pager.PagePool`; requests hold block tables instead
of cache rows.  Every prompt prefills through the chunk step, one
``prefill_chunk``-token chunk a tick *between* decode ticks (no
head-of-line blocking); admission is keyed on free pages, and a dry pool
preempts the youngest sequence by page eviction.  With SPLS enabled,
prefill prunes dead KV columns out of the pool entirely
(``spls_token_keep``), so the paper's sparsity buys admission capacity,
not just skipped math.

Sampling: ``greedy=True`` (default) takes the argmax; ``greedy=False``
samples with ``temperature`` through a PRNG key threaded from ``seed``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.paged_decode import pages_visited
from repro.models.attn_backend import AUTO, resolve_backend
from repro.models.common import cast_compute, dtype_of, off_compute_width
from repro.models.moe import MOE_STATS
from repro.observability import Telemetry, tree_bytes
from repro.sparse_compute import (CapacityController, chunk_flops, is_packed,
                                  resolve_compute_backend)

from .pager import (NULL_PAGE, PagePool, init_paged_cache, init_pos_pages,
                    init_pred_cache, keep_from_votes)
from .paged_model import (compact_slots, paged_decode_step,
                          paged_prefill_chunk, paged_prefill_chunk_spls)
from .scheduler import Scheduler, SchedulerConfig, SeqState

__all__ = ["Request", "ServeConfig", "PagedServingEngine"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: jnp.ndarray            # (Lp,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # keep the logits the first token was sampled from (a host copy of
    # one vocab row), e.g. to check serving against a reference
    return_logits: bool = False
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    first_logits: Optional[np.ndarray] = None
    done: bool = False


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    n_slots: int = 4
    max_len: int = 256
    # sampling: greedy argmax by default; greedy=False samples with
    # `temperature` through a PRNG key threaded from `seed`
    greedy: bool = True
    temperature: float = 1.0
    seed: int = 0
    # paged decode backend (repro.models.attn_backend): "auto",
    # "pallas_paged_decode" or "xla_paged_decode"; None, or a backend of
    # another kind, keeps the model config's own
    attn_backend: Optional[str] = None
    page_size: int = 16
    n_pages: Optional[int] = None   # None -> n_slots * pages(max_len) + 1
    prefill_chunk: int = 64
    max_prefills_per_tick: int = 1
    watermark: int = 0
    spls_page_prune: bool = True    # prune dead KV columns out of the pool
    spls_prune_vote: float = 0.5    # head-vote fraction a column must win
    # round a misaligned prefill_chunk up to the next multiple of
    # spls.window (one-time warning) instead of raising
    auto_align_chunk: bool = False
    # end-to-end sparse compute on the SPLS chunked-prefill path
    # (repro.sparse_compute): None -> cfg.compute_backend ("dense" keeps
    # today's simulation-mode execution); "packed_xla"/"packed_pallas"
    # compute only critical rows at bucketed static capacities
    compute_backend: Optional[str] = None
    # static capacity bucket set for the packed path (None -> quarter
    # steps of prefill_chunk); the margin scales the EMA'd critical-row
    # estimate before bucket selection (sparse_compute.CapacityController)
    capacity_buckets: Optional[Tuple[int, ...]] = None
    capacity_margin: float = 1.25
    # horizon-finalized column votes (repro.core.planner): None keeps
    # the end-of-prefill prune vote bit-for-bit; a finite horizon h >= 1
    # finalizes a column as pruned once it has been votable for h
    # consecutive chunks while still below the cross-head agreement
    # threshold (ceil(spls_prune_vote * H) heads -- the same bar the
    # end-of-prefill vote applies, evaluated early; bounded divergence
    # for K/V savings).  h == 1 with a packed compute backend
    # additionally packs the K/V *projection* to the surviving columns
    # -- the chunk's own plan votes land before formal QKV generation,
    # so pruned columns are never projected at all.
    vote_horizon: Optional[int] = None
    # serving telemetry (repro.observability): per-request lifecycle
    # spans, TTFT/TPOT histograms, SPLS sparsity instruments, and the
    # BENCH_serving.json report.  Default-on; False swaps in no-op sinks
    # that record nothing (the back-compat `stats` counters stay live
    # either way -- they are engine state, not diagnostics).  All
    # instruments are host-side with injected monotonic timestamps;
    # greedy outputs are bit-for-bit identical on and off.
    telemetry: bool = True


def _paged_decode_name(name: Optional[str],
                       cfg_name: Optional[str]) -> Optional[str]:
    """The backend name the paged decode site resolves from
    ServeConfig.attn_backend ``name``.

    A registered backend of another kind (a forward or dense-decode one)
    keeps the model config's own (``cfg_name``, ``"auto"`` by default), so
    the registry's kind-mismatch warning stays reserved for genuine
    configuration errors (and ``STRICT_BACKEND_KIND`` stays usable with
    the engine).  A name no registry knows is passed through, so the
    registry rejects it."""
    if name is None:
        return cfg_name
    if name == AUTO:
        return name
    from repro.models import available_backends

    if name not in available_backends():
        return name
    return (name if name in available_backends(decode=True, paged=True)
            else cfg_name)


def _program(fn, *bound, **fixed):
    """``fn`` with its leading arguments ``bound`` and keywords ``fixed``,
    under ``fn``'s own name: ``jax.jit`` of it compiles a program named
    ``jit_<fn>``, which is how the device trace names it (a lambda or a
    ``functools.partial`` would read ``jit__lambda`` or
    ``jit__unknown``)."""
    def program(*args, **kwargs):
        return fn(*bound, *args, **fixed, **kwargs)

    program.__name__ = program.__qualname__ = fn.__name__
    return program


@functools.partial(jax.jit, static_argnames=("greedy", "temperature"))
def sample_tokens(key: Optional[jax.Array], logits: jax.Array,
                  greedy: bool, temperature: float) -> jax.Array:
    """logits (..., V) -> (...,) int32 token ids; one program,
    ``jit_sample_tokens``."""
    if greedy or temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits.astype(jnp.float32) / temperature, axis=-1
    ).astype(jnp.int32)


class PagedServingEngine:
    """Continuous batching over the block-pool paged KV cache."""

    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig):
        assert cfg.input_mode == "tokens", "engine serves token models"
        assert all(b.mixer == "attn" for b in cfg.period), \
            "paged engine is attention-only (SSM state is O(1) per slot)"
        # every request decodes, and every prompt prefills in chunks that
        # attend causally across chunks: a non-causal model needs a
        # request kind that yields no token
        assert cfg.causal, "the engine serves causal models"
        # SPLS streams its plan one window-aligned chunk at a time (the
        # paper's progressive generation scheme); the page-prune vote
        # accumulates across chunks
        if cfg.spls.enabled and scfg.prefill_chunk % cfg.spls.window:
            if scfg.auto_align_chunk:
                aligned = -(-scfg.prefill_chunk // cfg.spls.window) \
                    * cfg.spls.window
                warnings.warn(
                    f"prefill_chunk ({scfg.prefill_chunk}) is not a "
                    f"multiple of the SPLS similarity window "
                    f"({cfg.spls.window}); auto_align_chunk rounded it up "
                    f"to {aligned}", RuntimeWarning, stacklevel=2)
                scfg = dataclasses.replace(scfg, prefill_chunk=aligned)
            else:
                raise ValueError(
                    f"prefill_chunk ({scfg.prefill_chunk}) must be a "
                    f"multiple of the SPLS similarity window "
                    f"({cfg.spls.window}): chunk boundaries must align "
                    f"with similarity windows for chunked prefill to "
                    f"reproduce the whole-prompt plan (set "
                    f"ServeConfig.auto_align_chunk=True to round up)")
        # the weights at compute width, cast once here: the steps compute
        # with them as given (an MoE router's stay float32).  The caller's
        # tree is neither donated nor deleted: it may still use it.
        dtype = dtype_of(cfg.compute_dtype)
        if off_compute_width(params, dtype):
            params = jax.jit(cast_compute, static_argnums=1)(params, dtype)
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self._key = jax.random.PRNGKey(scfg.seed)

        ps = scfg.page_size
        self.page_size = ps
        self.pages_per_seq = math.ceil(scfg.max_len / ps)
        n_pages = (scfg.n_pages if scfg.n_pages is not None
                   else scfg.n_slots * self.pages_per_seq + 1)
        self.pool = PagePool(n_pages, ps)
        self._prune = cfg.spls.enabled and scfg.spls_page_prune
        # end-to-end sparse compute (the SPLS chunked-prefill path):
        # "dense" keeps simulation-mode execution; packed backends compute
        # only critical rows at bucketed static capacities (one jit per
        # bucket pair) with leaders broadcasting to their followers
        self._compute = resolve_compute_backend(
            scfg.compute_backend if scfg.compute_backend is not None
            else cfg.compute_backend, sparse=cfg.spls.enabled)
        # horizon-finalized column votes (core.planner): a finite horizon
        # needs page pruning (the horizon decision *is* a prune decision)
        self._horizon = scfg.vote_horizon
        # the horizon's early finalization applies the same cross-head
        # agreement bar as the end-of-prefill vote (keep_from_votes)
        self._vote_need = max(1, math.ceil(scfg.spls_prune_vote
                                           * cfg.n_heads))
        if self._horizon is not None:
            if self._horizon < 1:
                raise ValueError(
                    f"vote_horizon must be >= 1 chunks (or None for the "
                    f"end-of-prefill vote), got {self._horizon}")
            if not (cfg.spls.enabled and self._prune):
                raise ValueError(
                    "vote_horizon requires SPLS (cfg.spls.enabled) and page "
                    "pruning (ServeConfig.spls_page_prune): the horizon "
                    "finalizes the streaming prune vote early")
        cs = scfg.prefill_chunk
        if is_packed(self._compute):
            self._cap_q = CapacityController(
                cs, buckets=scfg.capacity_buckets,
                margin=scfg.capacity_margin)
            self._cap_f = CapacityController(
                cs, buckets=scfg.capacity_buckets,
                margin=scfg.capacity_margin)
            # K/V projection capacity: only meaningful at vote_horizon == 1
            # (the only horizon whose decision precedes K/V generation)
            self._cap_kv = (CapacityController(
                cs, buckets=scfg.capacity_buckets,
                margin=scfg.capacity_margin)
                if self._horizon == 1 else None)
        else:
            self._cap_q = self._cap_f = self._cap_kv = None
        self.telemetry = Telemetry(enabled=scfg.telemetry)
        self.sched = Scheduler(
            SchedulerConfig(n_slots=scfg.n_slots,
                            prefill_chunk=scfg.prefill_chunk,
                            max_prefills_per_tick=scfg.max_prefills_per_tick,
                            watermark=scfg.watermark),
            self.pool, scfg.max_len, prune_aware=self._prune,
            telemetry=self.telemetry)

        self.cache = init_paged_cache(cfg, n_pages, ps)
        self.pos_pages = init_pos_pages(n_pages, ps)
        self.pred_cache = (init_pred_cache(cfg, n_pages, ps)
                           if cfg.spls.enabled else None)
        self._retired: List[Request] = []
        # resolved once (the same call paged_decode_step makes), so a
        # wrong-kind name fails at construction under STRICT_BACKEND_KIND
        # and stats name the kernel every decode tick dispatches to
        self.decode_backend = dec_be = resolve_backend(
            _paged_decode_name(scfg.attn_backend, cfg.attn_backend), cfg,
            L=n_pages * ps, decode=True, paged=True)
        # the old cache / pos_pages references die on reassignment every
        # tick, so donate them: decode scatters one token in place instead
        # of copying the whole page pool (donation is a no-op on CPU).
        # Every program keeps its function's name (jit_paged_decode_step,
        # ...), so the device trace can say which one an op ran in.
        self._decode = jax.jit(
            _program(paged_decode_step, cfg, backend=dec_be),
            donate_argnums=(1, 2))
        self._chunk = jax.jit(_program(paged_prefill_chunk, cfg),
                              donate_argnums=(1, 2))
        # SPLS chunk step: one jit covers *all* prompt lengths (top-k
        # count, start, and valid ride in as traced scalars); under packed
        # compute, one jit per capacity-bucket pair (the controller keeps
        # the pair set small)
        self._chunk_spls_jits: dict = {}
        self._compact = jax.jit(compact_slots, donate_argnums=(0, 1))
        # pool and weight byte gauges (metadata only, no device sync)
        self.telemetry.sparsity.note_pool_bytes(tree_bytes(self.cache),
                                                tree_bytes(self.pred_cache))
        self.telemetry.sparsity.note_weight_bytes(self.params)

    def _get_chunk_spls(self, cq: Optional[int], cf: Optional[int],
                        ckv: Optional[int] = None, horizon: bool = False):
        """Jitted SPLS chunk step for one capacity-bucket triple (dense
        compute uses the single ``(None, None, None)`` entry); ``horizon``
        adds the liveness-mask + decode-anchor operands of the
        horizon-finalized vote, passed as the keywords ``live`` and
        ``last_keep``."""
        key = (cq, cf, ckv, horizon)
        fn = self._chunk_spls_jits.get(key)
        if fn is None:
            fixed = dict(q_capacity=cq, ffn_capacity=cf,
                         compute_backend=self._compute)
            if horizon:
                fixed.update(kv_capacity=ckv, kv_vote_need=self._vote_need)
            fn = jax.jit(_program(paged_prefill_chunk_spls, self.cfg,
                                  **fixed),
                         donate_argnums=(1, 2, 3))
            self._chunk_spls_jits[key] = fn
        return fn

    # ------------------------------------------------------------------
    @property
    def stats(self) -> dict:
        """Back-compat dict view over the typed instruments: the
        scheduler counters (live ``CounterDictView``), pool gauges, and
        capacity-controller snapshots, assembled fresh per read."""
        out = {**self.sched.stats,
               "pages_in_use": self.pool.pages_in_use,
               "peak_pages": self.pool.peak_in_use,
               "free_pages": self.pool.free_pages,
               "guard_trips": self.pool.guard_trips,
               "compute_backend": self._compute,
               "decode_backend": self.decode_backend,
               "flops_saved_pct": self.sched.flops_saved_pct()}
        if self._cap_q is not None:
            out["capacity_q"] = self._cap_q.snapshot()
            out["capacity_ffn"] = self._cap_f.snapshot()
        if self._cap_kv is not None:
            out["capacity_kv"] = self._cap_kv.snapshot()
        return out

    def submit(self, req: Request) -> None:
        lp = int(req.prompt.shape[0])
        if lp > self.scfg.max_len:
            raise ValueError(f"request {req.rid}: prompt {lp} exceeds "
                             f"max_len {self.scfg.max_len}")
        self.sched.submit(req, [int(t) for t in np.asarray(req.prompt)],
                          req.max_new_tokens)
        # recorded only once the scheduler accepted it (a rejected
        # request would leave an unclosed lifecycle span)
        self.telemetry.request_submitted(req.rid, lp)

    # ------------------------------------------------------------------
    def _table_row(self, st: SeqState) -> np.ndarray:
        row = np.full((self.pages_per_seq,), NULL_PAGE, np.int32)
        row[:len(st.pages)] = st.pages
        return row

    def _chunk_prefill(self, st: SeqState) -> None:
        """One prompt chunk.  ``engine/prefill_chunk`` covers the page
        growth, the host preparation and the dispatch; the dense step is
        enqueued and not waited for, while the SPLS step reads its votes
        back inside the span."""
        cs = self.sched.cfg.prefill_chunk
        start = st.prefilled                 # == st.kv_len (columns stay
        #                          dense until the end-of-prefill compaction)
        valid = min(cs, st.prompt_len - start)
        with self.telemetry.span("engine/prefill_chunk", rid=st.req.rid,
                                 start=start, valid=valid):
            logits = self._chunk_step(st, start, valid)
        if logits is None:
            return   # preempted/aborted; telemetry unwound the track
        if st.phase == "decode":
            if self._prune and self.cfg.spls.enabled:
                with self.telemetry.span("engine/prune_compact",
                                         rid=st.req.rid):
                    self._finish_chunk_prune(st)
            self._emit_first(st, logits[0, 0])

    def _chunk_step(self, st: SeqState, start: int,
                    valid: int) -> Optional[jax.Array]:
        """Grow the sequence's pages and run the chunk step; the logits,
        or None where growing preempted the sequence."""
        tel = self.telemetry
        cs = self.sched.cfg.prefill_chunk
        if not self.sched.grow_to(st, start + valid):
            return None
        span_args = {"start": start, "valid": valid}
        tel.span_begin("prefill_chunk", rid=st.req.rid, args=span_args)
        chunk = np.zeros((cs,), np.int32)
        chunk[:valid] = st.tokens[start:start + valid]
        if self.cfg.spls.enabled:
            from repro.core.planner import horizon_update_live
            from repro.core.topk import topk_count
            k = topk_count(st.prompt_len, self.cfg.spls.k_ratio)
            packed = self._cap_q is not None
            cq = self._cap_q.capacity() if packed else None
            cf = (self._cap_f.capacity()
                  if packed and self.cfg.spls.ffn_sparsity else None)
            ckv = (self._cap_kv.capacity()
                   if self._cap_kv is not None else None)
            horizon = self._horizon
            S = self.pages_per_seq * self.page_size
            last_keep = st.prompt_len - 1
            args = [self.params, self.cache, self.pred_cache,
                    self.pos_pages, jnp.asarray(self._table_row(st)),
                    jnp.asarray(start, jnp.int32),
                    jnp.asarray(chunk)[None, :],
                    jnp.asarray(valid, jnp.int32), jnp.asarray(k, jnp.int32)]
            kw = {}
            if horizon is not None:
                if st.live is None:
                    st.live = np.ones((S,), bool)
                kw = dict(live=jnp.asarray(st.live),
                          last_keep=jnp.asarray(last_keep, jnp.int32))
            (logits, self.cache, self.pred_cache, self.pos_pages,
             kv_any, counts) = self._get_chunk_spls(
                cq, cf, ckv, horizon is not None)(*args, **kw)
            if self._prune:
                # cross-chunk vote accumulator: a head's "some row kept
                # this column" bit only ever turns on, so OR is exact
                votes = np.asarray(kv_any).reshape(self.cfg.n_heads, -1)
                st.head_votes = (votes if st.head_votes is None
                                 else st.head_votes | votes)
            if horizon is not None:
                # finalize columns whose probation expired below the
                # cross-head vote threshold (and mirror the device's
                # kv_capacity pack decision for this chunk's own columns
                # -- core.planner owns both)
                st.live = horizon_update_live(
                    st.live, st.head_votes.sum(axis=0), start=start,
                    valid=valid, chunk=cs, horizon=horizon,
                    last_keep=last_keep, vote_need=self._vote_need,
                    kv_capacity=ckv, metrics=tel.metrics)
            if packed:
                # the host readback of the critical counts syncs on the
                # chunk step; only the packed path pays it (dense compute
                # discards the counts and stays fully async)
                n_q, n_f, n_kv = (int(v)
                                  for v in np.asarray(counts).max(axis=0))
                self._cap_q.observe(n_q)
                if n_q > cq:
                    self._cap_q.note_overflow()
                tel.sparsity.note_capacity("q", cq, n_q, n_q > cq)
                if self.cfg.spls.ffn_sparsity:
                    self._cap_f.observe(n_f)
                    if n_f > cf:
                        self._cap_f.note_overflow()
                    tel.sparsity.note_capacity("ffn", cf, n_f, n_f > cf)
                if ckv is not None:
                    self._cap_kv.observe(n_kv)
                    if n_kv > ckv:
                        self._cap_kv.note_overflow()
                    tel.sparsity.note_capacity("kv", ckv, n_kv, n_kv > ckv)
            self.sched.note_flops(chunk_flops(
                self.cfg, cs, start + valid, q_rows=cq, ffn_rows=cf,
                kv_rows=ckv))
        else:
            logits, self.cache, self.pos_pages, *moe = self._chunk(
                self.params, self.cache, self.pos_pages,
                jnp.asarray(self._table_row(st)),
                jnp.asarray(start, jnp.int32), jnp.asarray(chunk)[None, :],
                jnp.asarray(valid, jnp.int32))
            if moe:
                tel.trace.defer(span_args, MOE_STATS, moe[0])
            self.sched.note_flops(chunk_flops(self.cfg, cs, start + valid))
        st.prefilled += valid
        st.kv_len += valid
        st.cur_pos += valid
        self.sched.stats["prefill_chunks"] += 1
        tel.span_end("prefill_chunk", rid=st.req.rid)
        return logits

    def _finish_chunk_prune(self, st: SeqState) -> None:
        """The page-prune vote is final once every prompt row has voted
        (votes are monotone in rows, so pruning any earlier would diverge
        from the whole-prompt decision, ``spls_token_keep``): threshold
        the accumulated head votes, compact kept columns, in original
        order, into the front of the sequence's own pages, and free the
        tail."""
        tel = self.telemetry
        tel.span_begin("prune_compact", rid=st.req.rid)
        Lp = st.prompt_len
        S = self.pages_per_seq * self.page_size
        tel.sparsity.note_votes(st.head_votes[:, :Lp])
        votes = st.head_votes.sum(axis=0).astype(np.int32)
        keep = keep_from_votes(votes[:Lp], self.cfg.n_heads,
                               self.scfg.spls_prune_vote)
        if st.live is not None:
            # horizon-finalized columns are gone even if they gathered
            # votes later could not reach them; and a voted own-column the
            # kv_capacity pack dropped was never materialized -- the final
            # keep set must honor both (the decode anchor stays live)
            keep &= st.live[:Lp]
        n_kept = int(keep.sum())
        keep_slots = np.zeros((S,), bool)
        keep_slots[:Lp] = keep
        self.cache, self.pos_pages = self._compact(
            self.cache, self.pos_pages, jnp.asarray(self._table_row(st)),
            jnp.asarray(keep_slots))
        needed = self.pool.pages_for(n_kept)
        if needed < len(st.pages):
            self.pool.free(st.pages[needed:])
            st.pages = st.pages[:needed]
        st.kv_len = n_kept
        st.head_votes = None
        self.sched.note_prune(Lp, n_kept)
        tel.sparsity.note_prune(Lp, n_kept)
        tel.span_end("prune_compact", rid=st.req.rid,
                     args={"kept": n_kept, "prompt_len": Lp})

    def _pick(self, logits: jax.Array) -> jax.Array:
        key = None
        if not self.scfg.greedy:
            self._key, key = jax.random.split(self._key)
        return sample_tokens(key, logits, greedy=self.scfg.greedy,
                             temperature=self.scfg.temperature)

    def _emit_first(self, st: SeqState, logits_row: jax.Array) -> None:
        """The first token; its readback waits for the prefill step."""
        with self.telemetry.span("engine/emit_first", rid=st.req.rid):
            if st.req.return_logits:
                st.req.first_logits = np.asarray(logits_row, np.float32)
            tok = int(self._pick(logits_row))
            st.req.output.append(tok)
            st.budget -= 1
        self.telemetry.first_token(st.req.rid)

    # ------------------------------------------------------------------
    def tick(self) -> int:
        """One engine iteration; returns number of slots decoded.

        With telemetry on, ``engine/tick`` holds one span per host phase
        (:meth:`Telemetry.span`): ``engine/admit``, then per planned
        prefill ``engine/prefill_chunk``, ``engine/prune_compact`` and
        ``engine/emit_first``, then ``engine/retire``,
        ``engine/decode_prepare``, ``engine/decode_dispatch``,
        ``engine/decode_readback``, ``engine/retire`` and
        ``engine/pool_observe``.  None of them waits for the device."""
        tel = self.telemetry
        with tel.span("engine/tick"):
            with tel.span("engine/admit"):
                self.sched.admit()
                plans = self.sched.plan_prefills()
            for st in plans:
                if self.sched.slots[st.slot] is not st:
                    continue  # preempted by an earlier prefill this tick
                self._chunk_prefill(st)
            with tel.span("engine/retire"):
                # a prefill-emitted token may hit eos/budget
                self._retire_finished()
            n_decoded = self._decode_tick()
            with tel.span("engine/retire"):
                self._retire_finished()
            with tel.span("engine/pool_observe"):
                # sample after retirement so a drained pool reads 0
                tel.sparsity.observe_pool(self.pool)
        return n_decoded

    def _decode_tick(self) -> int:
        """One batched decode step over every decode-ready sequence.

        The ``decode_tick`` span runs from the block tables to the tokens'
        readback; its args count the active slots, the pages that hold
        their KV once this step has written (``pages_live``), and the
        pages the Pallas decode kernel copies (``pages_grid``: the pages
        holding each row's attended slots, one null page for an inactive
        row; ``paged_decode.pages_visited`` of this step's lengths).  A
        model with held experts adds the step's ``moe_pairs``,
        ``moe_touched`` and ``moe_peak`` (``repro.models.moe.MOE_STATS``),
        read back without a sync (``TraceRecorder.defer``); the
        ``prefill_chunk`` span carries the chunk step's the same way."""
        tel = self.telemetry
        with tel.span("engine/decode_prepare"):
            # grow pages for every decode-ready row (may preempt the
            # youngest)
            for st in list(self.sched.decode_ready()):
                if self.sched.slots[st.slot] is not st or st.budget <= 0:
                    continue
                self.sched.grow_to(st, st.kv_len + 1)
            active = [st for st in self.sched.decode_ready()
                      if st.budget > 0
                      and len(st.pages) * self.page_size > st.kv_len]
            if not active:
                return 0
            n_slots = self.scfg.n_slots
            kv_len = np.zeros((n_slots,), np.int32)
            for st in active:
                kv_len[st.slot] = st.kv_len
            # the step attends over kv_len + 1 slots of every row
            span_args = {
                "n_active": len(active),
                "pages_live": sum(self.pool.pages_for(st.kv_len + 1)
                                  for st in active),
                "pages_grid": pages_visited(kv_len + 1, self.page_size)}
            tel.span_begin("decode_tick", args=span_args)
            tables = np.full((n_slots, self.pages_per_seq), NULL_PAGE,
                             np.int32)
            cur_pos = np.zeros((n_slots,), np.int32)
            tokens = np.zeros((n_slots, 1), np.int32)
            for st in active:
                tables[st.slot] = self._table_row(st)
                cur_pos[st.slot] = st.cur_pos
                tokens[st.slot, 0] = st.req.output[-1]
            step_args = (jnp.asarray(tables), jnp.asarray(kv_len),
                         jnp.asarray(cur_pos), jnp.asarray(tokens))
        with tel.span("engine/decode_dispatch"):
            logits, self.cache, self.pos_pages, *moe = self._decode(
                self.params, self.cache, self.pos_pages, *step_args)
            if moe:
                tel.trace.defer(span_args, MOE_STATS, moe[0])
        with tel.span("engine/decode_readback"):
            nxt = self._pick(logits[:, 0])
            for st in active:
                st.req.output.append(int(nxt[st.slot]))
                st.kv_len += 1
                st.cur_pos += 1
                st.budget -= 1
        tel.span_end("decode_tick")
        tel.tokens_decoded([st.req.rid for st in active])
        return len(active)

    def _retire_finished(self) -> None:
        # requests the scheduler aborted (optimistic admission that never
        # fit; see Scheduler.grow_to) retire with whatever they generated
        for req in self.sched.aborted:
            req.done = True
            self._retired.append(req)
            self.telemetry.request_aborted(req.rid)
        self.sched.aborted.clear()
        for st in list(self.sched.active()):
            req = st.req
            hit_eos = req.eos_id is not None and req.eos_id in req.output
            if (st.phase == "decode"
                    and (st.budget <= 0 or hit_eos
                         or st.cur_pos >= self.scfg.max_len - 1)):
                req.done = True
                self.sched.retire(st)
                self._retired.append(req)
                self.telemetry.request_retired(req.rid)

    def run_until_drained(self, max_ticks: int = 10000) -> List[Request]:
        """Tick until everything drains; returns the requests retired
        during this call, in retirement order."""
        start = len(self._retired)
        for _ in range(max_ticks):
            self.tick()
            if self.sched.idle():
                break
        return self._retired[start:]
