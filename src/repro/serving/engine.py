"""Serving engines: dense fixed-slot and block-pool paged.

:class:`ServingEngine` is the original continuous-batching engine -- a
dense ``n_slots x max_len`` KV cache, whole-prompt prefill into a free
slot, one batched decode per tick.  It remains the baseline (and the
parity oracle) for the paged engine.

:class:`PagedServingEngine` is the production-shaped path: KV lives in a
shared :class:`~repro.serving.pager.PagePool`, requests hold block tables
instead of cache rows, prompts longer than a chunk prefill incrementally
*between* decode ticks (no head-of-line blocking), admission is keyed on
free pages, and a dry pool preempts the youngest sequence by page
eviction.  With SPLS enabled, prefill prunes dead KV columns out of the
pool entirely (``spls_token_keep``), so the paper's sparsity buys
admission capacity, not just skipped math.

Both engines share :class:`Request`/:class:`ServeConfig` and the sampling
path: ``greedy=True`` (default) takes the argmax; ``greedy=False`` samples
with ``temperature`` through a PRNG key threaded from ``seed``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from collections import deque
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import decode_step, init_cache, prefill
from repro.models.attn_backend import AUTO, resolve_backend
from repro.observability import Telemetry, tree_bytes
from repro.sparse_compute import (CapacityController, chunk_flops, is_packed,
                                  resolve_compute_backend)

from .pager import (NULL_PAGE, PagePool, init_paged_cache, init_pos_pages,
                    init_pred_cache, keep_from_votes, spls_token_votes)
from .paged_model import (compact_slots, paged_decode_step,
                          paged_prefill_chunk, paged_prefill_chunk_spls,
                          scatter_prefill)
from .scheduler import Scheduler, SchedulerConfig, SeqState

__all__ = ["Request", "ServeConfig", "ServingEngine", "PagedServingEngine"]


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
    # attention backend override for this engine (None = cfg/auto); see
    # repro.models.attn_backend -- prefill resolves the forward side
    # (e.g. "pallas_flash"), ticks resolve the decode side (the paged
    # engine resolves the *paged* decode side).
    attn_backend: Optional[str] = None
    # paged-engine knobs (ignored by the dense engine)
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


def _backend_for_site(name: Optional[str], cfg_name: Optional[str], *,
                      decode: bool, paged: bool = False) -> Optional[str]:
    """Route a ServeConfig.attn_backend name to one engine site.

    The single config field intentionally drives every site an engine
    has; a site of a different kind keeps the model config's own backend
    (``cfg_name``, ``"auto"`` by default), so an engine can pin its
    decode backend through ServeConfig and its forward backend through
    the ArchConfig.  Doing the kind split *here* keeps the registry's
    kind-mismatch warning reserved for genuine configuration errors
    instead of firing on the engines' own documented fall-through (and
    keeps ``STRICT_BACKEND_KIND`` usable with the engines).  A name no
    site knows is passed through, so the registry rejects it."""
    if name is None or name == AUTO:
        return name
    from repro.models import available_backends

    if name not in available_backends():
        return name
    return (name if name in available_backends(decode=decode, paged=paged)
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


class _SamplerMixin:
    def _init_sampler(self, scfg: ServeConfig) -> None:
        self.scfg = scfg
        self._key = jax.random.PRNGKey(scfg.seed)

    def _pick(self, logits: jax.Array) -> jax.Array:
        key = None
        if not self.scfg.greedy:
            self._key, key = jax.random.split(self._key)
        return sample_tokens(key, logits, greedy=self.scfg.greedy,
                             temperature=self.scfg.temperature)


# ---------------------------------------------------------------------------
# dense fixed-slot engine (the baseline / parity oracle)
# ---------------------------------------------------------------------------

class ServingEngine(_SamplerMixin):
    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig):
        assert cfg.input_mode == "tokens", "engine serves token models"
        # the dense engine has no packed-compute path (it is the
        # simulation-mode parity oracle); surface a requested packed
        # backend loudly instead of silently measuring dense compute
        if is_packed(resolve_compute_backend(
                scfg.compute_backend if scfg.compute_backend is not None
                else cfg.compute_backend, sparse=cfg.spls.enabled)):
            warnings.warn(
                "ServingEngine (dense fixed-slot) executes dense compute "
                "only; the configured packed compute_backend applies to "
                "PagedServingEngine's chunked SPLS prefill and is ignored "
                "here", RuntimeWarning, stacklevel=2)
        if scfg.vote_horizon is not None:
            warnings.warn(
                "ServingEngine prefills whole prompts with the "
                "end-of-prefill prune vote; vote_horizon applies to "
                "PagedServingEngine's chunked SPLS prefill and is ignored "
                "here", RuntimeWarning, stacklevel=2)
        cfg_fwd, cfg_dec = cfg, cfg
        if scfg.attn_backend is not None:
            cfg_fwd = dataclasses.replace(cfg, attn_backend=_backend_for_site(
                scfg.attn_backend, cfg.attn_backend, decode=False))
            cfg_dec = dataclasses.replace(cfg, attn_backend=_backend_for_site(
                scfg.attn_backend, cfg.attn_backend, decode=True))
        self.cfg, self.params = cfg, params
        self._init_sampler(scfg)
        self.telemetry = Telemetry(enabled=scfg.telemetry)
        self.queue: deque = deque()
        self.slots: List[Optional[Request]] = [None] * scfg.n_slots
        self.pos = jnp.zeros((scfg.n_slots,), jnp.int32)
        self.tokens = jnp.zeros((scfg.n_slots, 1), jnp.int32)
        self.cache = init_cache(cfg, scfg.n_slots, scfg.max_len)
        self._retired: List[Request] = []
        self._decode = jax.jit(_program(decode_step, cfg_dec))
        # SPLS configs prefill with the progressive (streaming-
        # reproducible) plan builder so this engine stays the exact parity
        # oracle for the paged engine's chunked SPLS prefill
        plan_mode = "progressive" if cfg.spls.enabled else "auto"
        self._prefill = jax.jit(_program(prefill, cfg_fwd,
                                         max_len=scfg.max_len,
                                         plan_mode=plan_mode))

    # ------------------------------------------------------------------
    @property
    def stats(self) -> dict:
        """Minimal stats view (the paged engine carries the full set);
        dense compute executes everything, so savings are all zero."""
        return {"retired": len(self._retired),
                "compute_backend": "dense",
                "flops_saved_pct": {}}

    def submit(self, req: Request) -> None:
        self.telemetry.request_submitted(req.rid,
                                         int(req.prompt.shape[0]))
        self.queue.append(req)

    def _admit(self) -> None:
        """Move queued requests into free slots (prefill their prompt)."""
        for s in range(self.scfg.n_slots):
            if self.slots[s] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            self.telemetry.request_admitted(req.rid)
            lp = int(req.prompt.shape[0])
            self.telemetry.span_begin("full_prefill", rid=req.rid)
            logits, cache1 = self._prefill(self.params,
                                           req.prompt[None, :])
            # splice this row's prefilled cache into slot s
            self.cache = jax.tree.map(
                lambda full, one: full.at[:, s:s + 1].set(one),
                self.cache, cache1)
            if req.return_logits:
                req.first_logits = np.asarray(logits[0, -1], np.float32)
            nxt = int(self._pick(logits[0, -1]))
            req.output.append(nxt)
            self.telemetry.span_end("full_prefill", rid=req.rid)
            self.telemetry.first_token(req.rid)
            self.slots[s] = req
            self.pos = self.pos.at[s].set(lp)
            self.tokens = self.tokens.at[s, 0].set(nxt)

    def _retire(self) -> None:
        for s, req in enumerate(self.slots):
            if req is None:
                continue
            hit_eos = req.eos_id is not None and req.eos_id in req.output
            if len(req.output) >= req.max_new_tokens or hit_eos or \
                    int(self.pos[s]) >= self.scfg.max_len - 1:
                req.done = True
                self.slots[s] = None
                self._retired.append(req)
                self.telemetry.request_retired(req.rid)

    def tick(self) -> int:
        """One engine iteration; returns number of active slots decoded."""
        self._admit()
        self._retire()  # a prefill-emitted token may already hit eos/budget
        active = [s for s, r in enumerate(self.slots) if r is not None]
        if not active:
            return 0
        self.telemetry.span_begin("decode_tick",
                                  args={"n_active": len(active)})
        logits, self.cache = self._decode(self.params, self.cache,
                                          self.tokens, self.pos)
        nxt = self._pick(logits[:, 0])
        for s in active:
            tok = int(nxt[s])
            self.slots[s].output.append(tok)
        self.telemetry.span_end("decode_tick")
        self.telemetry.tokens_decoded(
            [self.slots[s].rid for s in active])
        self.pos = self.pos + jnp.asarray(
            [1 if self.slots[s] is not None else 0
             for s in range(self.scfg.n_slots)], jnp.int32)
        self.tokens = nxt[:, None]
        self._retire()
        return len(active)

    def run_until_drained(self, max_ticks: int = 10000) -> List[Request]:
        """Tick until queue and slots are empty; returns the requests that
        retired during this call, in retirement order."""
        start = len(self._retired)
        for _ in range(max_ticks):
            self.tick()
            if not self.queue and all(s is None for s in self.slots):
                break
        return self._retired[start:]


# ---------------------------------------------------------------------------
# paged engine
# ---------------------------------------------------------------------------

class PagedServingEngine(_SamplerMixin):
    """Continuous batching over the block-pool paged KV cache."""

    def __init__(self, cfg: ArchConfig, params, scfg: ServeConfig):
        assert cfg.input_mode == "tokens", "engine serves token models"
        assert all(b.mixer == "attn" for b in cfg.period), \
            "paged engine is attention-only (SSM state is O(1) per slot)"
        cfg_fwd, cfg_pgd = cfg, cfg
        if scfg.attn_backend is not None:
            cfg_fwd = dataclasses.replace(cfg, attn_backend=_backend_for_site(
                scfg.attn_backend, cfg.attn_backend, decode=False))
            cfg_pgd = dataclasses.replace(cfg, attn_backend=_backend_for_site(
                scfg.attn_backend, cfg.attn_backend, decode=True,
                paged=True))
        # chunked prefill needs causal cross-chunk attention.  SPLS no
        # longer disables it: the plan streams one window-aligned chunk at
        # a time (the paper's progressive generation scheme) and the
        # page-prune vote accumulates across chunks.
        chunkable = cfg.causal
        if cfg.spls.enabled and chunkable \
                and scfg.prefill_chunk % cfg.spls.window:
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
                    f"reproduce the full-prefill plan (set "
                    f"ServeConfig.auto_align_chunk=True to round up)")
        self.cfg, self.params = cfg, params
        self._init_sampler(scfg)

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
        # needs the streaming chunked path AND page pruning (the horizon
        # decision *is* a prune decision)
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
            if not (cfg.spls.enabled and self._prune and chunkable):
                raise ValueError(
                    "vote_horizon requires SPLS (cfg.spls.enabled), page "
                    "pruning (ServeConfig.spls_page_prune) and a causal "
                    "model (chunked prefill): the horizon finalizes the "
                    "streaming prune vote early")
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
            self.pool, scfg.max_len, chunkable=chunkable,
            prune_aware=self._prune,
            # packed compute: route whole prompts (<= one chunk) through
            # the chunk path too, so short prompts get token compaction
            # instead of silently running the dense full-prefill path
            chunk_all=is_packed(self._compute),
            telemetry=self.telemetry)

        self.cache = init_paged_cache(cfg, n_pages, ps)
        self.pos_pages = init_pos_pages(n_pages, ps)
        # the paged SPLS predictor cache is allocated lazily on the first
        # chunked SPLS prefill: full-prefill-only workloads (every prompt
        # <= prefill_chunk) never pay its pool memory
        self.pred_cache = None
        self._n_pages = n_pages
        self._retired: List[Request] = []
        # resolved once (the same call paged_decode_step makes), so a
        # wrong-kind name fails at construction under STRICT_BACKEND_KIND
        # and stats name the kernel every decode tick dispatches to
        self.decode_backend = dec_be = resolve_backend(
            cfg_pgd.attn_backend, cfg, L=n_pages * ps, decode=True,
            paged=True)
        # the old cache / pos_pages references die on reassignment every
        # tick, so donate them: decode scatters one token in place instead
        # of copying the whole page pool (donation is a no-op on CPU).
        # Every program keeps its function's name (jit_paged_decode_step,
        # ...), so the device trace can say which one an op ran in.
        self._decode = jax.jit(
            _program(paged_decode_step, cfg, backend=dec_be),
            donate_argnums=(1, 2))
        plan_mode = "progressive" if cfg.spls.enabled else "auto"
        self._prefill = jax.jit(_program(prefill, cfg_fwd,
                                         plan_mode=plan_mode))
        self._votes = jax.jit(_program(spls_token_votes, cfg))
        self._chunk = jax.jit(_program(paged_prefill_chunk, cfg),
                              donate_argnums=(1, 2))
        # SPLS chunk step: one jit covers *all* prompt lengths (top-k
        # count, start, and valid ride in as traced scalars); under packed
        # compute, one jit per capacity-bucket pair (the controller keeps
        # the pair set small)
        self._chunk_spls_jits: dict = {}
        self._compact = jax.jit(compact_slots, donate_argnums=(0, 1))
        # pool byte gauges (metadata only, no device sync); the predictor
        # cache gauge updates when its lazy allocation lands
        self.telemetry.sparsity.note_pool_bytes(tree_bytes(self.cache))

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
    def _dest_slots(self, st: SeqState, n: int) -> np.ndarray:
        """(n,) flat page-slot destinations for logical slots [0, n)."""
        pages = np.asarray(st.pages, np.int64)
        sl = np.arange(n)
        return pages[sl // self.page_size] * self.page_size \
            + sl % self.page_size

    def _table_row(self, st: SeqState) -> np.ndarray:
        row = np.full((self.pages_per_seq,), NULL_PAGE, np.int32)
        row[:len(st.pages)] = st.pages
        return row

    def _full_prefill(self, st: SeqState) -> None:
        """Whole-prompt prefill.  ``engine/full_prefill`` covers the
        dispatch and, with page pruning, the vote readback."""
        tel = self.telemetry
        with tel.span("engine/full_prefill", rid=st.req.rid,
                      prompt_len=st.prompt_len):
            tel.span_begin("full_prefill", rid=st.req.rid,
                           args={"prompt_len": st.prompt_len})
            toks = jnp.asarray(st.tokens, jnp.int32)[None, :]
            logits, dense_cache = self._prefill(self.params, toks)
            if self._prune:
                keep = keep_from_votes(self._votes(self.params, toks[0]),
                                       self.cfg.n_heads,
                                       self.scfg.spls_prune_vote)
            else:
                keep = np.ones((st.prompt_len,), bool)
            keep_idx = np.nonzero(keep)[0]
            n_kept = len(keep_idx)
            if not self.sched.grow_to(st, n_kept):
                # st itself was preempted (span unwound by the
                # preempt/abort telemetry); prefill recomputes later
                return
            dest = self._dest_slots(st, n_kept)
            self.cache, self.pos_pages = scatter_prefill(
                self.cache, self.pos_pages, dense_cache,
                jnp.asarray(keep_idx, jnp.int32),
                jnp.asarray(dest, jnp.int32))
            st.kv_len = n_kept
            st.cur_pos = st.prompt_len
            st.prefilled = st.prompt_len
            # whole-prompt prefill runs dense/simulation compute (packed
            # capacities apply on the chunked path); charged dense ==
            # executed
            self.sched.note_flops(chunk_flops(self.cfg, st.prompt_len,
                                              st.prompt_len))
            if self._prune:
                self.sched.note_prune(st.prompt_len, n_kept)
                tel.sparsity.note_prune(st.prompt_len, n_kept)
            tel.span_end("full_prefill", rid=st.req.rid,
                         args={"kept": n_kept})
        self._emit_first(st, logits[0, -1])

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
        tel.span_begin("prefill_chunk", rid=st.req.rid,
                       args={"start": start, "valid": valid})
        chunk = np.zeros((cs,), np.int32)
        chunk[:valid] = st.tokens[start:start + valid]
        if self.cfg.spls.enabled:
            from repro.core.planner import horizon_update_live
            from repro.core.topk import topk_count
            if self.pred_cache is None:
                self.pred_cache = init_pred_cache(self.cfg, self._n_pages,
                                                  self.page_size)
                tel.sparsity.note_pool_bytes(tree_bytes(self.cache),
                                             tree_bytes(self.pred_cache))
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
            logits, self.cache, self.pos_pages = self._chunk(
                self.params, self.cache, self.pos_pages,
                jnp.asarray(self._table_row(st)),
                jnp.asarray(start, jnp.int32), jnp.asarray(chunk)[None, :],
                jnp.asarray(valid, jnp.int32))
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
        from the full-prefill decision): threshold the accumulated head
        votes, compact kept columns -- in original order, the same layout
        ``scatter_prefill`` produces -- into the front of the sequence's
        own pages, and free the tail."""
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
        prefill ``engine/prefill_chunk`` (or ``engine/full_prefill``),
        ``engine/prune_compact`` and ``engine/emit_first``, then
        ``engine/retire``, ``engine/decode_prepare``,
        ``engine/decode_dispatch``, ``engine/decode_readback``,
        ``engine/retire`` and ``engine/pool_observe``.  None of them
        waits for the device."""
        tel = self.telemetry
        with tel.span("engine/tick"):
            with tel.span("engine/admit"):
                self.sched.admit()
                plans = self.sched.plan_prefills()
            for st in plans:
                if self.sched.slots[st.slot] is not st:
                    continue  # preempted by an earlier prefill this tick
                if self.sched.use_chunks(st.prompt_len):
                    self._chunk_prefill(st)
                else:
                    self._full_prefill(st)
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
        pages the decode kernel's grid visits (``pages_grid``: every page
        of every slot)."""
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
            tel.span_begin("decode_tick", args={
                "n_active": len(active),
                "pages_live": sum(self.pool.pages_for(st.kv_len + 1)
                                  for st in active),
                "pages_grid": n_slots * self.pages_per_seq})
            tables = np.full((n_slots, self.pages_per_seq), NULL_PAGE,
                             np.int32)
            kv_len = np.zeros((n_slots,), np.int32)
            cur_pos = np.zeros((n_slots,), np.int32)
            tokens = np.zeros((n_slots, 1), np.int32)
            for st in active:
                tables[st.slot] = self._table_row(st)
                kv_len[st.slot] = st.kv_len
                cur_pos[st.slot] = st.cur_pos
                tokens[st.slot, 0] = st.req.output[-1]
            step_args = (jnp.asarray(tables), jnp.asarray(kv_len),
                         jnp.asarray(cur_pos), jnp.asarray(tokens))
        with tel.span("engine/decode_dispatch"):
            logits, self.cache, self.pos_pages = self._decode(
                self.params, self.cache, self.pos_pages, *step_args)
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
