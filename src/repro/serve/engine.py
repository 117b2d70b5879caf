"""Continuous-batching FP8 serving engine.

The piece the ROADMAP's "heavy traffic" north star needs between the model
and the world: a request queue feeding interleaved prefill/decode over
(a) W8-resident FP8 expert weights (serve/w8.py — quantized ONCE, the
grouped GEMMs consume the paper's blockwise-po2 format directly) and
(b) a paged FP8-e4m3 KV cache with per-row po2 scales (serve/paged_kv.py).

Execution model
---------------
One engine *tick* = one call into a single jitted step function:

    engine_step(..., bucket=<static>) =
        [prefill one admitted request's prompt chunk]   (if bucket)
      + [decode every resident request one token]       (if any resident)
      + [sample (greedy / temperature+top-k)]

All shapes are STATIC per (bucket, any_decode, history): decode always runs
over the full `max_batch` slot array behind an `active` mask, and prompts
are padded to a power-of-two bucket — so XLA compiles O(|buckets|) programs
total and never recompiles as the batch mix changes (requests
arrive/finish/evict).

Chunked prefill (``prefill_chunk``): long prompts are sliced into bounded
token chunks, one chunk per tick, each writing its pages at an absolute
``start`` offset and (for continuations) attending to the already-prefilled
history through the page table.  Residents keep decoding every tick, so
decode latency under mixed load is bounded by ONE chunk's compute instead of
a whole long prompt; the continuation has strict FCFS priority over new
admissions.

Scheduling is FCFS with decode priority and a reserved-token budget
(serve/scheduler.py); KV pages come from a host-side free-list with
youngest-first eviction under pressure (restart semantics).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.recipes import Recipe
from repro.models.lm import (ParallelPlan, paged_decode_step, paged_prefill)
from repro.obs.metrics import po2_buckets
from repro.obs.sink import null_telemetry
from repro.obs.trace import Span, mark
from repro.serve.paged_kv import (PageAllocator, copy_page, init_paged_cache,
                                  pool_nbytes)
from repro.serve.scheduler import Request, RequestState, Scheduler

# latency histogram edges: 2^-4 .. 2^14 ms covers sub-ms decode ticks
# through multi-second saturated TTFTs
_LAT_BUCKETS = po2_buckets(-4, 14)


class TraceResults(dict):
    """run()'s return value: the rid -> per-request result dict it always
    was, plus `.stats` (run-level aggregate counters)."""
    stats: dict

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.stats = {}


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (all static — they shape the compiled programs)."""
    max_batch: int = 8                 # resident-request slots
    page_size: int = 16                # tokens per KV page
    n_pages: int = 256                 # pool pages (page 0 is scratch)
    max_pages_per_req: int = 16        # page-table width
    token_budget: int = 2048           # sum(prompt+max_new) over residents
    prefill_buckets: Sequence[int] = (16, 32, 64, 128)
    prefill_chunk: Optional[int] = None  # chunked prefill: max prompt tokens
                                       # per tick (None = whole prompt in one
                                       # tick).  Bounds how long residents'
                                       # decodes can stall behind a long
                                       # prompt; prompts may then exceed the
                                       # largest bucket (chunks must fit it)
    fp8_kv: bool = True                # e4m3 pages w/ po2 scales, else bf16
    w8_weights: bool = False           # pre-quantize expert weights (fp8_flow)
    prefix_cache: bool = False         # radix prefix cache over the KV pool:
                                       # shared page-aligned prompt prefixes
                                       # are quantized+prefilled once and
                                       # reused (refcounted pages; LRU leaf
                                       # eviction under pool pressure)
    top_k: int = 0                     # 0 -> full-vocab sampling
    eos_id: Optional[int] = None
    seed: int = 0
    role: str = "mixed"                # disaggregation tier:
                                       #   "mixed"   — classic engine (prefill
                                       #               + decode interleaved)
                                       #   "prefill" — admission + chunked
                                       #               prefill only; finished
                                       #               prefills PARK in the
                                       #               handoff queue for KV
                                       #               migration to a decode
                                       #               replica
                                       #   "decode"  — no admission; requests
                                       #               arrive pre-filled via
                                       #               the adopt path and run
                                       #               the masked decode batch

    @property
    def max_len(self) -> int:
        return self.max_pages_per_req * self.page_size


def sample_tokens(logits, key, temps, top_k: int):
    """logits (N, V); temps (N,) — greedy where temp <= 0, else
    temperature + (optional) top-k categorical."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lf = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    if top_k:
        kth = jax.lax.top_k(lf, top_k)[0][:, -1][:, None]
        lf = jnp.where(lf < kth, -1e30, lf)
    sampled = jax.random.categorical(key, lf, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def make_engine_step(cfg: ArchConfig, recipe: Recipe, plan: ParallelPlan,
                     ecfg: ServeConfig):
    """The one jitted step: optional bucketed prefill chunk + masked
    full-batch decode + sampling.  `bucket`/`any_decode`/`history` are
    static; `history` marks a chunked-prefill CONTINUATION (the chunk's
    queries attend to the already-prefilled pages at absolute offset
    `pf_start`)."""

    @partial(jax.jit, static_argnames=("bucket", "any_decode", "history"),
             donate_argnums=(1,))
    def engine_step(params, pools, page_tables, last_tok, pos, active, temps,
                    pf_tokens, pf_len, pf_ptrow, pf_start, pf_temp, key, *,
                    bucket: Optional[int], any_decode: bool,
                    history: bool = False):
        out = {}
        if bucket is not None:
            lg, pools = paged_prefill(cfg, recipe, plan, params, pools,
                                      pf_ptrow, pf_tokens, pf_len,
                                      start=pf_start, history=history)
            out["prefill_tok"] = sample_tokens(
                lg[:, -1, :], jax.random.fold_in(key, 0), pf_temp[None],
                ecfg.top_k)[0]
        if any_decode:
            lg, pools = paged_decode_step(cfg, recipe, plan, params, pools,
                                          page_tables, last_tok[:, None],
                                          pos, active)
            out["decode_toks"] = sample_tokens(
                lg[:, -1, :], jax.random.fold_in(key, 1), temps, ecfg.top_k)
        return pools, out

    return engine_step


class ServeEngine:
    """Continuous-batching serving over paged FP8 KV + W8-resident weights.

    Usage::

        eng = ServeEngine(cfg, recipe, plan, params, ServeConfig(...))
        results = eng.run([Request(prompt=[...], max_new_tokens=8), ...])
    """

    def __init__(self, cfg: ArchConfig, recipe: Recipe, plan: ParallelPlan,
                 params, ecfg: ServeConfig = ServeConfig(), telemetry=None):
        self.cfg, self.recipe, self.plan, self.ecfg = cfg, recipe, plan, ecfg
        self.tel = telemetry if telemetry is not None else null_telemetry()
        if ecfg.role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"unknown role {ecfg.role!r}")
        if ecfg.prefill_chunk is not None and (
                ecfg.prefill_chunk < 1
                or ecfg.prefill_chunk > max(ecfg.prefill_buckets)):
            raise ValueError(
                f"prefill_chunk {ecfg.prefill_chunk} must be in "
                f"[1, {max(ecfg.prefill_buckets)}] (largest bucket)")
        if ecfg.w8_weights and recipe.name == "fp8_flow":
            from repro.serve.w8 import quantize_params_for_serving
            params = quantize_params_for_serving(params)
        self.params = params
        self.pools = init_paged_cache(cfg, ecfg.n_pages, ecfg.page_size,
                                      fp8_kv=ecfg.fp8_kv)
        self.alloc = PageAllocator(ecfg.n_pages, ecfg.page_size)
        self.prefix_cache = None
        release_hook = None
        if ecfg.prefix_cache:
            from repro.serve.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(ecfg.page_size, telemetry=self.tel)
            # the single scheduler release point: decref instead of free, so
            # cache-held pages survive their writer finishing/being evicted
            release_hook = lambda st, pages, alloc: alloc.decref(pages)
        self.sched = Scheduler(ecfg.max_batch, ecfg.token_budget,
                               release_hook=release_hook)
        self._step_fn = make_engine_step(cfg, recipe, plan, ecfg)
        self._key = jax.random.key(ecfg.seed)
        self._tick_count = 0
        self.max_concurrent = 0
        self.total_decoded = 0
        self.n_rejected = 0
        self.n_prefill_chunks = 0
        self.n_migrated_out = 0
        # prefill tier: states whose prefill completed this/earlier ticks,
        # parked (slot/pages/budget held) until the router migrates their KV
        # to a decode replica and the receiver acks
        self.handoff: deque = deque()
        self._codec = None
        self._tick_now = self._tick_t0 = 0.0     # see _clock

    @property
    def codec(self):
        """KV page transfer codec for this engine's pool geometry (lazy —
        only disaggregated fleets pay for tracing it)."""
        if self._codec is None:
            from repro.serve.transfer import KVTransferCodec
            self._codec = KVTransferCodec(self.pools)
        return self._codec

    # -- queue -------------------------------------------------------------
    def _reject(self, req: Request, msg: str):
        """A request that can NEVER be served is dropped (counted) and the
        caller gets the ValueError it always did."""
        self.n_rejected += 1
        self.tel.counter("serve_rejected_total").inc()
        self.tel.record("request_rejected", rid=req.rid, reason=msg)
        raise ValueError(msg)

    def submit(self, req: Request) -> None:
        ecfg = self.ecfg
        if ecfg.role == "decode":
            self._reject(req, "decode-tier replica does not admit requests "
                         "(route to the prefill tier; KV arrives via adopt)")
        P = len(req.prompt)
        if P < 1 or req.max_new_tokens < 1:
            self._reject(req, "empty prompt / zero max_new_tokens")
        if ecfg.prefill_chunk is None and P > max(ecfg.prefill_buckets):
            self._reject(req, f"prompt {P} exceeds the largest prefill "
                         f"bucket {max(ecfg.prefill_buckets)} "
                         f"(set prefill_chunk to slice long prompts)")
        if P + req.max_new_tokens > ecfg.max_len:
            self._reject(req, f"request needs {P + req.max_new_tokens} "
                         f"tokens > max_len {ecfg.max_len}")
        if req.reserved_tokens > ecfg.token_budget:
            self._reject(req, "request alone exceeds the token budget")
        if self.alloc.pages_for(P + req.max_new_tokens) > ecfg.n_pages - 1:
            self._reject(req, "request alone exceeds the KV pool")
        self.sched.submit(req)
        mark("serve/submit", rid=req.rid, arrival=req.arrival_time)

    # -- one tick ----------------------------------------------------------
    def _alloc_pages(self, n: int):
        """Pool allocation with the prefix cache as the first pressure
        valve: LRU unreferenced radix leaves are dropped before any
        resident request is considered for eviction."""
        if self.prefix_cache is not None:
            return self.prefix_cache.alloc_pages(self.alloc, n)
        return self.alloc.alloc(n)

    def _grow_pages(self, st: RequestState) -> bool:
        """Ensure st's page table covers its next write; evicts YOUNGER
        residents under pressure (st self-evicts when it is the youngest —
        the oldest resident always progresses).  False if st got unseated."""
        need = st.next_pos // self.ecfg.page_size + 1
        while len(st.pages) < need:
            got = self._alloc_pages(1)
            if got is not None:
                st.pages.extend(got)
                continue
            # evict_youngest(requester=st) always has a victim (st itself at
            # worst); the too-small-pool case is rejected in submit()
            ev = self.sched.evict_youngest(self.alloc, requester=st)
            assert ev is not None
            self.tel.counter("serve_evicted_total").inc()
            self.tel.record("request_evicted", rid=ev.req.rid,
                            by=st.req.rid, n_evictions=ev.n_evictions)
            if ev is st:
                return False
        return st.slot in self.sched.active

    def tick(self, now: float, results: Dict[int, dict]) -> bool:
        """One engine tick; returns True if any work ran.

        `now` is the caller's request clock (the one `arrival_time` is on).
        Each call closes one `serve/tick` span whose children are the
        tick's phases; tokens are stamped at delivery, `now` plus the host
        time spent in the tick so far (`_clock`)."""
        self._tick_now, self._tick_t0 = now, time.perf_counter()
        with Span("serve/tick", ran=False, n_decode=0, bucket=None,
                  history=False, chunk=0, rid=None, n_waiting=0,
                  compiled=False) as sp:
            sp.attrs["ran"] = ran = self._tick(results, sp.attrs)
        return ran

    def _clock(self) -> float:
        """The request clock now, inside a tick."""
        return self._tick_now + (time.perf_counter() - self._tick_t0)

    def _schedule(self):
        """Grow the residents' pages (may evict), pick the decode set and
        at most one prefill chunk (admitting if none is mid-prefill), and
        copy a copy-on-write page.  Returns (pf, decode_slots)."""
        sched = self.sched
        # decode set: resident + prefilled, with page headroom (may evict).
        # Parked states (prefill tier, awaiting migration) are excluded:
        # their KV is frozen until the receiver copies it.
        for slot in sorted(sched.active):
            st = sched.active.get(slot)
            if st is not None and st.prefilled and not st.parked:
                self._grow_pages(st)
        decode_slots = [s for s in sorted(sched.active)
                        if sched.active[s].prefilled
                        and not sched.active[s].parked]

        # decode-priority prefill work: at most one prefill CHUNK rides this
        # tick.  An in-flight chunked prefill continues before anything new
        # is admitted (it was admitted first — FCFS), so decode is never
        # starved by more than one bounded chunk per tick.  A decode-tier
        # replica never prefills: its requests arrive pre-filled via adopt.
        pf = sched.mid_prefill() if self.ecfg.role != "decode" else None
        if pf is None and self.ecfg.role != "decode":
            at = self._clock()
            pf = sched.try_admit(self.alloc, at,
                                 prefix_cache=self.prefix_cache)
            if pf is not None:
                mark("serve/admit", rid=pf.req.rid, at=at)
                if pf.cached_tokens:
                    self.tel.record("prefix_hit", rid=pf.req.rid,
                                    cached_tokens=pf.cached_tokens,
                                    shared_pages=pf.n_shared_pages,
                                    cow=pf.cow_page is not None)
        if pf is not None and pf.cow_page is not None:
            # whole-prompt hit: duplicate the boundary page so the
            # recomputed final-token row writes a PRIVATE copy and the
            # shared original stays immutable
            src, dst = pf.cow_page
            pf.cow_page = None
            with self._mesh():
                self.pools = copy_page(self.pools, jnp.int32(src),
                                       jnp.int32(dst))
        return pf, decode_slots

    def _mesh(self):
        return self.plan.mesh if self.plan.mesh is not None \
            else contextlib.nullcontext()

    def _inputs(self, pf: Optional[RequestState], decode_slots):
        """The step's device arguments and static shape for this tick's
        decode set and prefill chunk.  Returns (args, static, chunk)."""
        ecfg, sched = self.ecfg, self.sched
        B, mp = ecfg.max_batch, ecfg.max_pages_per_req
        pt = np.zeros((B, mp), np.int32)
        pos = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        last = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        for s in decode_slots:
            st = sched.active[s]
            pt[s, :len(st.pages)] = st.pages
            pos[s] = st.next_pos
            active[s] = True
            last[s] = st.generated[-1]
            temps[s] = st.req.temperature

        bucket = None
        history = False
        chunk = 0
        pf_tokens = np.zeros((1, 1), np.int32)
        pf_len = np.int32(0)
        pf_ptrow = np.zeros((mp,), np.int32)
        pf_start = np.int32(0)
        pf_temp = np.float32(0.0)
        if pf is not None:
            P = len(pf.req.prompt)
            chunk = P - pf.prefill_pos
            if ecfg.prefill_chunk:
                chunk = min(chunk, ecfg.prefill_chunk)
            bucket = min(b for b in ecfg.prefill_buckets if b >= chunk)
            pf_tokens = np.zeros((1, bucket), np.int32)
            pf_tokens[0, :chunk] = pf.req.prompt[
                pf.prefill_pos:pf.prefill_pos + chunk]
            pf_len = np.int32(chunk)
            pf_start = np.int32(pf.prefill_pos)
            history = pf.prefill_pos > 0
            pf_ptrow[:len(pf.pages)] = pf.pages
            pf_temp = np.float32(pf.req.temperature)

        key = jax.random.fold_in(self._key, self._tick_count)
        args = (self.params, self.pools, jnp.asarray(pt), jnp.asarray(last),
                jnp.asarray(pos), jnp.asarray(active), jnp.asarray(temps),
                jnp.asarray(pf_tokens), pf_len, jnp.asarray(pf_ptrow),
                pf_start, pf_temp, key)
        static = dict(bucket=bucket, any_decode=bool(decode_slots),
                      history=history)
        return args, static, chunk

    def _tick(self, results: Dict[int, dict], attrs: dict) -> bool:
        """tick's body, one child span per phase; fills the tick's attrs."""
        with Span("serve/schedule"):
            pf, decode_slots = self._schedule()
        attrs["n_waiting"] = len(self.sched.waiting)
        if pf is None and not decode_slots:
            return False

        with self._mesh():
            with Span("serve/inputs"):
                args, static, chunk = self._inputs(pf, decode_slots)
            attrs.update(n_decode=len(decode_slots), chunk=int(chunk),
                         bucket=static["bucket"], history=static["history"],
                         rid=pf.req.rid if pf is not None else None)
            # the warm-up's recording wrapper has no jit cache to read
            cache_size = getattr(self._step_fn, "_cache_size", None)
            n_compiled = cache_size() if cache_size else 0
            with Span("serve/dispatch"):
                self.pools, out = self._step_fn(*args, **static)
        if cache_size and cache_size() > n_compiled:
            attrs["compiled"] = True
            self.tel.counter("serve_step_compiles_total").inc()
        with Span("serve/wait"):
            out = jax.device_get(out)

        with Span("serve/emit"):
            self._emit_tick(pf, decode_slots, static["bucket"], chunk, out,
                            results)
        return True

    def _emit_tick(self, pf, decode_slots, bucket, chunk, out, results
                   ) -> None:
        """Token bookkeeping after the step: counters, the prefilled
        request's progress (its first token on the final chunk, then the
        prefix insert / handoff), every decoded token."""
        ecfg, sched, tel = self.ecfg, self.sched, self.tel
        delivered = self._clock()
        self._tick_count += 1
        self.max_concurrent = max(self.max_concurrent,
                                  len(decode_slots) + (pf is not None))
        tel.counter("serve_ticks_total").inc()
        tel.counter("serve_decode_tokens_total").inc(len(decode_slots))
        if pf is not None:
            self.n_prefill_chunks += 1
            tel.counter("serve_prefill_chunks_total").inc()
        used = (ecfg.n_pages - 1) - self.alloc.free_pages
        tel.gauge("kv_used_pages").set(used)
        tel.histogram("kv_used_pages_hist",
                      edges=po2_buckets(0, 20)).observe(used)
        if tel.enabled:
            tel.record("serve_tick", tick=self._tick_count - 1,
                       n_decode=len(decode_slots), bucket=bucket,
                       chunk=int(chunk), kv_used_pages=used,
                       n_waiting=len(sched.waiting),
                       reserved_tokens=sched.reserved_tokens)

        if pf is not None:
            pf.prefill_pos += chunk
            if pf.prefill_pos >= len(pf.req.prompt):
                if self.prefix_cache is not None:
                    # every full prompt page is now written and stable
                    # (decode rows land beyond them) -> publish the prefix;
                    # blocks already cached keep their canonical pages
                    self.prefix_cache.insert(pf.req.prompt, pf.pages,
                                             self.alloc)
                # only the last chunk's logits are meaningful (the prompt's
                # final position) — intermediate chunks just fill pages
                self._emit(pf, int(out["prefill_tok"]), delivered, results)
                if self.ecfg.role == "prefill" \
                        and self.sched.active.get(pf.slot) is pf:
                    # prefill tier: done here — park (slot/pages/budget stay
                    # held so the KV survives) and queue for migration; the
                    # router ships the pages to a decode replica and acks
                    pf.parked = True
                    self.handoff.append(pf)
                    self.tel.gauge("handoff_queue_depth").set(
                        len(self.handoff))
        if decode_slots:
            toks = out["decode_toks"]
            for s in decode_slots:
                st = sched.active.get(s)
                if st is None:
                    continue
                self._emit(st, int(toks[s]), delivered, results)

    def _emit(self, st: RequestState, tok: int, now: float,
              results: Dict[int, dict]) -> None:
        """Deliver one token; `now` is its delivery time on the request
        clock."""
        st.generated.append(tok)
        st.prefilled = True
        self.total_decoded += 1
        if st.first_token_time is None:
            st.first_token_time = now
            mark("serve/first_token", rid=st.req.rid, at=now)
            self.tel.histogram("serve_ttft_ms", edges=_LAT_BUCKETS).observe(
                (now - st.req.arrival_time) * 1e3)
        elif st.last_token_time is not None:
            self.tel.histogram("serve_tbt_ms", edges=_LAT_BUCKETS).observe(
                (now - st.last_token_time) * 1e3)
        st.last_token_time = now
        if st.done(self.ecfg.eos_id):
            self.sched.finish(st.slot, self.alloc, now)
            mark("serve/finish", rid=st.req.rid, at=now)
            self.tel.counter("serve_finished_total").inc()
            n_tok = len(st.generated)
            ttft_ms = (st.first_token_time - st.req.arrival_time) * 1e3
            tbt_ms_mean = ((now - st.first_token_time) * 1e3
                           / max(n_tok - 1, 1))
            self.tel.record("request_done", rid=st.req.rid, n_tokens=n_tok,
                            ttft_ms=ttft_ms, tbt_ms_mean=tbt_ms_mean,
                            wait_ms=(st.admit_time - st.req.arrival_time)
                            * 1e3, n_evictions=st.n_evictions,
                            cached_tokens=st.cached_tokens)
            results[st.req.rid] = {
                "tokens": list(st.generated),
                "arrival": st.req.arrival_time,
                "admit": st.admit_time,
                "first_token": st.first_token_time,
                "finish": now,
                "n_evictions": st.n_evictions,
                "cached_tokens": st.cached_tokens,
            }

    # -- disaggregation: casting-free KV migration -------------------------
    # Two-phase protocol (router-orchestrated):
    #   1. receiver.reserve_for_adopt(meta)  — pin locally-cached prompt
    #      pages (incref) FIRST, then reserve fresh pages; all-or-nothing.
    #   2. donor.pack_handoff(st, skip)      — bitcast-pack only the pages
    #      the receiver lacks; receiver.commit_adopt scatters them in and
    #      installs the RequestState into the decode batch.
    #   3. donor.release_parked(st)          — ONLY after the receiver ack:
    #      pages leave via the release funnel (cache pages stay shareable).
    def pack_handoff(self, st: RequestState, skip_pages: int = 0):
        """Donor: one uint8 wire message carrying ``st.pages[skip_pages:]``
        (the receiver already holds bit-identical copies of the first
        `skip_pages` — content-addressable po2 pages make that dedupe
        sound) plus the request's resume metadata."""
        from repro.serve.transfer import TransferMeta
        ship = st.pages[skip_pages:]
        meta = TransferMeta(rid=st.req.rid, n_pages=len(ship),
                            page_size=self.ecfg.page_size,
                            bytes_per_page=self.codec.bytes_per_page,
                            pos=st.prefill_pos,
                            max_new_tokens=st.req.max_new_tokens,
                            temperature=st.req.temperature,
                            prompt=tuple(st.req.prompt),
                            generated=tuple(st.generated))
        with self._mesh():
            return self.codec.pack(self.pools, ship, meta)

    def reserve_for_adopt(self, req):
        """Receiver phase 1: returns (shared, fresh) page lists covering the
        migrating prompt, or None if a slot / the token budget / the pool
        cannot take it right now (the donor keeps the request parked and the
        router retries).  `req` is anything with .prompt/.max_new_tokens (a
        Request or a TransferMeta).  Locally-cached blocks are pinned by
        incref BEFORE the fresh tail allocates — the tail alloc may evict
        cache leaves, and a bare cache ref would make the match itself a
        victim (same ordering as try_admit)."""
        sched, ecfg = self.sched, self.ecfg
        if not sched._free_slots:
            return None
        P = len(req.prompt)
        n_total = self.alloc.pages_for(P)
        shared = (self.prefix_cache.match_pages(req.prompt)
                  if self.prefix_cache is not None else [])[:n_total]
        cached = len(shared) * ecfg.page_size
        if sched.reserved_tokens + P + req.max_new_tokens - cached \
                > sched.token_budget:
            return None
        self.alloc.incref(shared)
        n_fresh = n_total - len(shared)
        fresh = (self._alloc_pages(n_fresh) or None) if n_fresh else []
        if fresh is None:
            self.alloc.decref(shared)
            return None
        return shared, fresh

    def abort_adopt(self, shared, fresh) -> None:
        """Receiver: roll phase 1 back (decref pins, free fresh pages)."""
        self.alloc.decref(list(shared) + list(fresh))

    def commit_adopt(self, meta, payload, shared, fresh, now: float,
                     timing: Optional[dict] = None) -> RequestState:
        """Receiver phase 2: scatter the shipped page bytes into the fresh
        pages (pure bitcast — the pages land bit-identical to the donor's),
        rebuild the RequestState at the request's `pos`, install it in the
        decode batch, and publish the prompt prefix into the local radix
        tree so later migrations/admissions of the same tenant re-share
        these pages."""
        if fresh:
            with self._mesh():
                self.pools = self.codec.scatter(self.pools, payload, fresh)
        timing = timing or {}
        req = Request(prompt=list(meta.prompt),
                      max_new_tokens=meta.max_new_tokens,
                      arrival_time=timing.get("arrival", now),
                      temperature=meta.temperature, rid=meta.rid)
        st = RequestState(req=req, slot=-1, pages=list(shared) + list(fresh),
                          admit_seq=-1, admit_time=timing.get("admit", now),
                          generated=list(meta.generated),
                          first_token_time=timing.get("first"),
                          last_token_time=timing.get("last"),
                          prefilled=True, prefill_pos=meta.pos,
                          cached_tokens=len(shared) * self.ecfg.page_size,
                          n_shared_pages=len(shared))
        self.sched.adopt(st)
        if self.prefix_cache is not None:
            self.prefix_cache.insert(meta.prompt, st.pages, self.alloc)
        return st

    def release_parked(self, st: RequestState) -> None:
        """Donor phase 3 (post-ack): the parked request's slot, budget and
        pages are released through the scheduler funnel — with a prefix
        cache the prompt pages stay resident for future local hits, so
        migrating a tenant does not evict its prefix from the prefill
        tier."""
        st.parked = False
        self.sched.release(st, self.alloc)
        self.n_migrated_out += 1

    # -- driver ------------------------------------------------------------
    def run(self, requests: Sequence[Request],
            realtime: bool = True) -> Dict[int, dict]:
        """Drive a full trace to completion.  With realtime=True arrivals
        are honored against the wall clock (Poisson traces); otherwise every
        request is enqueued immediately (closed-loop saturation)."""
        pending = deque(sorted(requests, key=lambda r: r.arrival_time))
        results = TraceResults()
        t0 = time.perf_counter()
        idle_spins = 0
        while pending or not self.sched.idle():
            now = time.perf_counter() - t0
            while pending and (not realtime
                               or pending[0].arrival_time <= now):
                self.submit(pending.popleft())
            if self.tick(now, results):
                idle_spins = 0
                continue
            if pending:
                time.sleep(max(0.0, min(0.002,
                                        pending[0].arrival_time - now)))
                continue
            idle_spins += 1
            if idle_spins > 1000:
                raise RuntimeError(
                    "scheduler deadlock: waiting requests can never be "
                    "admitted (check token_budget / n_pages)")
        results.stats = self.stats()
        self.tel.record("serve_summary", **results.stats)
        self.tel.flush()
        return results

    def stats(self) -> Dict[str, int]:
        """Run-level aggregate counters (also on run()'s TraceResults.stats
        and in the obs registry as serve_* counters)."""
        s = self.sched.stats()
        out = {"ticks": self._tick_count, "admitted": s["admitted"],
               "evicted": s["evicted"], "finished": s["finished"],
               "rejected": self.n_rejected,
               "prefill_chunks": self.n_prefill_chunks,
               "decode_tokens": self.total_decoded,
               "max_concurrent": self.max_concurrent,
               "adopted": s["adopted"],
               "migrated_out": self.n_migrated_out,
               "role": self.ecfg.role}
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.stats())
        return out

    # -- reporting ---------------------------------------------------------
    def kv_bytes(self) -> int:
        return pool_nbytes(self.pools)
