"""Slot-level continuous-batching serving engine on the VEXP stack.

The engine replaces the old fixed-shape chunk loop (which left-padded
prompts with token 0, attended the padding during prefill, and passed one
scalar ``cache_len`` to decode — silently corrupting every request shorter
than the longest in its batch). The structural fix is per-slot state:

* a fixed pool of ``max_batch`` decode-state slots per policy group — a
  ``models.decode_state.DecodeState`` (KV cache + positions for the
  transformer families, batched per-layer ``(h, conv)`` snapshots for
  ssm, a mixed per-period state for hybrid), allocated once at
  ``max_seq`` (or the sliding window). The engine is state-kind-agnostic:
  admission, decode, freeing, donation and device-side liveness all go
  through the protocol, and the engine never branches on the model
  family;
* ragged admission — queued requests are right-padded to the state's
  prefill width (a pow2 length bucket, or the fixed window for hybrid),
  prefilled as one batch with per-request ``prompt_len`` (padding masked
  out of attention / dt-masked out of the recurrences), and their real
  rows are written into freed slots — KV rows by cache scatter,
  recurrent states at each row's *last real token*;
* per-slot decode — one fixed-shape ``(max_batch, 1)`` decode program per
  policy group with a per-slot ``(B,)`` position vector, so each slot
  advances at its own length (the kernels mask each row against its own
  ``cache_len``; recurrences carry position in their state);
* continuous batching — a slot is freed the step its request finishes
  (``max_new`` reached or a linear cache exhausted), its state is reset
  through the protocol (stale recurrent ``h``/``conv`` must not bleed
  into the next occupant), and the next queued request is admitted
  mid-decode instead of burning steps on dead slots.

Per-request execution policies: requests carry a ``group`` name and each
group owns one ExecPolicy, one state pool and exactly one decode
executable (PR 1's one-executable-per-policy contract), so eval traffic
can run ``exact`` numerics while bulk traffic runs ``vexp`` without
contaminating each other's batches or caches — including the recurrent
families, whose RG-LRU / SSD gate exponentials follow the same policy.

The decode hot loop is collective- and copy-minimal:

* **SPMD wiring** — when the state pool reports the capability
  (``DecodeState.supports_seq_sharding``; linear KV caches only) and
  ``distributed.sharding.decode_kv_axis`` reports a sequence-sharded
  decode cache on the serving mesh, each pallas-backend group's decode
  step is ONE ``shard_map`` program built at engine startup: per layer,
  the token's K/V land on the owning shard (drop-mode scatter), every
  shard sweeps its slice in partial-statistics mode, and the statistics
  fold through the policy's ``merge_strategy`` — "packed" is a single
  all_gather of the contiguous (acc | m | l) tile, i.e. exactly one
  collective per layer.
* **Donated step** — the state pool and the per-slot position vector are
  donated through the decode program (buffers reused in place: no state
  re-allocation per step), positions advance device-side (`pos + live`),
  and emitted tokens stay device-resident — a steady-state decode step
  performs zero host syncs and zero host->device transfers.

Chunked prefill (``ExecPolicy.prefill_chunk > 0``): instead of one
monolithic admission wave per prefill bucket, the scheduler becomes
two-queue — each engine tick runs one decode step plus AT MOST ONE
bounded prefill chunk. Queued prompts are admitted per-request into
freed slots (``DecodeState.begin_chunk``) and stream into their slot
``chunk_width`` tokens per tick through one fixed-shape resumable
program (``prefill_chunk_into``: rows not prefilling this tick carry
``clens == 0`` and pass through bit-untouched), so a long prompt never
stalls decode for longer than one chunk and TTFT for short requests no
longer queues behind long prompts' prefill. Mid-prefill slots are dead
to decode (``live == 0``; their position is pinned at the prompt length
by ``begin_chunk``), and the completion tick flips them live with no
extra device traffic. The chunk-step path keeps the decode loop's
zero-host-sync discipline: chunks are dispatched async, and TTFT /
per-chunk wall time are sampled only at scheduling events.

Fault tolerance (PR 9): requests carry deadlines and cooperative
cancellation (``reap`` drops them at scheduling events and releases
their slot/pages through ``DecodeState.abort_chunk`` / ``reset_slots``);
a seeded ``ft.inject.FaultInjector`` can be threaded through the engine
(``Server(injector=...)``) to force OutOfBlocks, step failures, slot
poisoning, straggler chunks and prefix corruption — off by default and
guarded at scheduling events only, so the hot loop stays sync-free; the
decode programs' finite-logits sentinel (token ``-1``) quarantines
poisoned slots at finish instead of streaming garbage; and a hysteretic
degradation ladder sheds load under sustained pool pressure (L1 halves
the prefill chunk width, L2 drops ``--degrade-groups`` to the policy's
``degrade_exp_backend``), restoring when pressure clears.

Profiler spans (``jax.profiler.TraceAnnotation``, on the profiler's own
clock, so a device idle gap can be put down to what the host was doing):
``serve.admit`` (a monolithic wave, from leaving the queue to the end of
its rows' bookkeeping; ``rows``, ``bucket``, ``pool_rows``,
``prompt_tokens``, ``queue_wait_ms``) holding ``serve.admit.wait`` (the
prefill sync; ``runahead``), ``serve.decode.dispatch`` (``live``), and
``serve.finish`` (``tokens``) holding ``serve.finish.wait`` (the token
gather; ``runahead``). ``_Group.runahead`` counts decode dispatches since
the host last waited on the device; ``Request.t_admit`` stamps when a
request left the queue. With no profile active a span costs about a
microsecond.
"""

from __future__ import annotations

import argparse
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.analysis.registry import hot_path
from repro.configs import get_config
from repro.core.attention import masked_policy
from repro.ft import (FAULT_SEED_ENV, FaultInjector, InjectedFault,
                      default_chaos_rates)
from repro.models import api
from repro.models.block_pool import OutOfBlocks
from repro.models.decode_state import (decode_state_for, _len_bucket,  # noqa: F401  (re-export)
                                       SPEC_PAD)
from repro.runtime import (ExecPolicy, resolve_policy, parse_policy_groups,
                           use_compile_cache)
from .mesh import make_host_mesh

# Bounded admission retry: with work in flight a rejected admission just
# waits for the next tick (pages WILL free); with nothing in flight no
# page can ever free on its own, so the engine retries with exponential
# backoff a bounded number of times — absorbing transient/injected
# rejections — then sheds the head request instead of spinning forever
# (the old behavior) or crashing the loop (the other old behavior).
MAX_ADMIT_RETRIES = 8
ADMIT_BACKOFF_S = 0.002
ADMIT_BACKOFF_CAP_S = 0.05
# A step-fault victim is re-queued and re-served this many times before
# the engine concludes the request itself kills the step and sheds it.
MAX_STEP_RETRIES = 3
# Degradation-ladder hysteresis, in scheduler ticks: escalation needs
# DEGRADE_AFTER consecutive pressured ticks, restoration RESTORE_AFTER
# clear ones — sticky both ways so a boundary workload cannot thrash
# the (cached) program swaps.
PRESSURE_HIGH = 0.85
DEGRADE_AFTER = 3
RESTORE_AFTER = 8


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new: int = 16
    group: str = "default"              # policy group (Server.policy_groups)
    out: list = field(default_factory=list)
    # "max_new" | "length_cap" on success; "cancelled" | "deadline" |
    # "quarantined" | "failed" when the engine stopped the request
    # without materializing tokens
    finish_reason: Optional[str] = None
    # wall-clock latency markers (filled by the engine)
    t_submit: float = 0.0
    t_admit: float = 0.0                # left the queue (0 while queued)
    t_first: float = 0.0
    t_done: float = 0.0
    # ---- lifecycle ----
    deadline_s: Optional[float] = None  # TTL from submit (None = server's)
    cancel_requested: bool = False
    retries: int = 0                    # step-fault re-serves so far

    def cancel(self):
        """Cooperative cancellation: flags the request; the engine honors
        it at the next scheduling event (``reap``), releasing the slot
        and any pages/prefix refs it holds."""
        self.cancel_requested = True


class _Group:
    """One policy group: ExecPolicy + DecodeState slot pool + scheduling.

    Greedy scheduling decisions depend only on token *counts* (max_new,
    cache capacity), never on token values — so emitted tokens stay on
    device as (B, 1) argmax arrays (computed inside the jitted programs)
    and each request's token ids are materialized once, when it finishes.
    The decode loop therefore never blocks on a device->host sync and
    JAX's async dispatch pipelines the steps exactly like the fixed-shape
    driver it replaced. Everything state-kind-specific — pool layout,
    admission scatter, program construction, donation, SPMD placement —
    lives behind ``self.state`` (models.decode_state).
    """

    def __init__(self, cfg, params, policy, max_batch, cache_s, *,
                 mesh=None, kv_axis=None, paged=False, block_page=None,
                 block_budget=None, prefix_cache=True):
        self.cfg, self.params, self.policy = cfg, params, policy
        self.max_batch, self.cache_s = max_batch, cache_s
        self.mesh, self.kv_axis = mesh, kv_axis
        # Whether the state actually pages is a protocol capability:
        # decode_state_for may resolve ``paged=True`` to a contiguous
        # state (O(1) recurrent state has nothing to page).
        state_cls = decode_state_for(cfg, paged=paged)
        self.paged = state_cls.is_paged
        if self.paged:
            self.state = state_cls(
                cfg, params, policy, max_batch, cache_s, mesh=mesh,
                kv_axis=kv_axis, page=block_page, n_pages=block_budget,
                prefix_cache=prefix_cache)
        else:
            self.state = state_cls(
                cfg, params, policy, max_batch, cache_s, mesh=mesh,
                kv_axis=kv_axis)
        self.queue: deque = deque()
        self.reqs: list = [None] * max_batch
        self.lens = np.zeros(max_batch, np.int64)   # tokens held per slot
        self.ntok = np.zeros(max_batch, np.int64)   # tokens emitted per slot
        # Device-side slot state: last tokens and a 0/1 liveness vector
        # (per-slot decode positions live inside the DecodeState and are
        # donated through its step). lens/ntok above are host *mirrors*
        # maintained from scheduling events alone (never read back).
        self.last = self.state.place_tokens(
            jnp.zeros((max_batch, 1), jnp.int32))
        self.live_dev = self.state.place_tokens(
            jnp.zeros((max_batch,), jnp.int32))
        self.decode_steps = 0
        self.runahead = 0           # decode dispatches (steps or bursts)
                                    # since the host last waited on the
                                    # device
        self.admit_s: list = []     # per-wave admission (prefill) wall time
        self.req_lat: list = []     # per-request submit->done wall latency
        # ---- chunked prefill (policy.prefill_chunk > 0) ----
        # resolved chunk width: 0 keeps the monolithic wave path, either
        # because the policy asked for it or because this pool cannot
        # chunk (a protocol capability: sharded/windowed paged pools
        # admit monolithically). Families round the requested budget up
        # to their invariant unit (ssm: cfg.ssm_chunk) so chunk
        # boundaries keep the fp summation order admission-invariant.
        self.chunk_c = (self.state.chunk_width(policy.prefill_chunk)
                        if policy.prefill_chunk
                        and self.state.supports_chunked() else 0)
        self.prefilling: dict = {}  # slot -> (Request, cursor tokens cached)
        self.chunk_s: list = []     # per-chunk *dispatch* wall time (async:
                                    # real first-token latency is ttft
                                    # below)
        self.ttft: list = []        # submit -> first-token-dispatch wall
                                    # time, sampled at scheduling events only
        self.peak_logical = 0       # max summed live tokens (paged bench)
        self.peak_pages = 0         # max physical pages in use
        self._toks: dict = {}       # slot -> [(B,1) / (B,W) token arrays]
        # ---- speculative decoding (policy.spec_k >= 2; Server wires it
        # per group through enable_spec) ----
        self.spec_k = 0             # 0 = plain one-token decode
        self.rem_dev = None         # (B,) int32 device emission budgets
        self._bursts = np.zeros(max_batch, np.int64)  # bursts per occupant
        self.spec_bursts = 0        # finished-request burst total
        self.spec_drafted = 0       # draft tokens proposed
        self.spec_accepted = 0      # draft tokens accepted by verify
        self.spec_rolled_back = 0   # draft tokens rolled back
        # ---- fault tolerance / lifecycle ----
        self.injector = None         # FaultInjector (Server threads it)
        self.base_policy = policy    # restore target for the ladder
        self.base_chunk = self.chunk_c
        self.degradable = False      # named in Server's --degrade-groups
        self.degraded = 0            # ladder rung applied to this group
        self.cancelled = 0
        self.deadline_missed = 0
        self.quarantined = 0
        self.step_faults = 0
        self.requeued = 0            # step-fault victims re-queued
        self.shed = 0                # requests dropped as unservable
        self.admit_retries = 0
        self._admit_fail = 0         # consecutive nothing-in-flight fails
        self._admit_pressure = False  # admission rejected this tick

    # --------------------------------------------- lifecycle / fault paths

    @hot_path
    def reap(self, now=None):
        """Request-lifecycle sweep, once per scheduler tick: drop
        cancelled and deadline-expired requests. Queued requests hold no
        pool state, so dropping them is free; a mid-prefill slot releases
        its reservation (pages, prefix refs, table row) through
        ``abort_chunk``; a decoding slot releases through the same abort
        path a quarantine uses. All host bookkeeping plus async device
        parking — the sweep that DOES sync runs only on the abort
        events themselves, never on the fault-free tick."""
        now = time.perf_counter() if now is None else now

        def expired(r):
            if r.cancel_requested:
                return "cancelled"
            if r.deadline_s is not None and \
                    now - r.t_submit > r.deadline_s:
                return "deadline"
            return None

        if self.queue and any(expired(r) for r in self.queue):
            kept: deque = deque()
            for r in self.queue:
                why = expired(r)
                if why is None:
                    kept.append(r)
                else:
                    self._finish_host(r, why)
            self.queue = kept
        for j in list(self.prefilling):
            why = expired(self.prefilling[j][0])
            if why is not None:
                r, _ = self.prefilling.pop(j)
                self.state.abort_chunk(j)
                self._finish_host(r, why)
                self.sweep()
        for j in range(self.max_batch):
            if self.reqs[j] is not None:
                why = expired(self.reqs[j])
                if why is not None:
                    self._abort_slot(j, why)

    def _finish_host(self, r, reason):
        """Terminal bookkeeping for a request stopped WITHOUT its tokens
        materializing (cancel/deadline/quarantine/shed): no req_lat
        sample — latency percentiles describe served traffic only."""
        r.finish_reason = reason
        r.t_done = time.perf_counter()
        if reason == "cancelled":
            self.cancelled += 1
        elif reason == "deadline":
            self.deadline_missed += 1
        elif reason == "quarantined":
            self.quarantined += 1

    def _abort_slot(self, j, reason):
        """Release a decoding slot without materializing its tokens:
        free + park the slot, reset its state (paged pools decref its
        pages), then run the invariant sweep."""
        self._bump_peaks()
        r = self.reqs[j]
        self._toks.pop(j, None)
        self.reqs[j] = None
        self.live_dev = self.live_dev.at[j].set(0)
        if self.rem_dev is not None:
            self.rem_dev = self.rem_dev.at[j].set(0)
        self._bursts[j] = 0
        self.state.reset_slots([j])
        self._finish_host(r, reason)
        self.sweep()

    def sweep(self):
        """Post-fault invariant sweep: refcount conservation, no orphaned
        block-table entries, freed slots parked at position zero —
        everything the pool holds is accounted to a live request or a
        cache entry. Runs after every quarantine/abort/recovery (and in
        tests after every chaos storm); deliberately NOT on the
        fault-free hot path, because it syncs on positions/tables."""
        occupied = {j for j in range(self.max_batch)
                    if self.reqs[j] is not None} | set(self.prefilling)
        self.state.check_integrity(occupied)

    def _admit_backoff(self) -> bool:
        """The one bounded-retry policy for a rejected admission (both
        admission modes' OutOfBlocks paths land here). In-flight work
        means pages WILL free: retry next tick, no sleep, reset the
        failure budget. Nothing in flight means no page can ever free on
        its own: retry MAX_ADMIT_RETRIES times with exponential backoff
        (transient/injected rejections clear), then shed the head
        request — it can never be admitted — instead of spinning forever
        or crashing the serve loop. Returns True if admission should be
        retried."""
        self.admit_retries += 1
        self._admit_pressure = True
        if any(q is not None for q in self.reqs) or self.prefilling:
            self._admit_fail = 0
            return True
        self._admit_fail += 1
        if self._admit_fail <= MAX_ADMIT_RETRIES:
            time.sleep(min(ADMIT_BACKOFF_S * 2 ** (self._admit_fail - 1),
                           ADMIT_BACKOFF_CAP_S))
            return True
        self._admit_fail = 0
        if self.queue:
            r = self.queue.popleft()
            self._finish_host(r, "failed")
            self.shed += 1
        return False

    def _recover_step_fault(self):
        """Self-heal after a failed decode dispatch. The donated carry
        must be presumed consumed, so ``DecodeState.recover`` drops the
        pool (paged pools also release every held page and the prefix
        cache, whose entries point into the dropped buffers). Every
        in-flight request — decoding AND mid-prefill — is a victim:
        re-queued at the head in submit order for a fresh admission, up
        to MAX_STEP_RETRIES re-serves each (a request that keeps killing
        the step is shed, not retried forever). Tokens emitted so far are
        dropped with the pool; re-admission replays the prompt, so a
        re-served request is token-identical to an undisturbed run."""
        victims = []
        for j in range(self.max_batch):
            if self.reqs[j] is not None:
                victims.append(self.reqs[j])
                self.reqs[j] = None
            self._toks.pop(j, None)
        for j in sorted(self.prefilling):
            victims.append(self.prefilling[j][0])
        self.prefilling.clear()
        self.state.recover()
        self.last = self.state.place_tokens(
            jnp.zeros((self.max_batch, 1), jnp.int32))
        self.live_dev = self.state.place_tokens(
            jnp.zeros((self.max_batch,), jnp.int32))
        if self.rem_dev is not None:
            self.rem_dev = self.state.place_tokens(
                jnp.zeros((self.max_batch,), jnp.int32))
        self.lens[:] = 0
        self.ntok[:] = 0
        self._bursts[:] = 0
        for r in sorted(victims, key=lambda v: v.t_submit, reverse=True):
            r.retries += 1
            if r.retries > MAX_STEP_RETRIES:
                self._finish_host(r, "failed")
                self.shed += 1
            else:
                r.out.clear()
                r.t_admit = r.t_first = 0.0
                self.requeued += 1
                self.queue.appendleft(r)
        self.sweep()

    def under_pressure(self) -> bool:
        """Pool-pressure signal, sampled at scheduling events only:
        admission was rejected this tick, or a paged pool's utilization
        (allocator counters — no device reads) crossed PRESSURE_HIGH."""
        if self._admit_pressure:
            return True
        if self.paged:
            return self.state.pool_stats()["utilization"] >= PRESSURE_HIGH
        return False

    def set_degraded(self, level: int):
        """Apply one rung of the degradation ladder. L1 halves the
        prefill chunk width — smaller prefill bites per tick, so decode
        drains page-holding slots sooner; L2 additionally drops a
        *degradable* group to the policy's ``degrade_exp_backend`` (the
        paper's ~0.78%-error envelope is the license). Both directions go
        through the module-level program caches, so after the first
        application stepping up or down never recompiles."""
        level = max(0, min(2, int(level)))
        if level == self.degraded:
            return
        self.degraded = level
        if self.base_chunk:
            self.chunk_c = (self.base_chunk if level == 0 else
                            self.state.chunk_width(
                                max(1, self.base_chunk // 2)))
        pol = self.base_policy
        if level >= 2 and self.degradable and \
                pol.exp_backend != pol.degrade_exp_backend:
            pol = pol.replace(exp_backend=pol.degrade_exp_backend)
        if pol != self.policy:
            self.policy = pol
            self.state.set_policy(pol)

    def enable_spec(self, spec_k: int):
        """Opt this group into self-speculative decode: each tick runs
        ``spec_k`` draft steps under the policy's ``draft_exp_backend``
        and ONE batched exact-policy verify. Raises if the state pool
        cannot roll back a rejected burst (``supports_speculative``).
        Emission budgets move on device (``rem_dev``): the host mirrors
        advance as upper bounds and are corrected at ``_settle_slot``
        syncs, which fire only when a budget *may* have crossed — the
        zero-host-sync-per-tick discipline of the plain loop holds."""
        self.state.enable_speculative(spec_k)
        self.spec_k = int(spec_k)
        self.rem_dev = self.state.place_tokens(
            jnp.zeros((self.max_batch,), jnp.int32))

    # ------------------------------------------------------------ admission

    def _take_wave(self, free):
        """Pop an admission wave off the queue: the maximal FIFO prefix
        that shares the HEAD request's prefill bucket. A long queued
        prompt cannot inflate the whole wave's prefill shape — the wave
        closes at it and it heads the NEXT wave at its own bucket, so
        shorter requests admitted alongside it never pay its width.
        Admission order stays strictly FIFO (no overtaking: request
        identity, not arrival luck, decides service order — and solo/
        batched token identity tests pin this). Paged groups additionally
        close the wave at (a) a request whose fresh-page need PLUS the
        evictable hit pages its admission pins does not fit the pool's
        free+evictable budget (a hit on a cache-only refcount-1 page
        consumes supply too: attach pins the page, so it must not be
        counted both as "no fresh page needed" and as "reclaimable";
        admission blocks on free pages — the decode loop never does),
        and (b) a request colder than the wave's prefix-hit depth — one
        shared history shape per prefill program, and a colder row would
        drag the wave's depth down, discarding the hotter rows' cache
        hits."""
        take = []
        bucket = head_h = avail = None
        pinned = set()     # evictable hit pages already debited this wave
        while free and self.queue:
            r = self.queue[0]
            b = self.state.prefill_width(len(r.prompt))
            if bucket is not None and b > bucket:
                break
            if self.paged:
                if avail is None:
                    avail = self.state.free_with_evictable()
                need, h = self.state.admission_need(
                    r.prompt, cap_h=head_h)
                if head_h is not None and h < head_h:
                    break
                pin, pin_gids = self.state.admission_pin(r.prompt, h,
                                                         pinned)
                if not ((need + pin) <= avail).all():
                    break
                avail = avail - need - pin
                pinned.update(pin_gids)
                if head_h is None:
                    head_h = h
            if bucket is None:
                bucket = b
            r.t_admit = time.perf_counter()
            take.append((free.pop(0), self.queue.popleft()))
        return take, bucket

    @hot_path
    def admit(self, admit_log=None):
        """Fill freed slots from the queue: one ragged batched prefill
        (monolithic), or per-request chunk admission when the group runs
        chunked prefill."""
        self._admit_pressure = False     # re-armed by a rejection below
        if self.injector is not None and \
                self.injector.fire("prefix.corrupt"):
            # detected prefix corruption is handled by invalidating the
            # chains — later admissions re-prefill instead of serving a
            # corrupt history (host-side cache surgery, no device sync)
            self.state.corrupt_prefix(self.injector)
        if self.chunk_c:
            return self.admit_chunked(admit_log)
        free = [j for j in range(self.max_batch) if self.reqs[j] is None]
        take, sp = self._take_wave(free)
        if not take:
            if free and self.queue and not self.prefilling and \
                    all(q is None for q in self.reqs):
                # free slots, a queued request, and NOTHING in flight —
                # yet the wave gate still couldn't take the head: its
                # page need exceeds anything the pool can ever supply.
                # Route through the bounded-retry policy (retry clears
                # transient/injected shortfalls, then shed) instead of
                # spinning the drain loop forever on an unservable head.
                self._admit_backoff()
            return
        with TraceAnnotation(
                "serve.admit", rows=len(take), bucket=sp,
                pool_rows=self.max_batch,
                prompt_tokens=sum(len(r.prompt) for _, r in take),
                queue_wait_ms=1e3 * sum(r.t_admit - r.t_submit
                                        for _, r in take)):
            slots = np.array([j for j, _ in take])
            # prefill always runs at the full pool width so admitting 1 or
            # max_batch requests hits the same executable per length bucket;
            # rows without an admitted request are dummies (length-1, ignored).
            toks = np.zeros((self.max_batch, sp), np.int32)
            plens = np.ones(self.max_batch, np.int32)
            for j, r in take:
                toks[j, :len(r.prompt)] = r.prompt
                plens[j] = len(r.prompt)
            full = len(take) == self.max_batch
            uniform = (full and all(len(r.prompt) == sp for _, r in take)
                       and self.policy.kernel_backend != "pallas")
            # uniform exact-bucket wave: no padding exists, skip the mask.
            # (Not under a pallas policy: the ragged path runs under
            # masked_policy, on the reference scan, so the fast path would
            # prefill through a different implementation than solo serving
            # and could flip a near-tie greedy argmax.)
            t0 = time.perf_counter()
            try:
                first = self.state.prefill_into(slots, toks, plens, full=full,
                                                uniform=uniform)
            except OutOfBlocks:
                # The admission gate debits fresh need AND pinned evictable
                # supply per row, so absent injected faults this is
                # unreachable by construction — but a failed allocation must
                # never crash the server. prefill_into released every page
                # the wave held; re-queue it in FIFO order and let the one
                # bounded-retry policy decide (retry next tick with work in
                # flight; bounded backoff then shed with nothing in flight).
                for _, r in reversed(take):
                    r.t_admit = 0.0
                    self.queue.appendleft(r)
                self._admit_backoff()
                return
            # the prefill sync also waits for every decode step still queued
            with TraceAnnotation("serve.admit.wait", runahead=self.runahead):
                jax.block_until_ready(first)
            self.runahead = 0
            self._admit_fail = 0
            self.admit_s.append(time.perf_counter() - t0)
            if full:
                self.last = first
            else:
                self.last = self.last.at[slots].set(first[slots])
            # one batched device-side liveness update per admission wave
            self.live_dev = self.live_dev.at[jnp.asarray(slots)].set(1)
            if self.spec_k:
                # seed the device emission budget (tokens after the first);
                # verify bursts decrement it by the true acceptance length
                self.rem_dev = self.rem_dev.at[jnp.asarray(slots)].set(
                    jnp.asarray([r.max_new - 1 for _, r in take], jnp.int32))
            now = time.perf_counter()
            for j, r in take:
                self.reqs[j] = r
                self.lens[j] = len(r.prompt)
                self.ntok[j] = 1
                self._toks[j] = [first]
                r.t_first = now
                self.ttft.append(now - r.t_submit)
                if admit_log is not None:
                    admit_log.append(r.rid)
                if self.ntok[j] >= r.max_new:
                    self._finish(j, "max_new")
            self._bump_peaks()

    # --------------------------------------------------- chunked admission

    @hot_path
    def admit_chunked(self, admit_log=None):
        """Begin chunked admission: one queued request per freed slot,
        strictly FIFO. No wave bucketing — admission is per-request, so a
        long prompt at the head claims its own slot and streams across
        ticks while the next tick admits the short request behind it into
        another slot. Paged pools reserve the slot's pages (and attach
        its own prefix-cache hits) in ``begin_chunk``; admission blocks
        on pages — the chunk/decode loop never does."""
        free = [j for j in range(self.max_batch)
                if self.reqs[j] is None and j not in self.prefilling]
        while free and self.queue:
            r = self.queue[0]
            j = free[0]
            try:
                cur = self.state.begin_chunk(j, r.prompt, len(r.prompt))
                try:
                    # the slot now holds its full reservation; it is
                    # released only by _chunk_done -> eventual finish, by
                    # reap/abort_chunk, or — if publishing the slot to
                    # the prefilling map itself fails — right here.
                    r.t_admit = time.perf_counter()
                    self.prefilling[j] = (self.queue.popleft(), cur)
                except BaseException:
                    self.state.abort_chunk(j)
                    raise
            except OutOfBlocks:
                # pool exhausted (or an injected admission fault):
                # begin_chunk released anything it held; the one
                # bounded-retry policy decides — retry next tick with
                # work in flight, bounded backoff then shed the head
                # with nothing in flight (it can never be admitted).
                if self._admit_backoff():
                    break
                continue             # head was shed; try the next request
            free.pop(0)
            self._admit_fail = 0
            if admit_log is not None:
                admit_log.append(r.rid)
        self._bump_peaks()

    @hot_path
    def prefill_chunk_once(self):
        """Advance every mid-prefill slot by ONE bounded chunk — the
        at-most-one-prefill-chunk half of the engine tick (no-op when
        nothing is prefilling). One fixed-shape (pool, chunk_c) program
        call per tick: each prefilling row contributes its next
        ``clens[j] <= chunk_c`` prompt tokens at its cursor; every other
        row rides along inert (``clens == 0``). Fully async — the chunk
        is dispatched, never synced (chunk_s records dispatch wall
        time), so the host runs ahead and XLA pipelines
        chunk and decode steps back to back."""
        if not self.prefilling:
            return
        if self.injector is not None and \
                self.injector.fire("chunk.delay"):
            time.sleep(self.injector.delay_s)   # straggler chunk
        toks = np.zeros((self.max_batch, self.chunk_c), np.int32)
        offs = np.zeros(self.max_batch, np.int32)
        clens = np.zeros(self.max_batch, np.int32)
        done = []
        for j in list(self.prefilling):
            r, cur = self.prefilling[j]
            n = min(self.chunk_c, len(r.prompt) - cur)
            toks[j, :n] = r.prompt[cur:cur + n]
            offs[j] = cur
            clens[j] = n
            if cur + n >= len(r.prompt):
                done.append(j)
            else:
                self.prefilling[j] = (r, cur + n)
        t0 = time.perf_counter()
        first = self.state.prefill_chunk_into(toks, offs, clens)
        self.chunk_s.append(time.perf_counter() - t0)
        if done:
            self._chunk_done(done, first)

    @hot_path
    def _chunk_done(self, done, first):
        """Completion dispatch for slots whose prompt finished this
        chunk: flip them live and seed decode — all device-async (the
        chunk program already pinned positions and wrote the state; the
        only device work here is the batched last-token/liveness update).
        TTFT is sampled here, at the scheduling event, not at a sync —
        the zero-host-sync discipline of the decode loop holds on the
        chunk-step path too."""
        sl = jnp.asarray(done)
        self.last = self.last.at[sl].set(first[sl])
        self.live_dev = self.live_dev.at[sl].set(1)
        if self.spec_k:
            self.rem_dev = self.rem_dev.at[sl].set(jnp.asarray(
                [self.prefilling[j][0].max_new - 1 for j in done],
                jnp.int32))
        now = time.perf_counter()
        for j in done:
            r, _ = self.prefilling.pop(j)
            self.reqs[j] = r
            self.lens[j] = len(r.prompt)
            self.ntok[j] = 1
            self._toks[j] = [first]
            r.t_first = now
            self.ttft.append(now - r.t_submit)
            self.state.finish_chunk(j, r.prompt, len(r.prompt))
            if self.ntok[j] >= r.max_new:
                self._finish(j, "max_new")
        self._bump_peaks()

    def _bump_peaks(self):
        """Track oversubscription highs (paged pools only): summed live
        logical tokens vs physical pages actually held."""
        if not self.paged:
            return
        logical = int(sum(self.lens[j] for j in range(self.max_batch)
                          if self.reqs[j] is not None))
        self.peak_logical = max(self.peak_logical, logical)
        self.peak_pages = max(self.peak_pages, self.state.alloc.n_used())

    # --------------------------------------------------------------- decode

    @hot_path
    def decode_once(self):
        """One batched decode step over the live slots (no-op when idle)."""
        cap = self.state.max_len()
        if cap is not None:
            # a linear cache is exhausted when the next write would fall
            # past the last slot — stop the request instead of letting a
            # clamped write silently overwrite the final cache row.
            # (Recurrent and ring-buffer state reports no cap.)
            for j in range(self.max_batch):
                if self.reqs[j] is not None and self.lens[j] >= cap:
                    self._finish(j, "length_cap")
        live = [j for j in range(self.max_batch) if self.reqs[j] is not None]
        if not live:
            return
        if self.injector is not None and \
                self.injector.fire("decode.poison"):
            # NaN one live slot's private state BEFORE the step: the
            # decode program's finite-logits sentinel must absorb it
            self.state.poison_slot(self.injector.choose(live))
        # dead slots decode their stale token over zeroed/parked state:
        # harmless (the slot has no request, and admission overwrites the
        # slot's state before it is read again). Positions live on device
        # (live slots advance by +1 inside the donated program), so the
        # hot loop ships nothing host->device and syncs on nothing.
        try:
            if self.injector is not None and \
                    self.injector.fire("decode.step_error"):
                raise InjectedFault("decode dispatch failed")
            with TraceAnnotation("serve.decode.dispatch", live=len(live)):
                nxt = self.state.step(self.last, self.live_dev)
        except InjectedFault:
            # The chaos harness's failed dispatch: the donated carry must
            # be presumed consumed, so rebuild the pool and re-queue the
            # victims. Any other error is a real fault of the program or
            # the device and propagates to the caller.
            self.step_faults += 1
            self._recover_step_fault()
            return
        self.last = nxt
        self.decode_steps += 1
        self.runahead += 1
        for j in live:
            self.lens[j] += 1
            self.ntok[j] += 1
            self._toks[j].append(nxt)
            if self.ntok[j] >= self.reqs[j].max_new:
                self._finish(j, "max_new")

    @hot_path
    def decode_spec_once(self):
        """One speculative decode burst over the live slots (no-op when
        idle): snapshot, ``spec_k`` draft steps under the draft policy,
        ONE batched exact-policy verify that accepts the longest agreeing
        prefix + 1 bonus token and folds the rollback into the device
        carry. The burst is fully async — acceptance lengths never reach
        the host; the mirrors below advance by the burst width W as
        UPPER bounds, and a mirror crossing its budget routes through
        the one ``_settle_slot`` sync, which either finishes the request
        or restores exact mirrors. Every emitted token is an exact-policy
        argmax, so (scan verify) greedy output is token-identical to the
        plain loop."""
        live = [j for j in range(self.max_batch) if self.reqs[j] is not None]
        if not live:
            return
        if self.injector is not None and \
                self.injector.fire("decode.poison"):
            self.state.poison_slot(self.injector.choose(live))
        try:
            if self.injector is not None and \
                    self.injector.fire("decode.step_error"):
                raise InjectedFault("decode dispatch failed")
            snap = self.state.spec_snapshot()
            cand = [self.last]
            cur = self.last
            for _ in range(self.spec_k):
                cur = self.state.draft_step(cur, self.live_dev)
                cand.append(cur)
            toks = jnp.concatenate(cand, axis=1)        # (B, W)
            block, nlast, self.rem_dev = self.state.verify_step(
                toks, snap, self.rem_dev, self.live_dev)
        except InjectedFault:
            # same recovery contract as the plain step: the donated
            # carry (and the snapshot fed to verify) must be presumed
            # consumed; rebuild the pool and re-queue the victims.
            self.step_faults += 1
            self._recover_step_fault()
            return
        self.last = nlast
        self.decode_steps += 1
        self.runahead += 1
        cap = self.state.max_len()
        w = self.spec_k + 1
        for j in live:
            r = self.reqs[j]
            self._bursts[j] += 1
            self._toks[j].append(block)
            # upper-bound mirror advance: the true per-burst acceptance
            # m <= W lives in the device carry. Mirrors only ever
            # over-estimate, so every budget crossing lands in
            # _settle_slot — which corrects them exactly.
            self.ntok[j] = min(self.ntok[j] + w, r.max_new)
            self.lens[j] = (min(self.lens[j] + w, cap) if cap is not None
                            else self.lens[j] + w)
            if self.ntok[j] >= r.max_new or \
                    (cap is not None and self.lens[j] >= cap):
                self._settle_slot(j)

    def _settle_slot(self, j):
        """A speculative slot whose upper-bound mirrors crossed its
        emission budget (max_new) or the linear cache cap: ONE
        device->host sync materializes the slot's real token column
        (PAD-filtered). If the budget truly is exhausted the request
        finishes through the normal path; otherwise the mirrors are
        corrected to exact values and the slot keeps decoding. Each
        settle-and-continue makes >= 1 token of progress per following
        burst (device clamps guarantee m >= 1 while budget and cap
        room remain), so settling cannot spin."""
        r = self.reqs[j]
        col = np.asarray(jnp.concatenate(self._toks[j], axis=1))[j]
        self.runahead = 0
        col = col[col != SPEC_PAD]
        n = int(col.size)
        pos = len(r.prompt) + n - 1     # cache rows the slot holds
        cap = self.state.max_len()
        if (col < 0).any() or n >= r.max_new:
            self._finish(j, "max_new")  # quarantine is decided inside
        elif cap is not None and pos >= cap:
            self._finish(j, "length_cap")
        else:
            self.ntok[j] = n
            self.lens[j] = pos

    @hot_path
    def _finish(self, j, reason):
        # logical footprint and held pages grow monotonically between
        # scheduling events, so sampling the peak just before a slot
        # releases (plus at admission/stats) is exact — and keeps the
        # decode hot loop free of per-step host accounting.
        cols = self._toks.pop(j)
        with TraceAnnotation("serve.finish",
                             tokens=sum(c.shape[1] for c in cols)):
            self._bump_peaks()
            r = self.reqs[j]
            # one device->host sync per finished request: gather its column
            # from the logged per-step argmax vectors / per-burst accepted
            # blocks (speculative groups; SPEC_PAD marks lanes past each
            # burst's accepted length and is filtered out here).
            with TraceAnnotation("serve.finish.wait", runahead=self.runahead):
                toks = np.asarray(jnp.concatenate(cols, axis=1))[j]
            self.runahead = 0
            toks = toks[toks != SPEC_PAD]
            if self.spec_k:
                b = int(self._bursts[j])
                self._bursts[j] = 0
                self.spec_bursts += b
                self.spec_drafted += b * self.spec_k
                # every burst that emitted anything spent one bonus token;
                # the rest of the column is accepted draft proposals
                acc = min(max(0, len(toks) - 1 - b), b * self.spec_k)
                self.spec_accepted += acc
                self.spec_rolled_back += b * self.spec_k - acc
                self.rem_dev = self.rem_dev.at[j].set(0)
            if (toks < 0).any():
                # the decode programs' sticky finite-logits sentinel: some
                # step saw non-finite logits for this row. Quarantine — never
                # stream the garbage — and scrub the slot (deep zero, not a
                # plain reset: surviving NaN rows would contaminate the next
                # occupant through additively-masked attention). Detection
                # costs nothing extra: the token column was already
                # materialized here.
                self.reqs[j] = None
                self.live_dev = self.live_dev.at[j].set(0)
                self.state.scrub_slot(j)
                self._finish_host(r, "quarantined")
                self.sweep()
                return
            r.out.extend(int(t) for t in toks)
            r.finish_reason = reason
            r.t_done = time.perf_counter()   # after the sync: true completion
            self.req_lat.append(r.t_done - r.t_submit)
            self.reqs[j] = None          # slot freed; next admit() reuses it
            # park the slot device-side: live=0 excludes it from position
            # advance, and the state resets the slot (recurrent h/conv is
            # read unconditionally — a stale occupant must not bleed).
            self.live_dev = self.live_dev.at[j].set(0)
            self.state.reset_slots([j])

    @property
    def busy(self) -> bool:
        return (bool(self.queue) or bool(self.prefilling)
                or any(r is not None for r in self.reqs))


class Server:
    """Slot-level continuous-batching server.

    One ExecPolicy per *group* (default: a single group from the usual
    resolution chain), each with its own ``max_batch``-slot state pool and
    exactly one decode executable. ``run(requests)`` drives admission and
    decode until every request is finished.

    Every decoding family serves through the same engine: the per-slot
    state is a ``models.decode_state.DecodeState`` — a KV cache for the
    transformer families, per-layer recurrent snapshots for ssm, a mixed
    per-period state for hybrid — and the scheduler only ever talks to
    that protocol.
    """

    def __init__(self, cfg, params, *, max_batch=4, max_seq=512, mesh=None,
                 policy: ExecPolicy | None = None,
                 policy_groups: Optional[dict] = None,
                 kv_mode: str = "auto", paged: bool = False,
                 block_page: Optional[int] = None,
                 block_budget: Optional[int] = None,
                 prefix_cache: bool = True,
                 injector: Optional[FaultInjector] = None,
                 deadline_s: Optional[float] = None,
                 degrade_groups=(), spec_groups=None):
        # raises for encoder-only archs; under --paged this resolves the
        # paged state class so the seq-sharding capability probe below
        # reflects what will actually serve
        state_cls = decode_state_for(cfg, paged=paged)
        self.cfg, self.params = cfg, params
        self.max_batch, self.max_seq = max_batch, max_seq
        self.paged = state_cls.is_paged
        self.mesh = mesh or make_host_mesh()
        self.policy = policy if policy is not None else resolve_policy(cfg)
        if self.policy.autotune or (policy_groups and any(
                p.autotune for p in policy_groups.values())):
            # warm the block-size tuner from the on-disk cache: a restart
            # on the same device kind reuses every previously-timed winner
            # instead of re-timing candidates on the first wave.
            from repro.kernels import dispatch as _dispatch
            n = _dispatch.load_autotune_cache()
            if n:
                print(f"[serve] autotune: {n} block-size winners loaded "
                      f"from {_dispatch.autotune_cache_path()}")
        self.cache_s = min(max_seq, cfg.sliding_window or max_seq)
        # Serve-loop SPMD wiring: when the state kind supports it (a
        # capability probed via the DecodeState protocol — linear KV
        # caches only) and the cache placement rules report a
        # sequence-sharded decode cache on this mesh, pallas-backend
        # groups route their decode step through the fused sharded path
        # (one shard_map program per group, built once here at startup)
        # instead of GSPMD-lowering the unsharded program.
        self.kv_axis = None
        if state_cls.supports_seq_sharding(cfg):
            from repro.distributed.sharding import decode_kv_axis
            ax = decode_kv_axis(cfg, self.mesh, max_batch, kv_mode=kv_mode)
            if (ax is not None and self.mesh.shape[ax] > 1
                    and self.cache_s % self.mesh.shape[ax] == 0):
                self.kv_axis = ax
        if self.paged and self.kv_axis is not None:
            # a sharded paged pool needs the page count per slot to split
            # evenly over the shards; pin the page size up front (the
            # autotuner must not pick one that breaks divisibility).
            nsh = self.mesh.shape[self.kv_axis]
            page_hint = int(block_page or self.policy.block_page)
            ns = -(-self.cache_s // page_hint)
            if ns % nsh != 0:
                self.kv_axis = None
            elif block_page is None:
                block_page = page_hint
        groups = dict(policy_groups) if policy_groups else {}
        if "default" not in groups:
            groups["default"] = self.policy
        self.policy_groups = groups
        self._groups = {
            name: _Group(cfg, params, pol, max_batch, self.cache_s,
                         mesh=self.mesh,
                         kv_axis=(self.kv_axis
                                  if pol.kernel_backend == "pallas"
                                  else None),
                         paged=paged, block_page=block_page,
                         block_budget=block_budget,
                         prefix_cache=prefix_cache)
            for name, pol in groups.items()}
        self.admit_log: list = []    # rids in admission order (tests/debug)
        # ---- fault tolerance / lifecycle ----
        self.injector = injector
        self.deadline_s = deadline_s     # default TTL for submitted requests
        degrade = set(degrade_groups or ())
        unknown = degrade - set(self._groups)
        if unknown:
            raise ValueError(f"unknown degrade group(s) {sorted(unknown)}; "
                             f"have {sorted(self._groups)}")
        for name, g in self._groups.items():
            g.degradable = name in degrade
            if injector is not None:
                g.injector = injector
                g.state.set_injector(injector)
        # Speculative decoding is per-group opt-in, twice over: the
        # group's policy must ask for it (spec_k >= 2) AND — when
        # --spec-groups names groups — the group must be named. With
        # spec_groups=None every spec_k group speculates. Enabling
        # raises for pools that cannot roll back a rejected burst
        # (ring-buffer KV, sharded pools, vlm extras).
        spec = None if spec_groups is None else set(spec_groups)
        if spec is not None:
            unknown = spec - set(self._groups)
            if unknown:
                raise ValueError(
                    f"unknown spec group(s) {sorted(unknown)}; "
                    f"have {sorted(self._groups)}")
        for name, g in self._groups.items():
            if spec is not None and name in spec and g.policy.spec_k < 2:
                raise ValueError(
                    f"group {name} named in spec_groups but its policy "
                    f"has spec_k={g.policy.spec_k} (need >= 2)")
            if g.policy.spec_k >= 2 and (spec is None or name in spec):
                g.enable_spec(g.policy.spec_k)
        # The ladder is strictly opt-in: with no --degrade-groups the
        # engine never trades chunk width or numerics for pressure —
        # tight paged pools run at high utilization as a matter of
        # course, and an un-opted operator gets exactly the configured
        # schedule (the chunked-prefill identity tests pin chunk_c).
        self._degrade_enabled = bool(degrade)
        self.degrade_level = 0
        self._pressure_ticks = 0
        self._clear_ticks = 0

    # ------------------------------------------------------------ scheduling

    def submit(self, r: Request) -> None:
        if r.group not in self._groups:
            raise ValueError(f"unknown policy group {r.group!r}; "
                             f"have {sorted(self._groups)}")
        plen = len(r.prompt)
        if plen < 1:
            raise ValueError(f"request {r.rid}: empty prompt")
        if plen > self.cache_s:
            raise ValueError(
                f"request {r.rid}: prompt of {plen} tokens exceeds the "
                f"cache capacity ({self.cache_s})")
        if r.max_new < 1:
            raise ValueError(f"request {r.rid}: max_new must be >= 1")
        if r.deadline_s is None:
            r.deadline_s = self.deadline_s
        r.t_submit = time.perf_counter()
        self._groups[r.group].queue.append(r)

    def cancel(self, rid: int) -> bool:
        """Cooperative cancellation by request id: flag the request
        wherever it lives (queued, mid-prefill or decoding); the next
        tick's reap drops it and releases whatever it holds. Returns
        False for an unknown or already-finished rid."""
        for g in self._groups.values():
            for r in g.queue:
                if r.rid == rid:
                    r.cancel()
                    return True
            for r in g.reqs:
                if r is not None and r.rid == rid:
                    r.cancel()
                    return True
            for r, _ in g.prefilling.values():
                if r.rid == rid:
                    r.cancel()
                    return True
        return False

    @hot_path
    def step(self) -> bool:
        """One scheduler tick: reap cancelled/expired requests, admit
        into freed slots, evaluate the degradation ladder, then (chunked
        groups) at most one bounded prefill chunk and one decode step
        per busy group. Chunk before decode: a prompt completing its last
        chunk goes live the same tick, so its first decode step follows
        immediately. Returns True while any work remains."""
        for g in self._groups.values():
            g.reap()
        for g in self._groups.values():
            g.admit(self.admit_log)
        self._degradation_tick()
        for g in self._groups.values():
            g.prefill_chunk_once()
        for g in self._groups.values():
            if g.spec_k:
                g.decode_spec_once()
            else:
                g.decode_once()
        return any(g.busy for g in self._groups.values())

    def _degradation_tick(self):
        """The ladder's hysteresis, from host-side pressure signals only
        (admission rejections this tick, allocator utilization):
        DEGRADE_AFTER consecutive pressured ticks escalate one rung —
        L1 halves the prefill chunk width, L2 also downgrades the
        --degrade-groups to their policy's ``degrade_exp_backend`` —
        and RESTORE_AFTER clear ticks step back down. The engine heals
        to full fidelity on its own; nothing stays degraded forever.
        Inert unless at least one group opted in via degrade_groups."""
        if not self._degrade_enabled:
            return
        pressured = any(g.under_pressure() for g in self._groups.values())
        if pressured:
            self._pressure_ticks += 1
            self._clear_ticks = 0
        else:
            self._clear_ticks += 1
            self._pressure_ticks = 0
        level = self.degrade_level
        if pressured and self._pressure_ticks >= DEGRADE_AFTER \
                and level < 2:
            level, self._pressure_ticks = level + 1, 0
        elif not pressured and self._clear_ticks >= RESTORE_AFTER \
                and level > 0:
            level, self._clear_ticks = level - 1, 0
        if level != self.degrade_level:
            self.degrade_level = level
            for g in self._groups.values():
                g.set_degraded(level)

    def drain(self) -> None:
        with self.mesh:
            while self.step():
                pass

    def run(self, requests: list[Request]) -> list[Request]:
        """Serve to completion; returns the requests with .out filled."""
        for r in requests:
            self.submit(r)
        self.drain()
        return requests

    # ------------------------------------------------------------ telemetry

    @hot_path
    def stats(self) -> dict:
        """Per-group decode-step count, request-latency tail (submit ->
        tokens materialized; measured at a real device sync, unlike the
        async per-step dispatch times), queue/prefill occupancy and TTFT.
        Everything here is assembled from host mirrors maintained at
        scheduling events — calling stats() mid-serve costs zero device
        syncs (the paged peak sample below reads allocator counters, not
        device state)."""
        out = {}
        for name, g in self._groups.items():
            lat = sorted(g.req_lat)
            ttft = sorted(g.ttft)

            def pct(xs, q):
                return xs[min(len(xs) * q // 100, len(xs) - 1)] \
                    if xs else 0.0

            out[name] = {
                "decode_steps": g.decode_steps,
                "p50_req_s": lat[len(lat) // 2] if lat else 0.0,
                "p95_req_s": pct(lat, 95),
                "admit_waves": len(g.admit_s),
                "admit_s_total": sum(g.admit_s, 0.0),
                # two-queue scheduler occupancy + chunk telemetry (the
                # monolithic path reports 0 chunks and admission-time
                # TTFT through the same keys)
                "queue_depth": len(g.queue),
                "prefilling": len(g.prefilling),
                "prefill_chunk": g.chunk_c,
                "prefill_chunks": len(g.chunk_s),
                "chunk_s_total": sum(g.chunk_s, 0.0),
                "p50_ttft_s": ttft[len(ttft) // 2] if ttft else 0.0,
                "p95_ttft_s": pct(ttft, 95),
                "policy": g.policy.describe(),
                # serving prefill masks every wave (ragged prompts, chunk
                # cursors, prefix history): its attention backend is
                # whatever masked_policy makes of the group's policy
                "prefill_attention": masked_policy(g.policy).kernel_backend,
                "decode_attention": g.policy.kernel_backend,
                "kv_axis": g.kv_axis,
                # ---- lifecycle / fault counters ----
                "cancelled": g.cancelled,
                "deadline_missed": g.deadline_missed,
                "quarantined": g.quarantined,
                "step_faults": g.step_faults,
                "requeued": g.requeued,
                "shed": g.shed,
                "admit_retries": g.admit_retries,
                "degraded": g.degraded,
            }
            if g.spec_k:
                # burst counters maintained at finish-time scheduling
                # events only (the burst itself never syncs acceptance)
                drafted = g.spec_drafted
                out[name].update({
                    "spec_k": g.spec_k,
                    "spec_verify": g.policy.spec_verify,
                    "spec_bursts": g.spec_bursts,
                    "spec_drafted": drafted,
                    "spec_accepted": g.spec_accepted,
                    "spec_rolled_back": g.spec_rolled_back,
                    "spec_acceptance": (g.spec_accepted / drafted
                                        if drafted else 0.0),
                })
            if g.paged:
                g._bump_peaks()          # sample mid-decode footprint
                pool = g.state.pool_stats()
                pool["peak_pages"] = g.peak_pages
                pool["peak_logical_tokens"] = g.peak_logical
                # summed live tokens the physical pool could hold if every
                # page were exclusive — >1.0 oversubscription means prefix
                # sharing is carrying logical state past physical capacity
                cap = pool["pages_allocatable"] * pool["page"]
                pool["peak_oversubscription"] = (g.peak_logical / cap
                                                 if cap else 0.0)
                out[name]["pool"] = pool
        return out

    def fault_stats(self) -> dict:
        """Chaos-harness summary: the engine's degradation level plus the
        injector's per-point seen/fired counters (empty when no injector
        is threaded). Kept out of stats(), whose keys are per-group."""
        out = {"degrade_level": self.degrade_level}
        if self.injector is not None:
            out["injector"] = self.injector.stats()
        return out

    # ----------------------------------------------------------- invariants

    def check_invariants(self):
        """Run every group's post-fault invariant sweep now: refcount
        conservation, no orphaned block-table entries, freed slots
        parked. Raises AssertionError on the first violation."""
        for g in self._groups.values():
            g.sweep()

    def assert_idle_clean(self):
        """Terminal leak check for a drained server: nothing queued or in
        flight anywhere, invariants hold, and — after dropping the prefix
        cache's own references — every paged group's allocator reports
        zero pages in use. Destructive to the prefix cache (this is a
        shutdown check); serving can continue but restarts cold."""
        for name, g in self._groups.items():
            if g.busy:
                raise AssertionError(f"group {name} still busy at "
                                     f"shutdown")
            g.sweep()
            if g.paged:
                if g.state.pcache is not None:
                    g.state.pcache.drop_all()
                used = g.state.alloc.n_used()
                if used:
                    raise AssertionError(
                        f"group {name}: {used} pages leaked")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--mixed-lengths", action="store_true",
                    help="draw prompt lengths in [4, --prompt-len] instead "
                         "of a uniform length (exercises ragged admission)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--exp-backend", default=None,
                    choices=["exact", "vexp", "vexp_hw"],
                    help="exponential backend (default: config/env)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["pallas", "reference", "xla"],
                    help="kernel backend (default: config/env)")
    ap.add_argument("--policy-groups", default=None,
                    help='per-request policy groups, e.g. '
                         '"eval=exact,bulk=vexp" (requests are assigned '
                         'round-robin); omit for a single default group')
    ap.add_argument("--autotune", action="store_true",
                    help="autotune kernel block sizes per shape bucket")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="serving prefill chunk size in tokens (0 = "
                         "monolithic wave prefill; > 0 streams prompts "
                         "into their slots chunk by chunk, one bounded "
                         "chunk per engine tick, overlapped with decode; "
                         "families may round up — ssm to cfg.ssm_chunk)")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV block pool (per-slot "
                         "block tables + refcounted allocator + shared-"
                         "prefix cache) instead of contiguous slot rows")
    ap.add_argument("--block-page", type=int, default=None,
                    help="KV page size in tokens (default: autotuned over "
                         "the decode_attention_paged candidates, or the "
                         "policy's block_page off the pallas backend)")
    ap.add_argument("--block-budget", type=int, default=None,
                    help="physical pages in the pool (default: one full "
                         "reservation per slot + per-shard scratch; set "
                         "lower to exercise prefix-sharing oversubscription)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the shared-prefix block cache (paged only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give all generated requests an identical first N "
                         "tokens (exercises the paged prefix cache)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="default per-request TTL in seconds (from "
                         "submit); expired requests are reaped at the "
                         "next scheduler tick and release their slot "
                         "and pages")
    ap.add_argument("--degrade-groups", default=None,
                    help='comma-separated policy groups the degradation '
                         'ladder may drop to the policy\'s '
                         'degrade_exp_backend under sustained pool '
                         'pressure, e.g. "bulk" (restored when pressure '
                         'clears)')
    ap.add_argument("--chaos", action="store_true",
                    help="thread a seeded FaultInjector through the "
                         "engine at the default chaos rates, assert "
                         "clean shutdown (zero leaked pages/slots) and "
                         "print the fault report")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help=f"chaos seed (default: ${FAULT_SEED_ENV} or 0)")
    ap.add_argument("--cancel-frac", type=float, default=0.0,
                    help="cancel roughly this fraction of the submitted "
                         "requests mid-serve (exercises cooperative "
                         "cancellation)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative decoding: draft tokens per decode "
                         "burst (0 = plain decode; >= 2 enables the "
                         "draft/verify loop — k cheap draft steps under "
                         "--draft-backend, then ONE batched exact-policy "
                         "verify accepting the longest agreeing prefix "
                         "+ 1 bonus token)")
    ap.add_argument("--draft-backend", default=None,
                    choices=["exact", "vexp", "vexp_hw"],
                    help="exp backend the draft steps run under "
                         "(default: vexp_hw, the paper's bit-exact RTL "
                         "model; emitted tokens always come from the "
                         "exact verify pass)")
    ap.add_argument("--spec-verify", default=None,
                    choices=["scan", "chunk"],
                    help='how verify scores the burst: "scan" replays '
                         'the exact decode step per lane (bitwise '
                         'speculative == plain, every family); "chunk" '
                         'scores all lanes in one batched pass (reads '
                         'cache + weights once per burst — the '
                         'throughput mode; KV caches only, may break '
                         'fp near-ties differently than plain decode)')
    ap.add_argument("--spec-groups", default=None,
                    help='comma-separated policy groups that speculate '
                         '(their policies need spec_k >= 2); omit to '
                         'speculate in every group whose policy asks')
    ap.add_argument("--kv-mode", default="auto",
                    choices=["auto", "seq", "batch"],
                    help='decode-cache placement: "seq" shards the KV '
                         'sequence dim over the mesh\'s model axis '
                         '(sequence-parallel fused decode); "auto" follows '
                         'distributed.sharding.cache_specs')
    ap.add_argument("--mesh-model", type=int, default=None,
                    help="model-axis size of the serving mesh (default: "
                         "all devices when --kv-mode seq, else 1)")
    args = ap.parse_args(argv)
    print(f"[serve] compile cache: {use_compile_cache()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    policy = resolve_policy(cfg, exp_backend=args.exp_backend,
                            kernel_backend=args.kernel_backend,
                            autotune=args.autotune or None,
                            prefill_chunk=args.prefill_chunk,
                            spec_k=args.spec_k,
                            draft_exp_backend=args.draft_backend,
                            spec_verify=args.spec_verify)
    groups = None
    if args.policy_groups:
        groups = parse_policy_groups(args.policy_groups, cfg, base=policy)
    print(f"[serve] policy: {policy.describe()}")
    if groups:
        for name, pol in groups.items():
            print(f"[serve]   group {name}: {pol.describe()}")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    n_model = args.mesh_model or (len(jax.devices())
                                  if args.kv_mode == "seq" else 1)
    mesh = make_host_mesh(1, n_model)
    injector = None
    if args.chaos:
        seed = (args.fault_seed if args.fault_seed is not None
                else int(os.environ.get(FAULT_SEED_ENV, "0") or "0"))
        injector = FaultInjector(seed=seed, rates=default_chaos_rates())
        print(f"[serve] chaos: seed={seed} rates={default_chaos_rates()}")
    degrade = tuple(s.strip() for s in (args.degrade_groups or "").split(",")
                    if s.strip())
    spec_groups = (tuple(s.strip() for s in args.spec_groups.split(",")
                         if s.strip())
                   if args.spec_groups is not None else None)
    server = Server(cfg, params, max_batch=args.max_batch,
                    max_seq=args.max_seq, mesh=mesh, policy=policy,
                    policy_groups=groups, kv_mode=args.kv_mode,
                    paged=args.paged, block_page=args.block_page,
                    block_budget=args.block_budget,
                    prefix_cache=not args.no_prefix_cache,
                    injector=injector, deadline_s=args.deadline,
                    degrade_groups=degrade, spec_groups=spec_groups)
    for name, g in server._groups.items():
        if g.spec_k:
            print(f"[serve] group {name}: speculative decode k={g.spec_k} "
                  f"draft={g.policy.draft_exp_backend} "
                  f"verify={g.policy.spec_verify}")
    print(f"[serve] mesh {dict(server.mesh.shape)}; sharded decode axis: "
          f"{server.kv_axis}" + ("; paged" if server.paged else ""))
    rng = np.random.default_rng(0)
    names = sorted(groups) if groups else ["default"]
    shared = rng.integers(0, cfg.vocab, (max(args.shared_prefix, 0),),
                          dtype=np.int32)
    reqs = []
    for i in range(args.requests):
        plen = (int(rng.integers(4, args.prompt_len + 1))
                if args.mixed_lengths else args.prompt_len)
        plen = max(plen, len(shared) + 1)   # >= 1 fresh suffix token
        prompt = rng.integers(0, cfg.vocab, (plen,), dtype=np.int32)
        prompt[:len(shared)] = shared
        reqs.append(Request(i, prompt, args.max_new,
                            group=names[i % len(names)]))
    t0 = time.perf_counter()
    for r in reqs:
        server.submit(r)
    if args.cancel_frac > 0:
        stride = max(1, int(round(1.0 / args.cancel_frac)))
        for r in reqs[::stride]:
            server.cancel(r.rid)
    server.drain()
    out = reqs
    dt = time.perf_counter() - t0
    ntok = sum(len(r.out) for r in out)
    ok = sum(r.finish_reason in ("max_new", "length_cap") for r in out)
    print(f"served {ok}/{len(out)} requests, {ntok} tokens in {dt:.2f}s "
          f"({ntok / dt:.1f} tok/s)")
    for name, s in server.stats().items():
        print(f"  group {name}: prefill attention {s['prefill_attention']}, "
              f"decode attention {s['decode_attention']}")
        print(f"  group {name}: {s['decode_steps']} decode steps, "
              f"request latency p50 {s['p50_req_s'] * 1e3:.1f}ms "
              f"p95 {s['p95_req_s'] * 1e3:.1f}ms, "
              f"ttft p50 {s['p50_ttft_s'] * 1e3:.1f}ms "
              f"p95 {s['p95_ttft_s'] * 1e3:.1f}ms")
        if s["prefill_chunks"]:
            print(f"    chunked prefill: width={s['prefill_chunk']}, "
                  f"{s['prefill_chunks']} chunks dispatched "
                  f"({s['chunk_s_total'] * 1e3:.1f}ms host dispatch)")
        if s.get("spec_k"):
            print(f"    speculative: k={s['spec_k']} "
                  f"verify={s['spec_verify']} bursts={s['spec_bursts']} "
                  f"drafted={s['spec_drafted']} "
                  f"accepted={s['spec_accepted']} "
                  f"rolled_back={s['spec_rolled_back']} "
                  f"(acceptance {s['spec_acceptance']:.2f})")
        if "pool" in s:
            p = s["pool"]
            line = (f"    pool: page={p['page']} used {p['pages_used']}/"
                    f"{p['pages_allocatable']} peak {p['peak_pages']} "
                    f"(logical {p['peak_logical_tokens']} tok, "
                    f"oversub {p['peak_oversubscription']:.2f}x)")
            if "prefix" in p:
                line += (f", prefix hit rate "
                         f"{p['prefix']['hit_rate']:.2f}")
            print(line)
    for name, s in server.stats().items():
        dropped = (s["cancelled"] + s["deadline_missed"]
                   + s["quarantined"] + s["shed"])
        if dropped or s["step_faults"] or s["admit_retries"]:
            print(f"    lifecycle: cancelled={s['cancelled']} "
                  f"deadline={s['deadline_missed']} "
                  f"quarantined={s['quarantined']} shed={s['shed']} "
                  f"step_faults={s['step_faults']} "
                  f"requeued={s['requeued']} "
                  f"admit_retries={s['admit_retries']}")
    if args.chaos:
        server.assert_idle_clean()
        fs = server.fault_stats()
        fired = fs.get("injector", {}).get("fired", {})
        print(f"[serve] chaos clean shutdown: zero leaked pages/slots; "
              f"faults fired: {fired or 'none'}; "
              f"degrade level at exit: {fs['degrade_level']}")
    for r in out[:3]:
        print(f"  req {r.rid} [{r.group}] len={len(r.prompt)}: "
              f"{r.out[:8]}... ({r.finish_reason})")
    # Chaos, cancellation and deadlines drop requests on purpose; without
    # them an unfinished request is a failure of the run.
    if ok < len(out) and not (args.chaos or args.cancel_frac > 0
                              or args.deadline is not None):
        reasons = sorted({str(r.finish_reason) for r in out
                          if r.finish_reason not in ("max_new",
                                                     "length_cap")})
        raise SystemExit(f"[serve] {len(out) - ok} of {len(out)} requests "
                         f"did not finish ({', '.join(reasons)})")


if __name__ == "__main__":
    main()
