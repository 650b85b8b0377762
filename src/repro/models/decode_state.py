"""Family-agnostic per-slot serving state: the DecodeState protocol.

The slot engine (``launch.serve``) used to hardcode its per-slot state as
a ``(kv_cache, (B,) positions)`` pair — an assumption smeared across
admission, decode, freeing and donation that made recurrent families
(ssm's per-layer ``(h, conv)`` snapshots, hybrid's mixed
recurrent/attention periods) unservable. This module is the replacement
boundary: one ``DecodeState`` object per policy group owning

  * the pool state pytree (``data``) — whatever arrays the family carries
    between decode steps, allocated once at pool width;
  * the per-slot device-side position vector (``pos_dev``), threaded and
    donated through the decode program so positions advance device-side;
  * the jitted prefill/decode programs (family-dispatched through
    ``models.api``, so one program builder covers every family).

The engine talks only to the protocol:

  ``prefill_into(slots, toks, plens, full=, uniform=)``
      run the pool-width (ragged right-padded) prefill and write the
      admitted rows into freed slots; returns the first greedy tokens.
  ``step(last, live)``
      one donated decode step over the pool; returns the next tokens.
  ``reset_slots(idx)``
      park freed slots (zero positions; recurrent states also zero their
      rows — stale ``h``/``conv`` from a previous occupant is read
      unconditionally every step, unlike KV rows which are masked by
      ``cache_len``).
  ``max_len()`` / ``prefill_width(n)`` / ``supports_seq_sharding(cfg)``
      capacity, admission width and SPMD capability probes — the engine
      never branches on the model family, only on these.

The generic pool ops (scatter admitted rows, pad a full-pool prefill to
capacity, zero freed slots) are driven by each family's leaf-axis
metadata (``state_spec.LeafAxes`` from ``transformer.cache_axes`` /
``ssm.state_axes`` / ``hybrid.cache_axes``): every leaf has one slot axis
and at most one sequence axis, which is all those operations need.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import hot_path

from . import api
from .block_pool import OutOfBlocks


def _guard_tokens(logits, last=None):
    """Greedy next-token with the non-finite sentinel folded in: a row
    whose logits are not all finite emits token ``-1`` (never a valid
    vocab id) instead of whatever ``argmax`` makes of NaN/inf. Passing
    ``last`` (the decode carry's previous tokens) makes the sentinel
    *sticky* — one poisoned step marks the slot until the engine
    quarantines it at the next scheduling event, even if later logits
    look finite again. Elementwise + one lane reduction, fused into the
    surrounding program: no collectives, no host work, no new outputs —
    the device-side per-slot finite-logits flag IS the token stream."""
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
    if last is not None:
        bad = bad | (last < 0)
    return jnp.where(bad, jnp.int32(-1), tok)


def _len_bucket(n: int, cap: int) -> int:
    """Pow2-rounded prefill length (>=8) so ragged admission shares a small
    set of prefill executables; capped at the cache's sequence capacity."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


# (repr(cfg), policy, decode_policy, kv_axis[, mesh]) -> (prefill_fn,
# prefill_plain_fn, decode_fn). jax.jit caches per function object, so the
# jitted closures must outlive any one Server — otherwise every server
# restart recompiles the programs. Greedy serving never reads logits on
# the host, so all programs return argmaxed (B, 1) token ids — one fused
# executable per step, no eager argmax dispatches.
#
# decode_fn(params, last, state, pos, live) -> (next, state, pos + live):
# the state pytree and the per-slot position vector are DONATED (their
# input buffers are reused for the outputs), so a decode step allocates no
# new state and the slot positions advance device-side — the hot loop
# performs zero host->device transfers and zero host syncs. The builder is
# family-generic: prefill/decode dispatch through models.api.
_PROGRAM_CACHE: dict = {}


def _programs(cfg, policy, mesh=None, kv_axis=None, decode_policy=None):
    # decode_policy: the (possibly merge-strategy-autotuned) policy the
    # decode program is built against; prefill keeps the group policy so
    # its in-jit autotune cache reads stay live.
    dpol = policy if decode_policy is None else decode_policy
    key = (repr(cfg), policy, dpol, kv_axis,
           mesh if kv_axis is not None else None)
    if key not in _PROGRAM_CACHE:
        pol = policy

        def prefill_fn(p, toks, plens):
            logits, state = api.prefill(
                p, cfg, {"tokens": toks, "prompt_len": plens}, policy=pol)
            return _guard_tokens(logits), state

        def prefill_plain_fn(p, toks):
            # every row full-length: no padding mask to apply (the common
            # uniform-traffic admission; skips the ragged machinery)
            logits, state = api.prefill(p, cfg, {"tokens": toks},
                                        policy=pol)
            return _guard_tokens(logits), state

        # chunk_fn(params, toks, state, off, clens) -> (next, state): one
        # fixed-shape resumable-prefill step over the whole pool. The
        # state is DONATED like the decode carry; rows with clens == 0
        # pass through bit-untouched, so decoding slots ride along free.
        def chunk_fn(p, toks, c, off, clens):
            logits, c = api.prefill_chunk(p, cfg, toks, c, off, clens,
                                          policy=pol)
            return _guard_tokens(logits), c

        if kv_axis is None:
            def decode_fn(p, t, c, pos, live):
                logits, state = api.decode_step(p, cfg, t, c, pos,
                                                policy=dpol, live=live)
                return _guard_tokens(logits, t), state, pos + live

            decode = jax.jit(decode_fn, donate_argnums=(2, 3))
            chunk = jax.jit(chunk_fn, donate_argnums=(2,))
        else:
            # Sequence-sharded decode (a KVDecodeState-only capability —
            # probed via supports_seq_sharding, never via the family):
            # ONE shard_map program per policy group, built here at engine
            # startup — the fused partial-statistics path instead of GSPMD
            # lowering. The cache lives (and stays) sharded along its S
            # axis; each layer's shard statistics fold through the
            # policy's merge strategy ("packed": one collective per
            # layer).
            from jax.sharding import PartitionSpec as P
            from repro.distributed.sharding import serve_cache_sharding
            from .transformer import decode_step_sharded
            # one source of truth for the pool placement: the program's
            # in/out specs are the spec of the sharding the engine
            # allocates the pool under.
            from jax.sharding import NamedSharding
            cshard = serve_cache_sharding(cfg, mesh, kv_axis)
            cspec = {name: s.spec for name, s in cshard.items()}

            def decode_local(p, t, c, pos, live):
                logits, c = decode_step_sharded(p, cfg, t, c, pos,
                                                policy=dpol,
                                                seq_axis=kv_axis,
                                                live=live)
                return _guard_tokens(logits, t), c, pos + live

            decode = jax.jit(
                jax.shard_map(decode_local, mesh=mesh,
                              in_specs=(P(), P(), cspec, P(), P()),
                              out_specs=(P(), cspec, P()), check_vma=False),
                donate_argnums=(2, 3))
            # Sharded chunk prefill: plain GSPMD with the carry pinned to
            # the pool placement on BOTH sides, so prefill compute lands
            # on the mesh and admitted rows are produced *under the pool
            # sharding* — no post-prefill re-placement device_put.
            repl = NamedSharding(mesh, P())
            chunk = jax.jit(chunk_fn,
                            in_shardings=(repl, repl, cshard, repl, repl),
                            out_shardings=(repl, cshard),
                            donate_argnums=(2,))

        _PROGRAM_CACHE[key] = (jax.jit(prefill_fn),
                               jax.jit(prefill_plain_fn),
                               decode, chunk)
    return _PROGRAM_CACHE[key]


# ---------------------------------------------------- speculative decoding

# Block-padding sentinel for token positions past a burst's accepted
# length. Distinct from the poison sentinel (-1): the engine filters PAD
# out of finished streams, while -1 still quarantines the slot.
SPEC_PAD = -2


def _spec_accept(toks, logits, clens, rem, live):
    """Device-side acceptance fold of one verify pass.

    ``toks`` (B, W) are the burst's candidates [t0, d1..dk] (t0 the
    pre-burst last token, d_i the draft proposals); ``logits`` (B, W, V)
    the exact-policy all-lane scores; ``clens`` (B,) the lanes actually
    scored (0 = dead/cap-full row); ``rem`` (B,) the per-slot remaining
    emission budget. Emits ``m = min(n_acc + 1, clens, rem)`` tokens per
    row: the longest draft prefix agreeing with the exact argmaxes plus
    the bonus token the exact pass proposes after it — so every emitted
    token is an exact-policy argmax and greedy output is identical to
    plain decode by construction. The non-finite poison sentinel is
    folded in lane-cumulatively (one bad lane poisons the rest of the
    burst) and stays sticky across bursts via t0 < 0. Elementwise + lane
    reductions only: no collectives, no host work."""
    b, w = toks.shape
    lanes = jnp.arange(w, dtype=jnp.int32)[None, :]
    e = jnp.argmax(logits, -1).astype(jnp.int32)                 # (B, W)
    badlane = ~jnp.all(jnp.isfinite(logits), axis=-1)
    bad = (jnp.cumsum(badlane.astype(jnp.int32), axis=1) > 0) \
        | (toks[:, :1] < 0)
    agree = (toks[:, 1:] == e[:, :-1]).astype(jnp.int32)         # (B, k)
    n_acc = jnp.sum(jnp.cumprod(agree, axis=1), axis=1)
    m = jnp.minimum(jnp.minimum(n_acc + 1, clens), rem)
    m = jnp.where(live > 0, jnp.maximum(m, 0), 0)
    tokv = jnp.where(bad, jnp.int32(-1), e)
    block = jnp.where(lanes < m[:, None], tokv, jnp.int32(SPEC_PAD))
    nlast = jnp.take_along_axis(tokv, jnp.clip(m - 1, 0, w - 1)[:, None], 1)
    nlast = jnp.where((m > 0)[:, None], nlast, toks[:, :1])
    return block, nlast, m


# (repr(cfg), policy, W, mode, cap[, page]) -> verify program. Same
# lifetime rationale as _PROGRAM_CACHE.
_SPEC_PROGRAM_CACHE: dict = {}


def _spec_programs(cfg, policy, w, mode, cap, page=None, impl="scan"):
    """ONE jitted exact-policy verify program for a W = spec_k + 1 draft
    burst, per (cfg, policy, W, pool flavor, impl).

    ``impl="scan"`` (default): the scoring pass is a ``lax.scan`` of W
    exact DECODE steps — the same per-token program math plain serving
    runs — fused with the acceptance fold into one dispatch. A
    chunk-shaped (all-lanes parallel) scoring pass was measured to
    differ from the decode-step path by ~1 bf16 ulp (different
    attention program shape, different XLA fusions), which flips argmax
    on near-tie logits and silently breaks the speculative == plain
    token-identity contract; the scan of decode steps makes identity
    hold by CONSTRUCTION, not by fp luck.

    ``impl="chunk"`` (KV modes only): scores all W lanes in ONE batched
    ``prefill_chunk`` pass — cache and weights are read once per burst
    instead of once per lane, which is the whole speculative speedup
    (a W-lane chunk costs about half of ONE decode step at serving
    sequence lengths). The price is the ~1-ulp divergence above: tokens
    remain exact-policy argmaxes of the chunk program, but near-tie
    logits may break ties differently than plain decode. Throughput
    mode; the identity contract holds only for "scan".

    Acceptance length ``m = min(n_agree + 1, clens, rem)`` is computed
    device-side and folded into the carry (positions advance by m,
    budgets shrink by m), so a burst costs zero host syncs. Modes:

      "kv"              single pass over the post-draft pool (donated):
                        the scan rewrites every burst row with exact
                        KV before any later step reads it, so the
                        cursor rewind IS the rollback — rows past the
                        new cursor are stale but cache_len-masked.
      "kv_paged"        same over a paged pool; tables are read-only
                        and NO page moves: full reservation means
                        rollback touches the allocator zero times.
      "recurrent"       two scans from the pre-burst snapshot c0
                        (recurrent state/ring KV has no rewindable
                        addressing): scan 1 scores all W lanes (state
                        discarded), scan 2 replays c0 through exactly
                        the accepted tokens with per-step live masking
                        — bit-identical to plain decode stopping at m.
                        c0 feeds both scans, so it is never donated.
      "recurrent_paged" the same two scans over the hybrid ring pools.

    ``cap`` is the linear cache capacity (lanes at positions >= cap are
    live-masked so the scan never writes past the pool) or None
    (recurrent state and ring buffers never exhaust)."""
    if impl not in ("scan", "chunk"):
        raise ValueError(f"unknown speculative verify impl {impl!r}")
    if impl == "chunk" and mode not in ("kv", "kv_paged"):
        raise ValueError(
            f"chunk verify needs a rewindable KV cache; mode {mode!r} "
            f"replays state step-exactly (use impl='scan')")
    key = (repr(cfg), policy, int(w), mode, cap, page, impl)
    if key not in _SPEC_PROGRAM_CACHE:
        pol = policy
        paged = mode.endswith("_paged")

        def _clens(pos0, live):
            room = (jnp.full_like(pos0, w) if cap is None
                    else jnp.int32(cap) - pos0)
            return jnp.where(live > 0, jnp.clip(room, 0, w), 0)

        def _lanes(toks):
            # scan inputs: ((W, B, 1) tokens, (W,) lane index)
            return (toks.T[:, :, None], jnp.arange(w, dtype=jnp.int32))

        def _scan(p, toks, c, tab, pos0, live, nlive, want_logits):
            # W decode steps fused into one program; step i runs with
            # live_i = live * (i < nlive), so masked lanes leave state
            # AND position bit-untouched — exactly a plain decode loop
            # that stopped after nlive steps.
            def body(carry, x):
                c, pos = carry
                ti, i = x
                lv = live * (i < nlive).astype(jnp.int32)
                if paged:
                    logits, c = api.decode_step_paged(
                        p, cfg, ti, c, tab, pos, policy=pol, live=lv)
                else:
                    logits, c = api.decode_step(p, cfg, ti, c, pos,
                                                policy=pol, live=lv)
                return (c, pos + lv), (logits[:, 0] if want_logits
                                       else jnp.zeros((), jnp.int32))
            (c, pos), ls = jax.lax.scan(body, (c, pos0), _lanes(toks))
            logits = (jnp.transpose(ls, (1, 0, 2)) if want_logits
                      else None)                              # (B, W, V)
            return logits, c, pos

        if mode in ("kv", "kv_paged") and impl == "chunk":
            def score_fn(p, toks, c, tab, pos0, rem, live):
                clens = _clens(pos0, live)
                if paged:
                    logits, c = api.prefill_chunk_paged(
                        p, cfg, toks, c, tab, pos0, clens, policy=pol,
                        all_lanes=True)
                else:
                    logits, c = api.prefill_chunk(
                        p, cfg, toks, c, pos0, clens, policy=pol,
                        all_lanes=True)
                block, nlast, m = _spec_accept(toks, logits, clens, rem,
                                               live)
                return block, nlast, c, pos0 + m, rem - m
        elif mode in ("kv", "kv_paged"):
            def score_fn(p, toks, c, tab, pos0, rem, live):
                clens = _clens(pos0, live)
                logits, c, _ = _scan(p, toks, c, tab, pos0, live, clens,
                                     True)
                block, nlast, m = _spec_accept(toks, logits, clens, rem,
                                               live)
                return block, nlast, c, pos0 + m, rem - m
        else:
            def score_fn(p, toks, c0, tab, pos0, rem, live):
                clens = _clens(pos0, live)
                logits, _, _ = _scan(p, toks, c0, tab, pos0, live, clens,
                                     True)
                block, nlast, m = _spec_accept(toks, logits, clens, rem,
                                               live)
                # the accepted tokens ARE toks[:, :m] (draft i agreed
                # with exact for i < m), so the replay feeds toks again
                c2, pos2 = _scan(p, toks, c0, tab, pos0, live, m,
                                 False)[1:]
                return block, nlast, c2, pos2, rem - m

        if mode == "kv":
            def verify_fn(p, toks, c, pos0, rem, live):
                return score_fn(p, toks, c, None, pos0, rem, live)

            verify = jax.jit(verify_fn, donate_argnums=(2, 3, 4))
        elif mode == "kv_paged":
            # XLA-CPU materializes the pool copy regardless; donation
            # would only add copies (mirrors _paged_programs).
            pool_d = () if jax.default_backend() == "cpu" else (2,)

            def verify_fn(p, toks, c, tab, pos0, rem, live):
                return score_fn(p, toks, c, tab, pos0, rem, live)

            verify = jax.jit(verify_fn, donate_argnums=pool_d + (4, 5))
        elif mode == "recurrent":
            def verify_fn(p, toks, c0, pos0, rem, live):
                return score_fn(p, toks, c0, None, pos0, rem, live)

            verify = jax.jit(verify_fn, donate_argnums=(3, 4))
        elif mode == "recurrent_paged":
            def verify_fn(p, toks, c0, tab, pos0, rem, live):
                return score_fn(p, toks, c0, tab, pos0, rem, live)

            verify = jax.jit(verify_fn, donate_argnums=(4, 5))
        else:
            raise ValueError(f"unknown speculative mode {mode!r}")

        _SPEC_PROGRAM_CACHE[key] = verify
    return _SPEC_PROGRAM_CACHE[key]


class DecodeState:
    """Base of the per-family serving-state implementations.

    Subclasses provide ``kind``, ``_state_axes(cfg)`` and (optionally)
    capability overrides; the pool algebra below is generic.
    """

    kind = "state"
    is_paged = False   # True for the block-pool states below

    @classmethod
    def supports_seq_sharding(cls, cfg) -> bool:
        """Whether this state can decode over a sequence-sharded pool
        (the SPMD serve loop). Only linear KV caches can."""
        return False

    def __init__(self, cfg, params, policy, pool_width, cache_s, *,
                 mesh=None, kv_axis=None):
        self.cfg, self.params, self.policy = cfg, params, policy
        self.pool_width, self.cache_s = pool_width, cache_s
        self.mesh, self.kv_axis = mesh, kv_axis
        self.axes = self._state_axes(cfg)
        self.data = None                 # pool pytree; set on first admit
        self.pos_dev = jnp.zeros((pool_width,), jnp.int32)
        self.params_decode = params
        self._repl = None                # mesh-replicated sharding (SPMD)
        self._state_shard = None         # sharded pool placement (SPMD)
        self._setup_placement()
        if self._repl is not None:
            self.params_decode = jax.device_put(params, self._repl)
            self.pos_dev = jax.device_put(self.pos_dev, self._repl)
        self.injector = None             # chaos harness (ft.inject)
        decode_policy = self._autotune_warmup()
        # remembered so set_policy can restore the EXACT original
        # programs (incl. the autotuned decode policy) after degradation
        self._policy0, self._dpol0 = policy, decode_policy
        self._dpol = decode_policy       # ACTIVE decode policy
        self._spec_k = 0                 # 0 = plain decode (no draft burst)
        (self._prefill, self._prefill_plain, self._decode,
         self._chunk) = _programs(cfg, policy, mesh, kv_axis,
                                  decode_policy)

    # ------------------------------------------------------- family hooks

    def _state_axes(self, cfg):
        raise NotImplementedError

    def _setup_placement(self):
        pass                             # single-device default

    def _autotune_warmup(self):
        return self.policy

    def max_len(self):
        """Length at which a slot must stop decoding (None = unbounded:
        recurrent state and ring-buffer windows never exhaust)."""
        return None

    def prefill_width(self, n: int) -> int:
        """Admission width for a wave whose longest prompt is ``n``."""
        return _len_bucket(n, self.cache_s)

    # --------------------------------------------------------- placement

    def place_tokens(self, x):
        """Place an engine-side array (tokens/liveness) next to the
        decode program's inputs (replicated on the mesh for SPMD)."""
        return x if self._repl is None else jax.device_put(x, self._repl)

    def _place_state(self, tree):
        if self._state_shard is None:
            return tree
        return jax.device_put(tree, self._state_shard)

    # ------------------------------------------------------- engine ops

    def prefill_into(self, slots, toks, plens, *, full, uniform=False):
        """One pool-width batched prefill; admitted rows land in freed
        slots. ``toks`` (pool_width, sp) right-padded prompts, ``plens``
        (pool_width,) real lengths (1 for rows without a request);
        ``full`` = the whole pool admitted at once (the prefill output
        *is* the pool, padded to capacity — no scatter); ``uniform`` =
        run the unmasked plain prefill (no padding exists). Returns the
        (pool_width, 1) first greedy tokens, placed for decode."""
        self._maybe_inject_admission_fault()
        if uniform:
            first, pref = self._prefill_plain(self.params,
                                              jnp.asarray(toks))
        else:
            first, pref = self._prefill(self.params, jnp.asarray(toks),
                                        jnp.asarray(plens))
        first = self.place_tokens(first)
        sp = toks.shape[1]
        if full:
            def pad(leaf, ax):
                if ax.seq is None or leaf.shape[ax.seq] == self.cache_s:
                    return leaf
                widths = [(0, 0)] * leaf.ndim
                widths[ax.seq] = (0, self.cache_s - leaf.shape[ax.seq])
                return jnp.pad(leaf, widths)

            self.data = self._place_state(
                jax.tree.map(pad, pref, self.axes))
        else:
            if self.data is None:
                self.data = self._place_state(
                    api.init_cache(self.cfg, self.pool_width,
                                   self.cache_s))
            sl = jnp.asarray(np.asarray(slots))

            def insert(pool, leaf, ax):
                rows_idx = [slice(None)] * leaf.ndim
                rows_idx[ax.batch] = sl
                rows = leaf[tuple(rows_idx)]
                if self._repl is not None:
                    rows = jax.device_put(rows, self._repl)
                idx = [slice(None)] * pool.ndim
                idx[ax.batch] = sl
                if ax.seq is not None:
                    idx[ax.seq] = slice(0, sp)
                return pool.at[tuple(idx)].set(rows)

            self.data = jax.tree.map(insert, self.data, pref, self.axes)
        sl = jnp.asarray(np.asarray(slots))
        self.pos_dev = self.pos_dev.at[sl].set(
            jnp.asarray(np.asarray(plens)[np.asarray(slots)], jnp.int32))
        return first

    @hot_path
    def step(self, last, live):
        """One donated decode step over the pool; positions advance by
        ``live`` device-side. Returns the (pool_width, 1) next tokens."""
        nxt, self.data, self.pos_dev = self._decode(
            self.params_decode, last, self.data, self.pos_dev, live)
        return nxt

    def reset_slots(self, slots):
        """Park freed slots: zero their positions and (where
        ``_reset_leaf`` says so) state rows, so a stale occupant can
        never bleed into the next request admitted into the slot
        (recurrent ``h``/``conv`` is read unconditionally every step)."""
        sl = jnp.asarray(np.asarray(slots))
        self.pos_dev = self.pos_dev.at[sl].set(0)
        if self.data is not None:
            def zero(leaf, ax):
                if not self._reset_leaf(ax):
                    return leaf
                idx = [slice(None)] * leaf.ndim
                idx[ax.batch] = sl
                return leaf.at[tuple(idx)].set(0)

            self.data = jax.tree.map(zero, self.data, self.axes)

    def _reset_leaf(self, ax) -> bool:
        """Whether ``reset_slots`` must zero a leaf with these axes.
        Default: every leaf (recurrent snapshots are read
        unconditionally). KV-bearing states skip their sequence leaves —
        decode masks those rows by ``cache_len`` and admission prefill
        overwrites them, so zeroing (S, Hkv, hd) rows per finish would
        out-cost a decode step."""
        return True

    # ------------------------------------------------- chunked prefill

    def supports_chunked(self) -> bool:
        """Whether this pool admits prompts through the resumable chunk
        path (``begin_chunk`` / ``prefill_chunk_into`` /
        ``finish_chunk``). Contiguous pools always can: prefill positions
        never wrap a ring (prompts fit the allocated width — the same
        invariant monolithic admission relies on), so cache slot ==
        absolute position throughout prefill."""
        return True

    def chunk_width(self, c: int) -> int:
        """Resolve a requested chunk budget of ``c`` tokens to this
        family's program width. Families with chunk-decomposed
        recurrences round up so chunk boundaries stay on their native
        block size (admission-invariant fp summation order)."""
        return max(1, int(c))

    def begin_chunk(self, slot, prompt, plen) -> int:
        """Start chunked admission of a ``plen``-token prompt into
        ``slot``; returns the starting cursor (tokens already cached —
        nonzero when a paged pool attaches prefix-cache hit pages). The
        slot's position is pinned at ``plen`` now: decode steps in
        between see the row as dead (live == 0) and leave both the state
        row and the parked position untouched, so the completion tick
        flips the slot live with no extra device write."""
        del prompt
        self._maybe_inject_admission_fault()
        self.pos_dev = self.pos_dev.at[int(slot)].set(int(plen))
        return 0

    def finish_chunk(self, slot, prompt, plen):
        """Complete a chunked admission (paged pools publish the
        prompt's full pages to the prefix cache here)."""

    @hot_path
    def prefill_chunk_into(self, toks, offs, clens):
        """One fixed-shape chunk step over the whole pool: ``toks``
        (pool_width, C) chunk tokens, ``offs``/``clens`` (pool_width,)
        per-slot cursors and valid counts (0 = row not prefilling this
        tick; such rows pass through bit-untouched). Returns the
        (pool_width, 1) greedy tokens at each row's last valid lane —
        meaningful only for rows whose prompt completes this chunk."""
        if self.data is None:
            self.data = self._place_state(
                api.init_cache(self.cfg, self.pool_width, self.cache_s))
        first, self.data = self._chunk(
            self.params_decode, self.place_tokens(jnp.asarray(toks)),
            self.data, self.place_tokens(jnp.asarray(offs, jnp.int32)),
            self.place_tokens(jnp.asarray(clens, jnp.int32)))
        return first

    # ----------------------------------------------------------- shared

    def _linear_cap(self):
        # A pool smaller than the sliding window can never wrap its ring
        # buffer correctly (the write cursor is pos % window, which runs
        # past the pool's extent) — such a pool behaves like a linear
        # cache and must stop slots at capacity, exactly like a
        # window-less cache. Only a full-window pool decodes unbounded.
        w = self.cfg.sliding_window
        if w is None or self.cache_s < w:
            return self.cache_s
        return None

    # ----------------------------------------- fault tolerance / lifecycle

    def set_injector(self, inj):
        """Wire the chaos harness (``ft.inject.FaultInjector``) into this
        pool's scheduling-event paths. ``None`` (the default) disables
        injection; every guarded site then pays one attribute check."""
        self.injector = inj

    def _maybe_inject_admission_fault(self):
        if self.injector is not None and \
                self.injector.fire("admit.out_of_blocks"):
            raise OutOfBlocks("injected: admission rejected")

    def abort_chunk(self, slot):
        """Abandon a mid-chunk admission: release everything
        ``begin_chunk`` reserved for ``slot`` (pages, prefix refs, table
        row, pinned position) and park the slot. ``reset_slots`` already
        IS that release for every implementation — paged pools decref the
        slot's pages, drop its pending hit depth and zero its table row —
        so the protocol method is the documented alias; the engine calls
        ``abort_chunk`` so the intent (reservation rollback, not a
        finished request) reads at the call site."""
        self.reset_slots([int(slot)])

    def poison_slot(self, slot) -> bool:
        """Corrupt one slot's private state with NaNs (the
        ``decode.poison`` chaos fault). Returns False when there is
        nothing to poison yet (pool unallocated). The decode program's
        finite-logits guard must turn this into sentinel tokens — never
        into silently-wrong samples."""
        if self.data is None:
            return False
        j = int(slot)

        def nanify(leaf, ax):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            idx = [slice(None)] * leaf.ndim
            idx[ax.batch] = j
            return leaf.at[tuple(idx)].set(jnp.nan)

        self.data = jax.tree.map(nanify, self.data, self.axes)
        return True

    def corrupt_prefix(self, injector) -> int:
        """Invalidate prefix-cache chains (the ``prefix.corrupt`` fault:
        detected corruption is handled by dropping the entry, never by
        serving it). Contiguous pools have no cache; paged KV overrides.
        Returns the number of entries invalidated."""
        return 0

    def scrub_slot(self, slot):
        """Quarantine release: zero EVERY floating leaf row of the slot
        — not just the rows ``reset_slots`` zeroes — then park it. A
        poisoned row's NaNs must not outlive its request: KV rows past a
        later occupant's ``cache_len`` still flow through additively-
        masked attention scores (NaN + -inf = NaN), so the plain reset
        (which skips cache_len-masked leaves by design) is not enough."""
        j = int(slot)
        if self.data is not None:
            def zero(leaf, ax):
                if not jnp.issubdtype(leaf.dtype, jnp.floating):
                    return leaf
                idx = [slice(None)] * leaf.ndim
                idx[ax.batch] = j
                return leaf.at[tuple(idx)].set(0)

            self.data = jax.tree.map(zero, self.data, self.axes)
        self.reset_slots([j])

    def recover(self):
        """Rebuild the pool after a failed (donated) decode dispatch. A
        raised step must be presumed to have consumed the donated carry
        buffers, so the only safe move is to drop the pool and park every
        slot; the engine re-queues the victims through normal
        admission."""
        self.data = None
        self.pos_dev = jnp.zeros((self.pool_width,), jnp.int32)
        if self._repl is not None:
            self.pos_dev = jax.device_put(self.pos_dev, self._repl)

    def set_policy(self, policy):
        """Swap the group's execution policy in place (the degradation
        ladder's lever). Programs come from the module-level cache, so
        flipping to a previously-used policy — including back to the
        original — is a dict lookup, not a recompile. Returns the decode
        policy the programs were built against (the original autotuned
        one when restoring)."""
        dpol = self._dpol0 if policy == self._policy0 else policy
        self.policy = policy
        self._dpol = dpol
        (self._prefill, self._prefill_plain, self._decode,
         self._chunk) = _programs(self.cfg, policy, self.mesh,
                                  self.kv_axis, dpol)
        if self._spec_k:
            # degradation rebuilds the draft + verify programs against
            # the group's ACTIVE policy: "speculative == plain decode
            # under this policy" holds on every ladder rung.
            self._wire_spec()
        return dpol

    # ------------------------------------------------- speculative decoding

    def supports_speculative(self) -> bool:
        """Whether this pool can run draft bursts + batched verify (the
        self-speculative decode path). Gated per subclass on the chunk
        program's addressing model (linear, unsharded)."""
        return False

    def _spec_mode(self) -> str:
        raise NotImplementedError

    def _spec_copy_state(self) -> bool:
        """Whether a burst snapshot must copy the state pytree. False
        for positional (KV) pools — the verify chunk overwrites draft
        rows with exact rows and the cursor rewind IS the rollback;
        True for recurrent state, which has no positions to rewind."""
        return False

    def enable_speculative(self, spec_k: int) -> None:
        """Switch the pool to self-speculative decode: k-step draft
        bursts under the policy's ``draft_exp_backend`` verified by ONE
        batched exact-policy pass. Builds (cache-hits) the draft decode
        and verify programs; re-wired by ``set_policy`` so degradation
        keeps draft/verify consistent with the active rung."""
        if not self.supports_speculative():
            raise ValueError(
                f"{self.kind} state cannot run speculative decode")
        if not (isinstance(spec_k, int) and spec_k >= 2):
            raise ValueError(f"spec_k must be an int >= 2, got {spec_k!r}")
        self._spec_k = int(spec_k)
        self._wire_spec()

    def _draft_policy(self):
        # the ACTIVE decode policy with only its exp backend swapped:
        # autotuned fields and degradation state carry over, so draft
        # and exact programs differ in exactly one execution choice.
        return self._dpol.replace(exp_backend=self.policy.draft_exp_backend)

    def _spec_impl(self) -> str:
        # recurrent replays must be step-exact; KV modes honor the
        # policy's scan/chunk verify choice.
        mode = self._spec_mode()
        return (self.policy.spec_verify if mode in ("kv", "kv_paged")
                else "scan")

    def _wire_spec(self):
        self._draft_decode = _programs(self.cfg, self.policy, self.mesh,
                                       self.kv_axis,
                                       self._draft_policy())[2]
        self._verify = _spec_programs(self.cfg, self.policy,
                                      self._spec_k + 1, self._spec_mode(),
                                      self.max_len(),
                                      impl=self._spec_impl())

    def spec_snapshot(self):
        """Pre-burst snapshot: a FRESH positions buffer (draft steps
        donate ``pos_dev``) plus, for recurrent families, a copy of the
        state the burst will advance. Cheap where rollback is cheap: KV
        pools snapshot positions only."""
        pos0 = self.pos_dev + 0
        state0 = (jax.tree.map(jnp.copy, self.data)
                  if self._spec_copy_state() else None)
        return (pos0, state0)

    def spec_restore(self, snap):
        """Roll every slot back to a snapshot (bitwise). ``verify_step``
        is the normal consumer of a snapshot — acceptance folds the
        rewind into the verify program — so the explicit restore is the
        abort/fault path and the protocol's testable rollback contract.
        On KV pools the cursor rewind is the whole rollback (stale draft
        rows past the cursor are cache_len-masked and overwritten by the
        next burst); paged pools additionally touch the allocator ZERO
        times — full reservation means every page is already held and
        no accepted-prefix page is ever freed."""
        pos0, state0 = snap
        self.pos_dev = pos0 + 0
        if state0 is not None:
            self.data = jax.tree.map(jnp.copy, state0)

    @hot_path
    def draft_step(self, last, live):
        """One decode step under the DRAFT policy's program — the same
        carry contract as ``step`` (state + positions donated, zero host
        work), differing only in the exp backend the kernels route to."""
        nxt, self.data, self.pos_dev = self._draft_decode(
            self.params_decode, last, self.data, self.pos_dev, live)
        return nxt

    @hot_path
    def verify_step(self, toks, snap, rem, live):
        """ONE batched exact-policy pass scoring all W = k + 1 burst
        candidates at their per-slot offsets. Returns ``(block, last,
        rem)``: the (B, W) accepted-token block (SPEC_PAD past each
        row's accepted length), the new last token, and the advanced
        budget. Acceptance length is computed device-side and folded
        into the carry — positions advance by m inside the program, so
        a burst adds zero host syncs over a plain decode tick."""
        pos0, state0 = snap
        carry = self.data if state0 is None else state0
        block, nlast, self.data, self.pos_dev, rem = self._verify(
            self.params_decode, toks, carry, pos0, rem, live)
        return block, nlast, rem

    def check_integrity(self, live_slots=()):
        """Post-fault invariant sweep (deliberately NOT hot-path: it
        syncs). Freed slots must be parked at position 0 — a nonzero
        parked position means an abort path skipped ``reset_slots``."""
        live = {int(j) for j in live_slots}
        pos = np.asarray(self.pos_dev)
        for j in range(self.pool_width):
            if j not in live and int(pos[j]) != 0:
                raise AssertionError(
                    f"freed slot {j} parked at pos {int(pos[j])}")


class KVDecodeState(DecodeState):
    """Transformer families (dense / moe / vlm): today's KV cache +
    per-slot positions, including the sequence-sharded SPMD path."""

    kind = "kv"

    @classmethod
    def supports_seq_sharding(cls, cfg) -> bool:
        # windowed archs keep the GSPMD path: the ring-buffer wrap write
        # straddles shard boundaries.
        return cfg.sliding_window is None

    def _state_axes(self, cfg):
        from .transformer import cache_axes
        return cache_axes(cfg)

    def max_len(self):
        # a linear cache is exhausted when the next write would fall past
        # the last slot; ring-buffer windows wrap instead.
        return self._linear_cap()

    def supports_speculative(self) -> bool:
        # linear caches only: the cheap position-only rollback relies
        # on rejected rows staying cache_len-masked until overwritten —
        # a ring-buffer wrap instead DESTROYS the pre-burst row it
        # lands on, which only a (costly) pool snapshot could restore.
        # Single-partition (the verify program is unsharded) and
        # token-only families (vlm extras don't fit a decode scan).
        return (self.kv_axis is None and self.max_len() is not None
                and self.cfg.family not in ("vlm", "audio"))

    def _spec_mode(self) -> str:
        return "kv"

    def _setup_placement(self):
        if self.kv_axis is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import serve_cache_sharding
        # decode runs over the mesh; prefill stays on the default device
        # (its outputs are re-placed at admission).
        self._repl = NamedSharding(self.mesh, P())
        self._state_shard = serve_cache_sharding(self.cfg, self.mesh,
                                                 self.kv_axis)

    def _reset_leaf(self, ax) -> bool:
        return False      # pure KV: every leaf is cache_len-masked

    def _autotune_warmup(self):
        """Eagerly tune the decode-attention block size for this group's
        decode shape. Timing is meaningless inside the jitted decode
        program (tracers, not device work), so the tuner only ever
        *reads* its cache there — this one eager call at the real
        (pool_width, cache_s) shape times the candidates, memoizes the
        winner for the jit path to pick up, and persists it to disk so
        the next server start skips even this.

        On a sequence-sharded group it additionally times the two
        collective merge strategies (packed single-collective vs
        pmax+2×psum) at the group's exact decode shape and returns the
        policy with the winner baked in (the shard_map decode program
        takes the policy statically, so it must resolve before the
        program is built). Returns the — possibly tuned — policy.
        """
        cfg, policy = self.cfg, self.policy
        if not policy.autotune or policy.kernel_backend != "pallas":
            return policy
        from repro.kernels.dispatch import dispatch, autotune_policy
        lay = cfg.kv_cache_layout
        pool = jax.eval_shape(
            lambda: api.init_cache(cfg, self.pool_width, self.cache_s))["k"]
        q = jnp.zeros((self.pool_width, 1, cfg.n_heads, cfg.hd),
                      jnp.dtype(cfg.compute_dtype))
        kv = jnp.zeros(pool.shape[1:], pool.dtype)   # one layer of the pool
        clen = jnp.full((self.pool_width,), self.cache_s, jnp.int32)
        dispatch("decode_attention", policy)(q, kv, kv, clen, layout=lay,
                                             policy=policy)
        if self.kv_axis is None:
            return policy
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels.decode_attention.ops import _sharded_program
        from .transformer import cache_seq_axis as _csa
        spec = [None] * kv.ndim
        spec[_csa(lay, stacked=False)] = self.kv_axis
        kvs = jax.device_put(kv, NamedSharding(self.mesh, P(*spec)))
        return autotune_policy(
            "decode_attention_sharded", policy,
            lambda p: _sharded_program(self.mesh, self.kv_axis, None, None,
                                       lay, p)(q, kvs, kvs, clen),
            q, kvs)


class RecurrentDecodeState(DecodeState):
    """ssm (mamba2/SSD): batched per-layer (h, conv) snapshots. No
    sequence axis anywhere — a slot's state is O(1) in its length, so
    there is no capacity cap and admission scatters whole slot rows."""

    kind = "recurrent"

    def _state_axes(self, cfg):
        from .ssm import state_axes
        return state_axes(cfg)

    def chunk_width(self, c: int) -> int:
        # Chunk boundaries pinned to the SSD block size: a boundary on a
        # ``cfg.ssm_chunk`` multiple keeps the per-block decomposition —
        # and so the fp summation order — identical to a one-shot pass,
        # making chunked prefill bitwise admission-invariant.
        q = self.cfg.ssm_chunk
        return -(-max(1, int(c)) // q) * q

    def supports_speculative(self) -> bool:
        return True                      # O(1) state: no cap, no shards

    def _spec_mode(self) -> str:
        return "recurrent"

    def _spec_copy_state(self) -> bool:
        return True


class HybridDecodeState(DecodeState):
    """hybrid (recurrentgemma/griffin): mixed per-period state — RG-LRU
    ``(h, conv)`` snapshots next to ring-buffer local-attention KV."""

    kind = "hybrid"

    def _state_axes(self, cfg):
        from .hybrid import cache_axes
        return cache_axes(cfg)

    def max_len(self):
        return self._linear_cap()

    def _reset_leaf(self, ax) -> bool:
        # zero only the recurrent snapshots; the ring-buffer KV leaves
        # are cache_len-masked and fully overwritten by the fixed-width
        # admission prefill, so zeroing them per finish is wasted work.
        return ax.seq is None

    def prefill_width(self, n: int) -> int:
        # Fixed admission width: the RG-LRU associative scan's combine
        # tree — and therefore its fp summation order — depends on the
        # scan *length*, so pow2 buckets would make a row's state drift
        # with the admission wave it rode in (vs. solo serving). A fixed
        # width keeps batched tokens bit-identical to solo tokens; it is
        # bounded by the sliding window, so the cost stays modest.
        return self.cache_s

    def supports_speculative(self) -> bool:
        # both regimes: the verify scans run plain decode steps, which
        # wrap the ring natively, and the snapshot copies the WHOLE
        # mixed state (RG-LRU rows AND ring KV) — a rejected burst's
        # ring overwrites are rebuilt from c0 by the replay scan, so
        # wrap-destroyed rows are never lost.
        return self.kv_axis is None

    def _spec_mode(self) -> str:
        return "recurrent"

    def _spec_copy_state(self) -> bool:
        return True


# --------------------------------------------------------------- paged pool

# (repr(cfg), policy, decode_policy, page, kv_axis[, mesh]) ->
# (prefill_hist_fn, decode_fn). Same lifetime rationale as _PROGRAM_CACHE.
_PAGED_PROGRAM_CACHE: dict = {}


def _paged_programs(cfg, policy, page, mesh=None, kv_axis=None,
                    decode_policy=None):
    dpol = policy if decode_policy is None else decode_policy
    key = (repr(cfg), policy, dpol, page, kv_axis,
           mesh if kv_axis is not None else None)
    if key not in _PAGED_PROGRAM_CACHE:
        pol = policy

        def prefill_hist_fn(p, toks, plens, hist):
            # suffix prefill against the shared-prefix KV gathered from
            # the pool (prefix-cache hot admission)
            logits, state = api.prefill(
                p, cfg, {"tokens": toks, "prompt_len": plens,
                         "hist": hist}, policy=pol)
            return _guard_tokens(logits), state

        # The pool donates everywhere except the CPU backend: XLA-CPU
        # lowers the page scatter to a full-pool materialization whether
        # or not the input buffer is donated, so donation there buys no
        # in-place update — it only adds an alias-restoring copy of the
        # whole pool per step (~25% of a reduced decode step). Positions
        # always donate; they are what keeps the hot loop host-sync-free.
        pool_d = () if jax.default_backend() == "cpu" else (2,)

        if kv_axis is None:
            def decode_fn(p, t, c, tab, pos, live):
                logits, c = api.decode_step_paged(p, cfg, t, c, tab, pos,
                                                  policy=dpol, live=live)
                return _guard_tokens(logits, t), c, pos + live

            decode = jax.jit(decode_fn, donate_argnums=pool_d + (4,))

            # chunk_fn(params, toks, pool, tables, off, clens): resumable
            # prefill scattered straight into the slots' reserved pages.
            # Sharded paged pools hold partition-local page ids the host
            # allocator owns — they admit monolithically (no chunk
            # program is built for them).
            def chunk_fn(p, toks, c, tab, off, clens):
                logits, c = api.prefill_chunk_paged(
                    p, cfg, toks, c, tab, off, clens, policy=pol)
                return _guard_tokens(logits), c

            chunk = jax.jit(chunk_fn, donate_argnums=pool_d)
        else:
            from jax.sharding import PartitionSpec as P
            from .transformer import decode_step_paged_sharded
            cspec = {"k": P(None, kv_axis), "v": P(None, kv_axis)}
            tspec = P(None, kv_axis)

            def decode_local(p, t, c, tab, pos, live):
                logits, c = decode_step_paged_sharded(
                    p, cfg, t, c, tab, pos, policy=dpol, seq_axis=kv_axis,
                    live=live)
                return _guard_tokens(logits, t), c, pos + live

            decode = jax.jit(
                jax.shard_map(decode_local, mesh=mesh,
                              in_specs=(P(), P(), cspec, tspec, P(), P()),
                              out_specs=(P(), cspec, P()), check_vma=False),
                donate_argnums=pool_d + (4,))
            chunk = None

        _PAGED_PROGRAM_CACHE[key] = (jax.jit(prefill_hist_fn), decode,
                                     chunk)
    return _PAGED_PROGRAM_CACHE[key]


def tune_block_page(cfg, policy, pool_width, cache_s):
    """Resolve the pool's page size BEFORE the pool exists: the page size
    is a pool-construction parameter (it shapes every KV leaf), so unlike
    ``block_s`` it can never be re-tuned per call — this one eager
    autotune over ``CANDIDATES["decode_attention_paged"]`` times each
    candidate on a synthetic pool of the group's real decode shape and
    the winner is baked into the pool. Non-autotuning / non-pallas
    policies use ``policy.block_page`` as-is."""
    if not policy.autotune or policy.kernel_backend != "pallas":
        return policy.block_page
    from repro.kernels.dispatch import autotune_policy, dispatch
    lay = cfg.kv_cache_layout
    q = jnp.zeros((pool_width, 1, cfg.n_heads, cfg.hd),
                  jnp.dtype(cfg.compute_dtype))
    clen = jnp.full((pool_width,), cache_s, jnp.int32)

    def run(p):
        pg = p.block_page
        ns = -(-cache_s // pg)
        n = 1 + pool_width * ns
        shape = ((n, cfg.n_kv_heads, pg, cfg.hd) if lay == "bhsd"
                 else (n, pg, cfg.n_kv_heads, cfg.hd))
        pool = jnp.zeros(shape, jnp.bfloat16)
        tab = jnp.arange(1, 1 + pool_width * ns,
                         dtype=jnp.int32).reshape(pool_width, ns)
        return dispatch("decode_attention_paged", p)(
            q, pool, pool, tab, clen, layout=lay, policy=p)

    tuned = autotune_policy("decode_attention_paged", policy, run, q)
    return tuned.block_page


def _paged_scatter_impl(pool, rows, g, sl, page, lay, batch_ax):
    if sl is not None:
        rows = jnp.take(rows, sl, axis=batch_ax)
    L = rows.shape[0]
    nc = g.shape[0] // rows.shape[1]
    if lay == "bhsd":
        n, hkv, sp, hd = rows.shape[1:]
        r = jnp.pad(rows, [(0, 0)] * 3 + [(0, nc * page - sp), (0, 0)])
        r = r.reshape(L, n, hkv, nc, page, hd).transpose(0, 1, 3, 2, 4, 5)
        r = r.reshape(L, n * nc, hkv, page, hd)
    else:
        if rows.ndim == 4:               # lane-dense prefill rows
            rows = rows.reshape(rows.shape[:3] + pool.shape[-2:])
        n, sp, hkv, hd = rows.shape[1:]
        r = jnp.pad(rows, [(0, 0)] * 2 + [(0, nc * page - sp),
                                          (0, 0), (0, 0)])
        r = r.reshape(L, n * nc, page, hkv, hd)
    return pool.at[:, g].set(r.astype(pool.dtype))


_paged_scatter_jit = jax.jit(_paged_scatter_impl,
                             static_argnums=(4, 5, 6))


def _paged_scatter(pool, rows, gids, page, lay, *, rows_sel=None):
    """Scatter per-slot prefill KV into pool pages. ``pool`` is a stacked
    (L, N, page, Hkv, hd) ("bshd") / (L, N, Hkv, page, hd) ("bhsd") pool;
    ``rows`` the admitted rows of the prefill cache, (L, n, sp, Hkv*hd) or
    (L, n, sp, Hkv, hd) / (L, n, Hkv, sp, hd); ``gids`` (n, ceil(sp/page))
    GLOBAL page positions (the sharded pool's global axis order is
    partition-major, matching the allocator's gid layout). A partial last
    page is zero-padded — those positions sit beyond every reader's
    ``cache_len`` until decode overwrites them. Jitted (shape-keyed) so
    an admission pays one dispatch, not one per pad/reshape/scatter op.
    ``rows_sel=(sl, axis)`` folds the admitted-row gather of the full
    prefill cache into the same program instead of an eager
    advanced-index on the host path."""
    g = jnp.asarray(np.asarray(gids).reshape(-1), jnp.int32)
    if rows_sel is None:
        return _paged_scatter_jit(pool, rows, g, None, page, lay, 0)
    sl, batch_ax = rows_sel
    return _paged_scatter_jit(pool, rows, g, jnp.asarray(sl), page, lay,
                              int(batch_ax))


def _paged_gather_hist_impl(pool, g, page, lay):
    b, hp = g.shape
    got = pool[:, g.reshape(-1)]
    L = got.shape[0]
    if lay == "bhsd":                       # (L, B*hP, Hkv, page, hd)
        hkv, hd = got.shape[2], got.shape[4]
        got = got.reshape(L, b, hp, hkv, page, hd)
        got = got.transpose(0, 1, 2, 4, 3, 5).reshape(L, b, hp * page,
                                                      hkv, hd)
    else:                                   # (L, B*hP, page, Hkv, hd)
        got = got.reshape(L, b, hp * page, *got.shape[3:])
    return got


_paged_gather_jit = jax.jit(_paged_gather_hist_impl,
                            static_argnums=(2, 3))


# One dispatch for an admission's table-row + position writes.
_admit_rows_jit = jax.jit(
    lambda tab, pos, sl, rows, pl: (tab.at[sl].set(rows),
                                    pos.at[sl].set(pl)))


def _paged_integrity(state, live):
    """Shared paged-pool invariant sweep: allocator self-check (free-list
    conservation), freed slots hold no pages and have all-zero table
    rows, and every page's refcount exactly equals its holders (slot
    tables + prefix-cache entries) — conservation with no orphans. Host
    work over host mirrors plus one table readback; runs only at
    fault-recovery events and in tests."""
    state.alloc.check()
    holders: dict = {}
    for j, pages in enumerate(state.slot_pages):
        if j not in live and pages:
            raise AssertionError(
                f"freed slot {j} still holds {len(pages)} pages")
        for gid in pages:
            holders[int(gid)] = holders.get(int(gid), 0) + 1
    pcache = getattr(state, "pcache", None)
    if pcache is not None:
        for gid, _, _ in pcache._entries.values():
            holders[int(gid)] = holders.get(int(gid), 0) + 1
    for gid in range(state.n_pages):
        if gid % state.alloc.per_part == 0:
            continue                      # scratch pages are never held
        refs = state.alloc.refcount(gid)
        held = holders.get(gid, 0)
        if refs != held:
            raise AssertionError(
                f"page {gid}: refcount {refs} != {held} holders")
    if state.tables is not None:
        tab = np.asarray(state.tables)
        for j in range(state.pool_width):
            if j not in live and tab[j].any():
                raise AssertionError(
                    f"freed slot {j} has a nonzero table row")


def _paged_gather_hist(pool, gids, page, lay):
    """Gather prefix pages into a contiguous (L, B, h, Hkv, hd) history
    (always "bshd" — the ``hist`` contract of ``transformer.prefill``).
    Rows without a history point at the scratch page; their gathered
    content is arbitrary and their outputs are ignored. Jitted for the
    same hot-admission dispatch reason as ``_paged_scatter``."""
    g = jnp.asarray(np.asarray(gids), jnp.int32)
    return _paged_gather_jit(pool, g, page, lay)


class PagedKVDecodeState(KVDecodeState):
    """Transformer families over a paged pool: fixed-size KV pages behind
    per-slot block tables, a host-side refcounted allocator, and a
    shared-prefix page cache.

    The tentpole invariants:

      * full reservation — a slot's whole table (ceil(cache_s/page)
        columns, minus its prefix-cache hits) is allocated at admission,
        so the decode hot loop NEVER touches the allocator or the tables:
        zero host work, zero host syncs, no preemption.
      * oversubscription comes from sharing, not from overcommit — N
        slots on a shared prefix of P pages store P + N*suffix physical
        pages against N*(P+suffix) logical tokens.
      * no shared page is ever written — decode writes only at positions
        >= the slot's prompt length, which lie strictly past every full
        (hashable, shareable) prompt page; ``BlockAllocator.cow`` remains
        the defensive discipline for any future in-page writer.
    """

    kind = "paged-kv"
    is_paged = True

    def __init__(self, cfg, params, policy, pool_width, cache_s, *,
                 mesh=None, kv_axis=None, n_pages=None, page=None,
                 prefix_cache=True):
        from .block_pool import BlockAllocator, PrefixCache
        self.page = int(page or tune_block_page(cfg, policy, pool_width,
                                                cache_s))
        self.ns = -(-cache_s // self.page)          # table columns per slot
        nsh = 1 if kv_axis is None else mesh.shape[kv_axis]
        if kv_axis is not None and self.ns % nsh:
            raise ValueError(
                f"table width {self.ns} not divisible by {nsh} shards")
        if n_pages is None:
            n_pages = nsh + pool_width * self.ns    # scratch + full pool
        if n_pages % nsh:
            raise ValueError(f"page budget {n_pages} not divisible by "
                             f"{nsh} shards")
        self.n_pages = int(n_pages)
        self.alloc = BlockAllocator(
            self.n_pages, n_partitions=nsh,
            cols_per_part=None if nsh == 1 else self.ns // nsh)
        self.use_prefix = bool(prefix_cache) and cfg.sliding_window is None
        self.pcache = PrefixCache(self.alloc, self.page) \
            if self.use_prefix else None
        self.slot_pages = [[] for _ in range(pool_width)]
        self.tables = None                          # device (B, nS) int32
        self._chunk_hit = {}       # slot -> prefix-hit depth (pages)
        super().__init__(cfg, params, policy, pool_width, cache_s,
                         mesh=mesh, kv_axis=kv_axis)
        (self._hist_prefill, self._decode_paged,
         self._chunk_paged) = _paged_programs(
            cfg, policy, self.page, mesh, kv_axis, self._decode_policy)

    # ------------------------------------------------------------ plumbing

    def _autotune_warmup(self):
        # the contiguous decode-attention tune is meaningless here and
        # the page size was already resolved before pool construction
        self._decode_policy = self.policy
        return self.policy

    def _placed_tables(self, arr):
        if self.kv_axis is None:
            return jnp.asarray(arr, jnp.int32)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(jnp.asarray(arr, jnp.int32),
                              NamedSharding(self.mesh, P(None,
                                                         self.kv_axis)))

    def _setup_placement(self):
        if self.kv_axis is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._repl = NamedSharding(self.mesh, P())
        self._state_shard = {
            "k": NamedSharding(self.mesh, P(None, self.kv_axis)),
            "v": NamedSharding(self.mesh, P(None, self.kv_axis))}

    def _ensure_pool(self):
        if self.data is None:
            self.data = self._place_state(api.init_paged_cache(
                self.cfg, self.pool_width, self.n_pages, self.page))
            self.tables = self._placed_tables(
                np.zeros((self.pool_width, self.ns), np.int32))

    def _local_ids(self, gids):
        """Device-table values for global page ids (partition-local on a
        sharded pool: each shard indexes its own pool slice)."""
        g = np.asarray(gids, np.int64)
        return (g % self.alloc.per_part).astype(np.int32)

    # ------------------------------------------------------------- budget

    def pages_per_slot(self) -> int:
        return self.ns

    def free_with_evictable(self):
        """Per-partition page budget: free pages plus prefix-cache pages
        held only by the cache (refcount 1) — live state is never
        evicted, so those are genuinely reclaimable under pressure."""
        free = self.alloc.free_counts()
        if self.pcache is not None:
            ev = np.zeros_like(free)
            for gid, _, _ in self.pcache._entries.values():
                if self.alloc.refcount(gid) == 1:
                    ev[self.alloc.part_of(gid)] += 1
            free = free + ev
        return free

    def admission_need(self, prompt, *, cap_h=None):
        """(per-partition fresh-page counts, hit depth) for admitting one
        request. The hit depth is this prompt's own prefix-cache depth
        (capped at ``cap_h``, the wave's shared depth); fresh pages are
        the reserved columns ``[h, ns)`` mapped to their partitions."""
        h = 0
        if self.pcache is not None:
            p = np.asarray(prompt).reshape(-1)
            h = min(self.pcache.probe(p), (len(p) - 1) // self.page)
        if cap_h is not None:
            h = min(h, cap_h)
        need = np.zeros(self.alloc.n_partitions, np.int64)
        for c in range(h, self.ns):
            need[self.alloc.part_of_col(c)] += 1
        return need, h

    def can_admit(self, n_slots: int) -> bool:
        """Whether ``n_slots`` full (cold) reservations fit."""
        per_part = (self.ns if self.alloc.n_partitions == 1
                    else self.ns // self.alloc.n_partitions)
        return bool((self.free_with_evictable() >= n_slots * per_part).all())

    def admission_pin(self, prompt, h, reserved):
        """Evictable supply this request's admission will consume beyond
        its fresh-page need: per-partition counts (and gids) of its first
        ``h`` hit pages that are cache-only (refcount 1) and not already
        in ``reserved`` (pages pinned earlier in the same wave).
        ``free_with_evictable`` counts those pages as reclaimable while
        ``admission_need`` counts them as hits needing no fresh page —
        but attach raises their refcount, so the admission gate must
        debit them or it double-counts the supply and a later row's
        allocation can run out of pages mid-prefill."""
        pin = np.zeros(self.alloc.n_partitions, np.int64)
        gids = []
        if self.pcache is None or not h:
            return pin, gids
        p = np.asarray(prompt).reshape(-1)
        for gid in self.pcache.hit_gids(p, max_pages=h):
            if gid not in reserved and self.alloc.refcount(gid) == 1:
                pin[self.alloc.part_of(gid)] += 1
                gids.append(gid)
        return pin, gids

    def pool_stats(self) -> dict:
        s = {"page": self.page, "pages_total": self.n_pages,
             "pages_allocatable": self.n_pages - self.alloc.n_partitions,
             "pages_used": self.alloc.n_used(),
             "pages_free": self.alloc.n_free()}
        s["utilization"] = s["pages_used"] / max(s["pages_allocatable"], 1)
        if self.pcache is not None:
            s["prefix"] = self.pcache.stats()
        return s

    # ------------------------------------------------------- engine ops

    def prefill_into(self, slots, toks, plens, *, full, uniform=False):
        self._ensure_pool()
        self._maybe_inject_admission_fault()
        slots = list(np.asarray(slots).reshape(-1))
        toks_np = np.asarray(toks)
        plens_np = np.asarray(plens).reshape(-1)
        page, ns = self.page, self.ns

        # ---- prefix probe: the wave's shared history depth is the MIN
        # over its rows (one uniform hist shape per prefill program);
        # a cold row in the wave degrades it to a cold admission.
        h_pages = 0
        if self.pcache is not None and slots:
            h_pages = ns
            for j in slots:
                n_hit = self.pcache.probe(toks_np[j, :plens_np[j]])
                # a hit must leave >= 1 suffix token (the prefill needs a
                # real position to emit the first logits from)
                n_hit = min(n_hit, (int(plens_np[j]) - 1) // page)
                h_pages = min(h_pages, n_hit)

        # ---- attach the shared prefix FIRST, for every row, before any
        # fresh-page allocation: attach pins the hit pages (refcount++),
        # so an eviction triggered by a later row's alloc_cols can no
        # longer free a chain another row probed. If a probed page
        # vanished anyway (evicted in the probe->attach window), degrade
        # the wave to the depth every row actually holds — never crash.
        held_pref = {j: [] for j in slots}
        if h_pages:
            try:
                for j in slots:
                    held_pref[j] = self.pcache.attach(
                        toks_np[j, :plens_np[j]], max_pages=h_pages)
            except BaseException:
                # release every row already attached: a wave must hold
                # all of its references or none of them
                for gids in held_pref.values():
                    for gid in gids:
                        self.alloc.decref(int(gid))
                raise
            got = min(len(held_pref[j]) for j in slots)
            if got < h_pages:
                for j in slots:
                    for gid in held_pref[j][got:]:
                        self.alloc.decref(int(gid))
                    held_pref[j] = held_pref[j][:got]
                h_pages = got
        h = h_pages * page

        # ---- reserve the rest of each slot's table up front (full
        # reservation). All-or-nothing for the whole wave: on OutOfBlocks
        # every page the wave holds (attached and fresh) is released, so
        # the engine can re-queue the wave with no pages leaked.
        from .block_pool import OutOfBlocks
        new_tab = {}
        try:
            for j in slots:
                new_tab[j] = held_pref[j] + self.alloc.alloc_cols(
                    range(h_pages, ns))
        except OutOfBlocks:
            for j in slots:
                for gid in new_tab.get(j, held_pref[j]):
                    self.alloc.decref(int(gid))
            raise
        for j in slots:
            self.slot_pages[j] = new_tab[j]

        # ---- prefill (cold: full prompts; hot: suffix against the
        # gathered history) + page scatter of the computed KV
        lay = self.cfg.kv_cache_layout
        sl = jnp.asarray(np.asarray(slots))
        if h_pages == 0:
            if uniform:
                first, pref = self._prefill_plain(self.params,
                                                  jnp.asarray(toks))
            else:
                first, pref = self._prefill(self.params, jnp.asarray(toks),
                                            jnp.asarray(plens))
            sp = toks.shape[1]
            col0 = 0
        else:
            hist_tab = np.zeros((self.pool_width, h_pages), np.int64)
            for j in slots:
                hist_tab[j] = new_tab[j][:h_pages]
            hist = {kname: _paged_gather_hist(self.data[kname], hist_tab,
                                              page, lay)
                    for kname in ("k", "v")}
            sp = _len_bucket(int((plens_np - h).max()), self.cache_s - h)
            toks_suf = np.ones((self.pool_width, sp), toks_np.dtype)
            plens_suf = np.ones((self.pool_width,), plens_np.dtype)
            for j in slots:
                n_suf = int(plens_np[j]) - h
                toks_suf[j, :n_suf] = toks_np[j, h:h + n_suf]
                plens_suf[j] = n_suf
            first, pref = self._hist_prefill(
                self.params, jnp.asarray(toks_suf), jnp.asarray(plens_suf),
                hist)
            col0 = h_pages
        first = self.place_tokens(first)

        nc = -(-sp // page)
        gids = np.zeros((len(slots), nc), np.int64)
        for i, j in enumerate(slots):
            gids[i] = new_tab[j][col0:col0 + nc]
        for kname in ("k", "v"):
            ax = self.axes[kname]
            self.data[kname] = _paged_scatter(
                self.data[kname], pref[kname], gids, page, lay,
                rows_sel=(sl, ax.batch))

        # ---- publish full prompt pages to the prefix cache (the cache
        # takes its own refs, so shared prefixes outlive their slot)
        if self.pcache is not None:
            for j in slots:
                prompt = toks_np[j, :plens_np[j]]
                for c in range(h_pages, int(plens_np[j]) // page):
                    self.pcache.insert(prompt, c, self.slot_pages[j][c])

        # ---- table rows + positions (one fused device update)
        tab_rows = np.zeros((len(slots), ns), np.int32)
        for i, j in enumerate(slots):
            tab_rows[i] = self._local_ids(new_tab[j])
        self.tables, self.pos_dev = _admit_rows_jit(
            self.tables, self.pos_dev, sl, jnp.asarray(tab_rows),
            jnp.asarray(plens_np[np.asarray(slots)], jnp.int32))
        return first

    @hot_path
    def step(self, last, live):
        nxt, self.data, self.pos_dev = self._decode_paged(
            self.params_decode, last, self.data, self.tables, self.pos_dev,
            live)
        return nxt

    # ------------------------------------------------- speculative decoding

    def supports_speculative(self) -> bool:
        # same preconditions as per-slot chunk admission: the verify
        # chunk writes through the device tables (unsharded, linear)
        return self.supports_chunked()

    def _spec_mode(self) -> str:
        return "kv_paged"

    def _wire_spec(self):
        self._draft_decode_paged = _paged_programs(
            self.cfg, self.policy, self.page, self.mesh, self.kv_axis,
            self._draft_policy())[1]
        self._verify = _spec_programs(self.cfg, self.policy,
                                      self._spec_k + 1, self._spec_mode(),
                                      self.max_len(), page=self.page,
                                      impl=self._spec_impl())

    @hot_path
    def draft_step(self, last, live):
        nxt, self.data, self.pos_dev = self._draft_decode_paged(
            self.params_decode, last, self.data, self.tables, self.pos_dev,
            live)
        return nxt

    @hot_path
    def verify_step(self, toks, snap, rem, live):
        # tables are read-only and rollback never frees a page (full
        # reservation holds every column, accepted prefix included)
        pos0, _ = snap
        block, nlast, self.data, self.pos_dev, rem = self._verify(
            self.params_decode, toks, self.data, self.tables, pos0, rem,
            live)
        return block, nlast, rem

    # ------------------------------------------------- chunked prefill

    def supports_chunked(self) -> bool:
        # per-slot chunk admission writes through the device tables, so
        # it needs global == partition-local page ids (unsharded pools)
        # and a linear, non-wrapping table (no sliding window). Sharded
        # and windowed paged pools admit monolithically.
        return self.kv_axis is None and self.cfg.sliding_window is None

    def begin_chunk(self, slot, prompt, plen) -> int:
        """Reserve the slot's whole table up front (the same full-
        reservation invariant as monolithic admission) and attach this
        prompt's own prefix-cache hits — per-request, not the wave-min
        depth of batched admission, so a chunked request's hit depth is
        independent of who it was admitted with. The cursor starts past
        the attached pages; shared pages are never written by chunks
        (only full pages are shared, and writes begin at the cursor)."""
        self._ensure_pool()
        self._maybe_inject_admission_fault()
        from .block_pool import OutOfBlocks
        j, plen = int(slot), int(plen)
        prompt = np.asarray(prompt).reshape(-1)[:plen]
        page, ns = self.page, self.ns
        h_pages, held = 0, []
        if self.pcache is not None:
            # a hit must leave >= 1 suffix token to emit logits from
            h_pages = min(self.pcache.probe(prompt), (plen - 1) // page)
            if h_pages:
                held = self.pcache.attach(prompt, max_pages=h_pages)
                h_pages = len(held)
        try:
            tab = held + self.alloc.alloc_cols(range(h_pages, ns))
        except OutOfBlocks:
            for gid in held:
                self.alloc.decref(int(gid))
            raise
        self.slot_pages[j] = tab
        self._chunk_hit[j] = h_pages
        self.tables = self.tables.at[j].set(
            jnp.asarray(self._local_ids(tab), jnp.int32))
        self.pos_dev = self.pos_dev.at[j].set(plen)
        return h_pages * page

    def finish_chunk(self, slot, prompt, plen):
        # publish the prompt's full pages (past the attached hits) so
        # later requests share them — the cache takes its own refs
        j, plen = int(slot), int(plen)
        h0 = self._chunk_hit.pop(j, 0)
        if self.pcache is None:
            return
        prompt = np.asarray(prompt).reshape(-1)[:plen]
        for c in range(h0, plen // self.page):
            self.pcache.insert(prompt, c, self.slot_pages[j][c])

    @hot_path
    def prefill_chunk_into(self, toks, offs, clens):
        self._ensure_pool()
        first, self.data = self._chunk_paged(
            self.params, jnp.asarray(toks), self.data, self.tables,
            jnp.asarray(offs, jnp.int32), jnp.asarray(clens, jnp.int32))
        return first

    def reset_slots(self, slots):
        sl = jnp.asarray(np.asarray(slots))
        self.pos_dev = self.pos_dev.at[sl].set(0)
        for j in np.asarray(slots).reshape(-1):
            for gid in self.slot_pages[int(j)]:
                self.alloc.decref(int(gid))
            self.slot_pages[int(j)] = []
            self._chunk_hit.pop(int(j), None)
        if self.tables is not None:
            self.tables = self.tables.at[sl].set(0)

    # ----------------------------------------- fault tolerance / lifecycle

    def set_injector(self, inj):
        super().set_injector(inj)
        self.alloc.injector = inj        # alloc.out_of_blocks fires there

    def poison_slot(self, slot) -> bool:
        # NaN only the slot's PRIVATE pages (refcount 1): shared /
        # published prefix pages back other requests' histories, and the
        # fault model is "this slot's state went bad", not "the cache
        # lied to everyone". A fully-shared slot (aligned prompt, all
        # pages published) has no private page yet — report False so the
        # chaos driver picks another victim.
        if self.data is None:
            return False
        gids = [int(g) for g in self.slot_pages[int(slot)]
                if self.alloc.refcount(int(g)) == 1]
        if not gids:
            return False
        ids = jnp.asarray(self._local_ids(gids), jnp.int32)
        for kname in ("k", "v"):
            self.data[kname] = self.data[kname].at[:, ids].set(jnp.nan)
        return True

    def corrupt_prefix(self, injector) -> int:
        if self.pcache is None or not self.pcache._entries:
            return 0
        n = max(1, len(self.pcache._entries) // 2)
        return self.pcache.invalidate(n=n, rng=injector.rng)

    def scrub_slot(self, slot):
        # zero the slot's PRIVATE pages in the pool BEFORE the reset
        # returns them to the free list: a NaN page reallocated to a
        # later request sits past its cache_len but still flows through
        # additively-masked attention scores. Shared/published pages are
        # never poisoned (poison_slot skips them) and never written.
        j = int(slot)
        gids = [int(g) for g in self.slot_pages[j]
                if self.alloc.refcount(int(g)) == 1]
        if gids and self.data is not None:
            ids = jnp.asarray(self._local_ids(gids), jnp.int32)
            for kname in ("k", "v"):
                self.data[kname] = self.data[kname].at[:, ids].set(0)
        self.reset_slots([j])

    def recover(self):
        # the donated carry (pool + tables' target) is gone; every page
        # the slots hold AND every cached prefix page points into it —
        # release them all, then drop the pool itself
        for j in range(self.pool_width):
            for gid in self.slot_pages[j]:
                self.alloc.decref(int(gid))
            self.slot_pages[j] = []
        self._chunk_hit.clear()
        if self.pcache is not None:
            self.pcache.drop_all()
        self.tables = None
        super().recover()

    def set_policy(self, policy):
        dpol = super().set_policy(policy)
        self._decode_policy = dpol
        (self._hist_prefill, self._decode_paged,
         self._chunk_paged) = _paged_programs(
            self.cfg, policy, self.page, self.mesh, self.kv_axis, dpol)
        return dpol

    def check_integrity(self, live_slots=()):
        super().check_integrity(live_slots)
        _paged_integrity(self, {int(j) for j in live_slots})


class PagedHybridDecodeState(HybridDecodeState):
    """Hybrid family over a paged pool: the O(1) recurrent leaves keep
    their slot rows (generic scatter/zero), the ring-buffer KV leaves
    live in slotless page pools behind a fixed per-slot ring table of
    ceil(window/page) pages — allocated whole at admission, freed whole
    at finish. No prefix cache: a ring's page content depends on the
    slot's wrap phase, so pages are never content-addressable."""

    kind = "paged-hybrid"
    is_paged = True

    def __init__(self, cfg, params, policy, pool_width, cache_s, *,
                 mesh=None, kv_axis=None, n_pages=None, page=None,
                 prefix_cache=True):
        from .block_pool import BlockAllocator
        if kv_axis is not None:
            raise ValueError("paged hybrid state is single-partition")
        self.page = int(page or policy.block_page)
        self.ns = -(-cache_s // self.page)
        if n_pages is None:
            n_pages = 1 + pool_width * self.ns
        self.n_pages = int(n_pages)
        self.alloc = BlockAllocator(self.n_pages)
        self.pcache = None
        self.use_prefix = False
        self.slot_pages = [[] for _ in range(pool_width)]
        self.tables = None
        super().__init__(cfg, params, policy, pool_width, cache_s,
                         mesh=mesh, kv_axis=kv_axis)
        (_, self._decode_paged,
         self._chunk_paged) = _paged_programs(cfg, policy, self.page,
                                              None, None, policy)

    def can_admit(self, n_slots: int) -> bool:
        return self.alloc.n_free() >= n_slots * self.ns

    def free_with_evictable(self):
        return self.alloc.free_counts()

    def admission_need(self, prompt, *, cap_h=None):
        return np.array([self.ns], np.int64), 0

    def admission_pin(self, prompt, h, reserved):
        return np.zeros(1, np.int64), []    # no prefix cache: nothing pins

    def pages_per_slot(self) -> int:
        return self.ns

    def pool_stats(self) -> dict:
        s = {"page": self.page, "pages_total": self.n_pages,
             "pages_allocatable": self.n_pages - 1,
             "pages_used": self.alloc.n_used(),
             "pages_free": self.alloc.n_free()}
        s["utilization"] = s["pages_used"] / max(s["pages_allocatable"], 1)
        return s

    def _ensure_pool(self):
        if self.data is None:
            self.data = api.init_paged_cache(self.cfg, self.pool_width,
                                             self.n_pages, self.page)
            self.tables = jnp.zeros((self.pool_width, self.ns), jnp.int32)

    def prefill_into(self, slots, toks, plens, *, full, uniform=False):
        self._ensure_pool()
        self._maybe_inject_admission_fault()
        slots = list(np.asarray(slots).reshape(-1))
        plens_np = np.asarray(plens).reshape(-1)
        if uniform:
            first, pref = self._prefill_plain(self.params,
                                              jnp.asarray(toks))
        else:
            first, pref = self._prefill(self.params, jnp.asarray(toks),
                                        jnp.asarray(plens))
        sp = toks.shape[1]
        sl = jnp.asarray(np.asarray(slots))
        gids = np.zeros((len(slots), -(-sp // self.page)), np.int64)
        tab_rows = np.zeros((len(slots), self.ns), np.int32)
        # all-or-nothing for the wave: a mid-wave OutOfBlocks releases the
        # earlier rows' rings so the engine can re-queue without a leak
        from .block_pool import OutOfBlocks
        try:
            for i, j in enumerate(slots):
                held = self.alloc.alloc_cols(range(self.ns))
                self.slot_pages[j] = held
                tab_rows[i] = held
                gids[i] = held[:gids.shape[1]]
        except OutOfBlocks:
            for j in slots:
                for gid in self.slot_pages[j]:
                    self.alloc.decref(int(gid))
                self.slot_pages[j] = []
            raise
        self.tables = self.tables.at[sl].set(jnp.asarray(tab_rows))

        def place(pool, leaf, ax):
            if ax.seq is None:           # recurrent leaf: slot-row scatter
                rows_idx = [slice(None)] * leaf.ndim
                rows_idx[ax.batch] = sl
                idx = [slice(None)] * pool.ndim
                idx[ax.batch] = sl
                return pool.at[tuple(idx)].set(leaf[tuple(rows_idx)])
            return _paged_scatter(pool, leaf, gids, self.page, "bshd",
                                  rows_sel=(sl, ax.batch))

        self.data = jax.tree.map(place, self.data, pref, self.axes)
        self.pos_dev = self.pos_dev.at[sl].set(
            jnp.asarray(plens_np[np.asarray(slots)], jnp.int32))
        return first

    @hot_path
    def step(self, last, live):
        nxt, self.data, self.pos_dev = self._decode_paged(
            self.params_decode, last, self.data, self.tables, self.pos_dev,
            live)
        return nxt

    # ------------------------------------------------- speculative decoding

    def supports_speculative(self) -> bool:
        # both ring regimes (see HybridDecodeState): the verify scans
        # wrap natively and the snapshot copies the ring pools too.
        # Single-partition by construction.
        return True

    def _spec_mode(self) -> str:
        return "recurrent_paged"

    def _wire_spec(self):
        self._draft_decode_paged = _paged_programs(
            self.cfg, self.policy, self.page, None, None,
            self._draft_policy())[1]
        self._verify = _spec_programs(self.cfg, self.policy,
                                      self._spec_k + 1, self._spec_mode(),
                                      self.max_len(), page=self.page)

    @hot_path
    def draft_step(self, last, live):
        nxt, self.data, self.pos_dev = self._draft_decode_paged(
            self.params_decode, last, self.data, self.tables, self.pos_dev,
            live)
        return nxt

    @hot_path
    def verify_step(self, toks, snap, rem, live):
        # the snapshot copy carries BOTH the RG-LRU rows and the ring
        # page pools; the two-pass verify rebuilds the exact post-accept
        # state from it. Tables read-only, zero allocator work.
        pos0, state0 = snap
        block, nlast, self.data, self.pos_dev, rem = self._verify(
            self.params_decode, toks, state0, self.tables, pos0, rem,
            live)
        return block, nlast, rem

    # ------------------------------------------------- chunked prefill

    def begin_chunk(self, slot, prompt, plen) -> int:
        # allocate the slot's whole ring up front, exactly like
        # monolithic admission; prompts fit the window so prefill
        # positions never wrap the ring table
        self._ensure_pool()
        self._maybe_inject_admission_fault()
        j = int(slot)
        held = self.alloc.alloc_cols(range(self.ns))
        self.slot_pages[j] = held
        self.tables = self.tables.at[j].set(
            jnp.asarray(np.asarray(held), jnp.int32))
        self.pos_dev = self.pos_dev.at[j].set(int(plen))
        return 0

    @hot_path
    def prefill_chunk_into(self, toks, offs, clens):
        self._ensure_pool()
        first, self.data = self._chunk_paged(
            self.params, jnp.asarray(toks), self.data, self.tables,
            jnp.asarray(offs, jnp.int32), jnp.asarray(clens, jnp.int32))
        return first

    def reset_slots(self, slots):
        super().reset_slots(slots)       # positions + recurrent leaf rows
        sl = jnp.asarray(np.asarray(slots))
        for j in np.asarray(slots).reshape(-1):
            for gid in self.slot_pages[int(j)]:
                self.alloc.decref(int(gid))
            self.slot_pages[int(j)] = []
        if self.tables is not None:
            self.tables = self.tables.at[sl].set(0)

    # ----------------------------------------- fault tolerance / lifecycle

    def set_injector(self, inj):
        super().set_injector(inj)
        self.alloc.injector = inj

    def poison_slot(self, slot) -> bool:
        # NaN only the recurrent snapshots: the paged KV leaves are
        # slotless pools whose batch axis the contiguous nanify would
        # mis-index. The RG-LRU state is read unconditionally every step,
        # so recurrent NaNs alone are guaranteed to reach the logits.
        if self.data is None:
            return False
        j = int(slot)

        def nanify(leaf, ax):
            if ax.seq is not None or \
                    not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            idx = [slice(None)] * leaf.ndim
            idx[ax.batch] = j
            return leaf.at[tuple(idx)].set(jnp.nan)

        self.data = jax.tree.map(nanify, self.data, self.axes)
        return True

    def recover(self):
        for j in range(self.pool_width):
            for gid in self.slot_pages[j]:
                self.alloc.decref(int(gid))
            self.slot_pages[j] = []
        self.tables = None
        super().recover()

    def scrub_slot(self, slot):
        # recurrent rows zero through the generic scrub; the slot's ring
        # pages are zeroed in the slotless pools before they return to
        # the free list (same NaN-reallocation hazard as paged KV)
        j = int(slot)
        gids = [int(g) for g in self.slot_pages[j]]
        if gids and self.data is not None:
            ids = jnp.asarray(np.asarray(gids), jnp.int32)

            def zero(leaf, ax):
                if not jnp.issubdtype(leaf.dtype, jnp.floating):
                    return leaf
                if ax.seq is None:
                    idx = [slice(None)] * leaf.ndim
                    idx[ax.batch] = j
                    return leaf.at[tuple(idx)].set(0)
                return leaf.at[:, ids].set(0)

            self.data = jax.tree.map(zero, self.data, self.axes)
        self.reset_slots([j])

    def set_policy(self, policy):
        dpol = super().set_policy(policy)
        (_, self._decode_paged,
         self._chunk_paged) = _paged_programs(self.cfg, policy, self.page,
                                              None, None, dpol)
        return dpol

    def check_integrity(self, live_slots=()):
        super().check_integrity(live_slots)
        _paged_integrity(self, {int(j) for j in live_slots})


def decode_state_for(cfg, paged=False):
    """The DecodeState implementation serving ``cfg`` (the one family
    dispatch of the serving stack). ``paged`` selects the block-pool
    states; recurrent state is O(1) per slot — nothing to page — so ssm
    serves through the contiguous state either way."""
    if cfg.family == "ssm":
        return RecurrentDecodeState
    if cfg.family == "hybrid":
        return PagedHybridDecodeState if paged else HybridDecodeState
    if cfg.family == "audio":
        raise ValueError("encoder-only arch has no decode state to serve")
    return PagedKVDecodeState if paged else KVDecodeState
