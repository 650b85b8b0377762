"""Slot-level continuous-batching serving engine tests.

The headline regression: a batch mixing prompt lengths must produce
exactly the greedy tokens each request gets when served alone — the old
driver left-padded with token 0, attended the padding during prefill and
decoded every slot at the longest request's position, so any unequal-length
batch silently produced wrong tokens.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import api
from repro.launch.serve import Server, Request, _len_bucket
from repro.models.transformer import cache_seq_axis
from repro.runtime import resolve_policy, parse_policy_groups

EXP_BACKENDS = ("exact", "vexp", "vexp_hw")


@pytest.fixture(scope="module")
def cfg():
    return get_config("gpt2-small").reduced()


@pytest.fixture(scope="module")
def params(cfg):
    return api.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (n,), dtype=np.int32) for n in lens]


def _serve(cfg, params, prompts, idxs, *, max_new=6, max_batch=4,
           max_seq=64, policy=None, policy_groups=None, groups_of=None):
    srv = Server(cfg, params, max_batch=max_batch, max_seq=max_seq,
                 policy=policy, policy_groups=policy_groups)
    reqs = [Request(i, prompts[i].copy(), max_new,
                    group=(groups_of or {}).get(i, "default"))
            for i in idxs]
    srv.run(reqs)
    return {r.rid: r.out for r in reqs}, srv


# ------------------------------------------------------- headline regression

class TestMixedLengthOracle:
    @pytest.mark.parametrize("exp", EXP_BACKENDS)
    def test_unequal_batch_matches_solo(self, cfg, params, exp):
        """2-request unequal-length batch == each request served alone,
        token for token, under every exp backend."""
        pol = resolve_policy(cfg, env={}, exp_backend=exp)
        prompts = _prompts(cfg, (5, 11))
        together, _ = _serve(cfg, params, prompts, [0, 1], policy=pol)
        solo0, _ = _serve(cfg, params, prompts, [0], policy=pol)
        solo1, _ = _serve(cfg, params, prompts, [1], policy=pol)
        assert together[0] == solo0[0]
        assert together[1] == solo1[1]

    def test_uniform_full_pool_fast_path_matches_solo(self, cfg, params):
        """A full-width exact-bucket wave takes the plain-prefill + padded
        cache fast path; its tokens must equal solo serving (which runs
        the masked ragged path)."""
        prompts = _prompts(cfg, (8, 8, 8, 8))   # bucket(8) == 8, pool of 4
        together, srv = _serve(cfg, params, prompts, [0, 1, 2, 3],
                               max_batch=4)
        assert srv.admit_log == [0, 1, 2, 3]
        for i in range(4):
            solo, _ = _serve(cfg, params, prompts, [i])
            assert together[i] == solo[i], i

    def test_uniform_full_pool_pallas_matches_solo(self, cfg, params):
        """Under a pallas policy a full exact-bucket wave must not take
        the unmasked fast path (which would prefill through the real
        Pallas kernel while solo serving runs the demoted reference scan
        — a different fp accumulation order that can flip a near-tie
        argmax)."""
        pol = resolve_policy(cfg, env={}, kernel_backend="pallas")
        prompts = _prompts(cfg, (8, 8, 8, 8))
        together, _ = _serve(cfg, params, prompts, [0, 1, 2, 3],
                             max_batch=4, policy=pol)
        for i in range(4):
            solo, _ = _serve(cfg, params, prompts, [i], policy=pol)
            assert together[i] == solo[i], i

    def test_bhsd_pallas_per_slot_kernel(self, cfg, params):
        """The head-major cache + per-slot (B,) cache_len Pallas decode
        route must also match solo serving (exercises the slot-pool insert
        along the bhsd sequence axis and the vectorized-length kernel)."""
        ocfg = cfg.optimized()
        assert ocfg.kv_cache_layout == "bhsd"
        oparams = api.init_params(ocfg, jax.random.PRNGKey(0))
        pol = resolve_policy(ocfg, env={}, kernel_backend="pallas")
        prompts = _prompts(ocfg, (5, 11))
        together, _ = _serve(ocfg, oparams, prompts, [0, 1],
                             max_new=5, policy=pol)
        solo0, _ = _serve(ocfg, oparams, prompts, [0], max_new=5, policy=pol)
        solo1, _ = _serve(ocfg, oparams, prompts, [1], max_new=5, policy=pol)
        assert together[0] == solo0[0]
        assert together[1] == solo1[1]

    def test_windowed_arch_pallas_ring_buffer(self):
        """Ring-buffer windowed serving under a pallas policy: the fused
        kernel now covers windowed decode (no reference fallback), and a
        mixed-length windowed batch must still match solo serving token
        for token — including past the window roll-over."""
        wcfg = get_config("h2o-danube3-4b").reduced()
        assert wcfg.sliding_window
        wparams = api.init_params(wcfg, jax.random.PRNGKey(0))
        pol = resolve_policy(wcfg, env={}, kernel_backend="pallas")
        prompts = _prompts(wcfg, (5, 11))
        # max_new past the window (16) forces the ring-buffer wrap
        together, _ = _serve(wcfg, wparams, prompts, [0, 1],
                             max_new=10, max_seq=wcfg.sliding_window * 3,
                             policy=pol)
        solo0, _ = _serve(wcfg, wparams, prompts, [0], max_new=10,
                          max_seq=wcfg.sliding_window * 3, policy=pol)
        solo1, _ = _serve(wcfg, wparams, prompts, [1], max_new=10,
                          max_seq=wcfg.sliding_window * 3, policy=pol)
        assert together[0] == solo0[0]
        assert together[1] == solo1[1]


# --------------------------------------------------------- ragged prefill api

class TestRaggedPrefill:
    def test_prompt_len_masks_padding(self, cfg, params):
        """api.prefill with prompt_len: per-row last-real logits equal the
        solo prefill logits and pad K/V cache rows are zeroed."""
        prompts = _prompts(cfg, (5, 11))
        toks = np.zeros((2, 16), np.int32)
        toks[0, :5], toks[1, :11] = prompts[0], prompts[1]
        lb, cb = api.prefill(params, cfg, {"tokens": jnp.asarray(toks),
                                           "prompt_len": jnp.array([5, 11])})
        for i, p in enumerate(prompts):
            ls, _ = api.prefill(params, cfg, {"tokens": jnp.asarray(p[None])})
            np.testing.assert_array_equal(np.asarray(lb[i, 0]),
                                          np.asarray(ls[0, 0]))
        k = np.asarray(cb["k"], np.float32)
        assert (k[:, 0, 5:] == 0).all() and (k[:, 1, 11:] == 0).all()

    def test_prompt_len_accepted_for_recurrent_families(self):
        """Ragged prefill is family-uniform now (the DecodeState refactor):
        an ssm prompt_len batch must not raise and must return per-row
        last-real-token logits (full coverage in
        tests/test_recurrent_serving.py)."""
        mcfg = get_config("mamba2-1.3b").reduced()
        mparams = api.init_params(mcfg, jax.random.PRNGKey(0))
        logits, state = api.prefill(
            mparams, mcfg, {"tokens": jnp.zeros((2, 8), jnp.int32),
                            "prompt_len": jnp.array([4, 8])})
        assert logits.shape == (2, 1, mcfg.vocab)
        assert np.isfinite(np.asarray(logits)).all()


# --------------------------------------------------- scheduler / slot algebra

class TestScheduler:
    def test_admission_order_and_slot_reuse(self, cfg, params):
        """5 requests through 2 slots: FIFO admission, every request
        completes with exactly max_new tokens."""
        lens = (5, 9, 7, 6, 8)
        news = (2, 5, 3, 4, 1)
        prompts = _prompts(cfg, lens)
        srv = Server(cfg, params, max_batch=2, max_seq=64)
        reqs = [Request(i, prompts[i].copy(), news[i]) for i in range(5)]
        srv.run(reqs)
        assert srv.admit_log == [0, 1, 2, 3, 4]
        for r in reqs:
            assert len(r.out) == r.max_new, r.rid
            assert r.finish_reason == "max_new"
            assert r.t_done >= r.t_first >= r.t_submit > 0

    def test_finished_slots_freed_not_burned(self, cfg, params):
        """A slot whose request finishes is freed for the queue instead of
        decoding dead tokens until the batch-wide max: serving (1, 8, 1)
        max_new through 2 slots needs ~7 decode steps, not 8 * 3."""
        prompts = _prompts(cfg, (5, 7, 6))
        srv = Server(cfg, params, max_batch=2, max_seq=64)
        reqs = [Request(0, prompts[0].copy(), 1),
                Request(1, prompts[1].copy(), 8),
                Request(2, prompts[2].copy(), 1)]
        srv.run(reqs)
        assert [len(r.out) for r in reqs] == [1, 8, 1]
        # req 0 finishes at admission (token from prefill); req 2 rides in
        # the freed slot while req 1 keeps decoding.
        assert srv.stats()["default"]["decode_steps"] <= 8

    def test_decode_past_capacity_stops_slot(self, cfg, params):
        """A request that would decode past max_seq is stopped with
        finish_reason="length_cap" instead of silently overwriting the
        last cache row (the old dynamic_update_slice clamp)."""
        prompts = _prompts(cfg, (11,))
        srv = Server(cfg, params, max_batch=2, max_seq=16)
        r = Request(0, prompts[0].copy(), 50)
        srv.run([r])
        # 1 prefill token + (16 - 11) decode writes at positions 11..15
        assert len(r.out) == 6
        assert r.finish_reason == "length_cap"

    def test_submit_validation(self, cfg, params):
        srv = Server(cfg, params, max_batch=2, max_seq=16)
        with pytest.raises(ValueError):   # prompt longer than the cache
            srv.submit(Request(0, np.zeros(17, np.int32), 4))
        with pytest.raises(ValueError):   # unknown group
            srv.submit(Request(1, np.zeros(4, np.int32), 4, group="nope"))
        with pytest.raises(ValueError):   # encoder-only: no decode state
            Server(get_config("hubert-xlarge").reduced(), params)

    def test_len_bucket(self):
        assert [_len_bucket(n, 512) for n in (1, 8, 9, 100)] == \
            [8, 8, 16, 128]
        assert _len_bucket(400, 96) == 96   # capped at cache capacity


# ----------------------------------------------------------- policy groups

class TestPolicyGroups:
    def test_exact_slots_isolated_from_vexp(self, cfg, params):
        """In a mixed-policy server, the exact group's tokens equal a
        pure-exact server's tokens (a vexp slot never contaminates an
        exact slot's numerics), and vice versa."""
        prompts = _prompts(cfg, (5, 11, 7))
        groups = {"eval": resolve_policy(cfg, env={}, exp_backend="exact"),
                  "bulk": resolve_policy(cfg, env={}, exp_backend="vexp")}
        mixed, _ = _serve(cfg, params, prompts, [0, 1, 2],
                          policy_groups=groups,
                          groups_of={0: "eval", 1: "bulk", 2: "eval"})
        pure_exact, _ = _serve(cfg, params, prompts, [0, 2],
                               policy=groups["eval"])
        pure_vexp, _ = _serve(cfg, params, prompts, [1],
                              policy=groups["bulk"])
        assert mixed[0] == pure_exact[0]
        assert mixed[2] == pure_exact[2]
        assert mixed[1] == pure_vexp[1]

    def test_parse_policy_groups(self, cfg):
        g = parse_policy_groups("eval=exact,bulk=vexp_hw/xla", cfg, env={})
        assert g["eval"].exp_backend == "exact"
        assert g["bulk"].exp_backend == "vexp_hw"
        assert g["bulk"].kernel_backend == "xla"
        for bad in ("", "noequals", "x=,", "a=exact,a=vexp"):
            with pytest.raises(ValueError):
                parse_policy_groups(bad, cfg, env={})

    def test_parse_policy_groups_base_beats_cfg_and_env(self, cfg):
        """A resolved base policy already encodes config/env/CLI
        precedence; neither cfg fields nor stale env vars may shadow it
        (e.g. a CLI --kernel-backend xla must survive into every group)."""
        base = resolve_policy(cfg, env={}, kernel_backend="xla")
        g = parse_policy_groups("eval=exact", cfg, base=base)
        assert g["eval"].kernel_backend == "xla"
        assert g["eval"].exp_backend == "exact"
        g2 = parse_policy_groups("eval=exact", cfg, base=base,
                                 env={"REPRO_KERNEL_BACKEND": "reference"})
        assert g2["eval"].kernel_backend == "reference"  # explicit env wins


# ------------------------------------------------- per-slot decode kernel

class TestPerSlotDecodeKernel:
    def test_vector_cache_len_vs_reference(self):
        """The Pallas flash-decode kernel with a (B,) cache_len vector
        must match the reference reduction row for row."""
        from repro.kernels.decode_attention import (decode_attention,
                                                    decode_attention_ref)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        b, h, hkv, d, smax = 3, 8, 2, 64, 512
        q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
        kc = jax.random.normal(ks[1], (b, hkv, smax, d), jnp.float32)
        vc = jax.random.normal(ks[2], (b, hkv, smax, d), jnp.float32)
        clen = jnp.array([300, 17, 512], jnp.int32)
        out = decode_attention(q, kc, vc, clen, block_s=128, interpret=True)
        ref = decode_attention_ref(q, kc, vc, clen)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)
        # each row must equal the same row decoded alone at its own length
        for i, cl in enumerate((300, 17, 512)):
            solo = decode_attention(q[i:i + 1], kc[i:i + 1], vc[i:i + 1],
                                    cl, block_s=128, interpret=True)
            np.testing.assert_allclose(np.asarray(out[i:i + 1]),
                                       np.asarray(solo), atol=2e-3,
                                       rtol=2e-3)


# -------------------------------------------------- donated zero-copy decode

class TestDonatedDecodeStep:
    def test_cache_and_pos_buffers_donated(self, cfg, params):
        """The decode step donates the KV cache and the slot-position
        vector: the pre-step buffers must be consumed (reused in place),
        not left alive next to freshly allocated outputs."""
        srv = Server(cfg, params, max_batch=2, max_seq=64)
        prompts = _prompts(cfg, (5,))
        srv.submit(Request(0, prompts[0].copy(), 8))
        g = srv._groups["default"]
        g.admit()
        cache_before = g.state.data["k"]
        pos_before = g.state.pos_dev
        g.decode_once()
        assert cache_before.is_deleted(), "KV cache was re-allocated"
        assert pos_before.is_deleted(), "position buffer was copied"
        srv.drain()

    def test_positions_advance_device_side(self, cfg, params):
        """Slot positions live on device and advance by the liveness
        vector inside the decode program — the host mirrors (lens) must
        stay in lockstep without ever being shipped down."""
        srv = Server(cfg, params, max_batch=2, max_seq=64)
        prompts = _prompts(cfg, (5, 9))
        srv.submit(Request(0, prompts[0].copy(), 6))
        srv.submit(Request(1, prompts[1].copy(), 3))
        g = srv._groups["default"]
        g.admit()
        for _ in range(4):
            g.decode_once()
        live = [j for j in range(2) if g.reqs[j] is not None]
        pos = np.asarray(g.state.pos_dev)
        for j in range(2):
            expect = g.lens[j] if j in live else 0   # parked at finish
            assert pos[j] == expect, (j, pos, g.lens)
        srv.drain()


class TestNoSilentFailure:
    def test_real_decode_error_escapes(self, cfg, params, monkeypatch):
        """Only the chaos harness's InjectedFault is recovered in the
        decode step; any other error is a real fault and must reach the
        caller instead of being retried and shed."""
        srv = Server(cfg, params, max_batch=2, max_seq=64)
        srv.submit(Request(0, _prompts(cfg, (5,))[0], 4))
        g = srv._groups["default"]
        g.admit()

        def broken(*a, **k):
            raise RuntimeError("device lost")

        monkeypatch.setattr(g.state, "step", broken)
        with pytest.raises(RuntimeError, match="device lost"):
            g.decode_once()
        assert g.step_faults == 0 and g.shed == 0

    def test_main_exits_nonzero_when_requests_fail(self, tmp_path,
                                                   monkeypatch, capsys):
        """Prompts of three pages into a pool of one allocatable page can
        never be admitted: they are shed as "failed", and without chaos,
        cancellation or deadlines that fails the run."""
        from repro.launch.serve import main
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        argv = ["--reduced", "--requests", "2", "--prompt-len", "40",
                "--max-new", "2", "--max-seq", "64", "--paged",
                "--block-page", "16", "--block-budget", "2"]
        with pytest.raises(SystemExit) as exc:
            main(argv)
        assert exc.value.code not in (0, None)
        assert "2 of 2 requests did not finish" in str(exc.value.code)


def test_write_token_kv_oob_drop_negative_positions():
    """The sharded decode write hands every shard the same token with
    shard-local positions: anything outside [0, S) — including *negative*
    positions, which a bare mode="drop" scatter would wrap numpy-style —
    must leave the cache untouched."""
    from repro.models.transformer import _write_token_kv
    # "bshd": the lane-dense pool layer (B, S, Hkv*hd) and the 4-D form
    # the hybrid family's ring caches keep
    for layout, shape in (("bshd", (2, 5, 24)), ("bshd", (2, 5, 3, 8)),
                          ("bhsd", (2, 3, 5, 8))):
        kv_shape = (2, 1, 3, 8) if layout == "bshd" else (2, 3, 1, 8)
        cache = jnp.zeros(shape, jnp.float32)
        kv = jnp.ones(kv_shape, jnp.float32)
        # row 0 in-slice at 1; row 1 below the slice (the owner's
        # neighbour shard sees lpos in [-S, 0)) — must drop, not wrap
        out = _write_token_kv(cache, kv, jnp.array([1, -2]), layout,
                              oob_drop=True)
        s_ax = cache_seq_axis(layout, stacked=False)
        rows = np.asarray(jnp.moveaxis(out, s_ax, 1))    # (B, S, ...)
        assert (rows[0, 1] == 1).all(), layout
        assert (rows[1] == 0).all(), f"{layout}: negative pos wrapped"
        # above the slice: also dropped
        out2 = _write_token_kv(cache, kv, jnp.array([5, 7]), layout,
                               oob_drop=True)
        assert (np.asarray(out2) == 0).all(), layout


# ------------------------------------------------------- cache layout axis

def test_cache_seq_axis():
    """"bshd" stacked caches are (L, B, S, Hkv*hd) -> axis 2; "bhsd" are
    (L, B, Hkv, S, hd) -> axis 3 (the old _grow_cache hardcoded -3, which
    padded Hkv on head-major caches)."""
    assert cache_seq_axis("bshd") == 2
    assert cache_seq_axis("bhsd") == 3
    assert cache_seq_axis("bshd", stacked=False) == 1
    assert cache_seq_axis("bhsd", stacked=False) == 2
    with pytest.raises(ValueError):
        cache_seq_axis("sbhd")
    import dataclasses
    cfg = get_config("gpt2-small").reduced()
    for lay in ("bshd", "bhsd"):
        c = api.init_cache(dataclasses.replace(cfg, kv_cache_layout=lay),
                           2, 32)
        assert c["k"].shape[cache_seq_axis(lay)] == 32
        if lay == "bshd":         # heads folded into the lanes
            assert c["k"].shape == (cfg.n_layers, 2, 32,
                                    cfg.n_kv_heads * cfg.hd)
