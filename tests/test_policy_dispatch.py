"""Execution-policy layer tests: resolution precedence, dispatch table,
autotune caching, and cross-backend numerical consistency.

The accuracy tests pin the paper's envelope: all three exp backends must
produce softmax rows within ~0.78% max relative error of the exact
transcendental (Table IV's bound, plus BF16 input quantization for the
hardware model).
"""

import glob
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.runtime import ExecPolicy, resolve_policy, ENV_PREFIX
from repro.kernels import dispatch as kd
from repro.configs import get_config


class TestPolicyResolution:
    def test_defaults(self):
        p = resolve_policy(env={})
        assert p.exp_backend == "vexp"
        assert p.kernel_backend == "pallas"

    def test_config_fields_flow_in(self):
        cfg = get_config("gpt2-small")
        p = resolve_policy(cfg, env={})
        assert p.exp_backend == cfg.exp_impl
        # attention_impl "flash" maps to the reference backend
        assert p.kernel_backend == "reference"
        assert p.block_k == cfg.attn_block_k

    def test_env_overrides_config(self):
        cfg = get_config("gpt2-small")
        env = {ENV_PREFIX + "EXP_BACKEND": "exact",
               ENV_PREFIX + "KERNEL_BACKEND": "xla",
               ENV_PREFIX + "BLOCK_Q": "256",
               ENV_PREFIX + "AUTOTUNE": "1"}
        p = resolve_policy(cfg, env=env)
        assert p.exp_backend == "exact"
        assert p.kernel_backend == "xla"
        assert p.block_q == 256
        assert p.autotune is True

    def test_call_overrides_beat_env(self):
        env = {ENV_PREFIX + "EXP_BACKEND": "exact"}
        p = resolve_policy(env=env, exp_backend="vexp_hw")
        assert p.exp_backend == "vexp_hw"

    def test_process_env_is_read(self, monkeypatch):
        monkeypatch.setenv(ENV_PREFIX + "EXP_BACKEND", "vexp_hw")
        assert resolve_policy().exp_backend == "vexp_hw"

    def test_invalid_values_raise(self):
        with pytest.raises(ValueError):
            ExecPolicy(exp_backend="fast_but_wrong")
        with pytest.raises(ValueError):
            ExecPolicy(kernel_backend="cuda")
        with pytest.raises(ValueError):
            ExecPolicy(block_q=0)
        with pytest.raises(ValueError):
            resolve_policy(env={ENV_PREFIX + "BLOCK_K": "huge"})
        with pytest.raises(ValueError):
            resolve_policy(not_a_field=1)

    def test_merge_strategy_field(self):
        """ISSUE 4: the collective merge strategy is a first-class policy
        field — defaulted to the packed single-collective form, settable
        from the environment, validated, and part of the hash/jit key."""
        assert ExecPolicy().merge_strategy == "packed"
        p = resolve_policy(env={ENV_PREFIX + "MERGE_STRATEGY": "split"})
        assert p.merge_strategy == "split"
        with pytest.raises(ValueError):
            ExecPolicy(merge_strategy="psum_of_vibes")
        assert ExecPolicy() != ExecPolicy(merge_strategy="split")
        assert "merge=packed" in ExecPolicy().describe()

    def test_sharded_autotune_candidates_cover_both_strategies(self):
        cands = kd.CANDIDATES["decode_attention_sharded"]
        assert {c["merge_strategy"] for c in cands} == {"packed", "split"}

    def test_hashable_static_arg(self):
        # policies must be usable as static jit args (jit caches per policy)
        a = ExecPolicy(exp_backend="vexp")
        b = ExecPolicy(exp_backend="vexp")
        assert hash(a) == hash(b) and a == b
        assert a != a.replace(exp_backend="exact")

    def test_config_projection_roundtrip(self):
        cfg = get_config("gpt2-small")
        p = ExecPolicy(exp_backend="vexp_hw", kernel_backend="pallas",
                       block_q=64, block_k=64)
        cfg2 = cfg.with_policy(p)
        assert cfg2.exp_impl == "vexp_hw"
        assert cfg2.attention_impl == "pallas"
        # resolving the projected config reproduces the policy fields
        p2 = resolve_policy(cfg2, env={})
        assert p2.exp_backend == p.exp_backend
        assert p2.kernel_backend == p.kernel_backend


class TestDispatch:
    def test_table_covers_all_ops_and_backends(self):
        for op in kd.OPS:
            for kb in ("pallas", "reference", "xla"):
                fn = kd.dispatch(op, ExecPolicy(kernel_backend=kb))
                assert callable(fn), (op, kb)

    def test_unknown_op_raises(self):
        with pytest.raises(ValueError):
            kd.dispatch("conv3d", ExecPolicy())

    def test_exp_callable_resolution(self):
        """The recurrent-gate exp resolution: policy.exp_backend wins,
        the legacy exp_impl string is the fallback — so --policy-groups
        flips RG-LRU / SSD gate numerics like softmax numerics."""
        from repro.core.vexp import EXP_FNS
        for exp in ("exact", "vexp", "vexp_hw"):
            pol = ExecPolicy(exp_backend=exp)
            assert kd.exp_callable(pol) is EXP_FNS[exp]
            # policy beats the legacy string
            assert kd.exp_callable(pol, "exact") is EXP_FNS[exp]
        assert kd.exp_callable(None, "vexp_hw") is EXP_FNS["vexp_hw"]
        with pytest.raises(ValueError):
            kd.exp_callable(None, "nope")

    def test_no_hardcoded_exp_in_kernels(self):
        """Acceptance guard: no kernel body may pin vexp_f32 — the exp
        backend must arrive via the policy/registry."""
        root = os.path.join(os.path.dirname(__file__), "..", "src",
                            "repro", "kernels")
        for path in glob.glob(os.path.join(root, "*", "kernel.py")):
            src = open(path).read()
            assert "vexp_f32" not in src, f"hardcoded exp in {path}"

    def test_softmax_backends_agree_within_envelope(self):
        """exact vs vexp vs vexp_hw softmax rows within the paper's ~0.78%
        max-relative-error envelope (relative to the row max probability,
        which is how exp error propagates through the normalization)."""
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 256)) * 4
        from repro.core.softmax import softmax
        outs = {}
        for exp in ("exact", "vexp", "vexp_hw"):
            pol = ExecPolicy(exp_backend=exp, kernel_backend="pallas")
            outs[exp] = np.asarray(softmax(x, policy=pol), np.float64)
            np.testing.assert_allclose(outs[exp].sum(-1), 1.0, atol=1e-3)
        ref = outs["exact"]
        rowmax = ref.max(-1, keepdims=True)
        for exp in ("vexp", "vexp_hw"):
            rel = np.abs(outs[exp] - ref) / rowmax
            assert rel.max() < 0.0078 * 2, \
                f"{exp}: rel err {rel.max():.4f} beyond envelope"

    def test_kernel_backends_agree_per_exp(self):
        """For a fixed exp backend, all three kernel backends compute the
        same function (same math, different execution)."""
        x = jax.random.normal(jax.random.PRNGKey(1), (32, 384)) * 6
        from repro.core.softmax import softmax
        for exp in ("exact", "vexp", "vexp_hw"):
            outs = [np.asarray(softmax(
                x, policy=ExecPolicy(exp_backend=exp, kernel_backend=kb)))
                for kb in ("pallas", "reference", "xla")]
            np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)
            np.testing.assert_allclose(outs[0], outs[2], atol=1e-6)

    def test_flash_attention_policy_switch(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 64))
        k = jax.random.normal(ks[1], (1, 128, 2, 64))
        v = jax.random.normal(ks[2], (1, 128, 2, 64))
        from repro.kernels.flash_attention.ref import attention_exact_ref
        ref = np.asarray(attention_exact_ref(q, k, v, causal=True))
        for exp in ("exact", "vexp", "vexp_hw"):
            pol = ExecPolicy(exp_backend=exp, kernel_backend="pallas",
                             block_q=64, block_k=64)
            out = kd.dispatch("flash_attention", pol)(
                q, k, v, causal=True, policy=pol)
            np.testing.assert_allclose(np.asarray(out), ref, atol=6e-3)

    def test_decode_attention_policy(self):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (2, 1, 4, 64))
        kc = jax.random.normal(ks[1], (2, 2, 128, 64))
        vc = jax.random.normal(ks[2], (2, 2, 128, 64))
        from repro.core.attention import decode_attention
        ref = np.asarray(decode_attention(q, kc, vc, 100, exp_impl="vexp",
                                          layout="bhsd"))
        pol = ExecPolicy(exp_backend="vexp", kernel_backend="pallas",
                         block_s=64)
        out = kd.dispatch("decode_attention", pol)(
            q, kc, vc, 100, layout="bhsd", policy=pol)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3)


class TestAutotune:
    def test_repeated_shape_hits_cache(self):
        kd.autotune_cache_clear()
        x = jax.random.normal(jax.random.PRNGKey(4), (64, 256))
        pol = ExecPolicy(kernel_backend="pallas", autotune=True)
        sm = kd.dispatch("softmax", pol)
        sm(x, policy=pol)
        stats = kd.autotune_cache_stats()
        assert stats["misses"] == 1
        sm(x, policy=pol)
        stats = kd.autotune_cache_stats()
        assert stats["misses"] == 1, "repeated shape re-timed"
        assert stats["hits"] == 1

    def test_shape_buckets(self):
        kd.autotune_cache_clear()
        pol = ExecPolicy(kernel_backend="pallas", autotune=True)
        sm = kd.dispatch("softmax", pol)
        # 200 and 250 rows bucket to the same pow2 (256): one miss total
        sm(jax.random.normal(jax.random.PRNGKey(5), (200, 256)), policy=pol)
        sm(jax.random.normal(jax.random.PRNGKey(6), (250, 256)), policy=pol)
        assert kd.autotune_cache_stats()["misses"] == 1
        # 300 rows buckets to 512: a new miss
        sm(jax.random.normal(jax.random.PRNGKey(7), (300, 256)), policy=pol)
        assert kd.autotune_cache_stats()["misses"] == 2

    def test_no_timing_under_jit_trace(self):
        """Inside an outer jit trace wall-clock timing is meaningless
        (tracers, not device work): the tuner must not time or pollute
        the cache, only reuse an eagerly-tuned winner if one exists."""
        kd.autotune_cache_clear()
        pol = ExecPolicy(kernel_backend="pallas", autotune=True)
        sm = kd.dispatch("softmax", pol)
        x = jax.random.normal(jax.random.PRNGKey(9), (64, 256))
        traced = jax.jit(lambda x: sm(x, policy=pol))(x)
        assert kd.autotune_cache_stats()["misses"] == 0
        # eager tune, then the jitted path picks up the cached winner
        sm(x, policy=pol)
        assert kd.autotune_cache_stats()["misses"] == 1
        jax.jit(lambda x: sm(x + 1.0, policy=pol))(x)
        stats = kd.autotune_cache_stats()
        assert stats["misses"] == 1 and stats["hits"] >= 1
        np.testing.assert_allclose(
            np.asarray(traced),
            np.asarray(sm(x, policy=pol.replace(autotune=False))),
            atol=1e-6)

    def test_autotuned_result_matches_untuned(self):
        kd.autotune_cache_clear()
        x = jax.random.normal(jax.random.PRNGKey(8), (96, 256)) * 3
        base = ExecPolicy(kernel_backend="pallas")
        tuned = base.replace(autotune=True)
        sm = kd.dispatch("softmax", base)
        np.testing.assert_allclose(
            np.asarray(sm(x, policy=tuned)),
            np.asarray(sm(x, policy=base)), atol=1e-6)


    def test_all_candidates_failing_raises(self, tmp_path, monkeypatch):
        """A tuner whose every candidate raises must raise too, and must
        neither memoize nor persist an empty winner."""
        path = str(tmp_path / "autotune.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
        kd.autotune_cache_clear()
        pol = ExecPolicy(kernel_backend="pallas", autotune=True)

        def broken(p):
            raise ValueError(f"block_s={p.block_s} does not lower")

        x = jnp.zeros((2, 1, 4, 64), jnp.float32)
        with pytest.raises(RuntimeError, match="every candidate failed"):
            kd.autotune_policy("decode_attention", pol, broken, x)
        assert kd.autotune_cache_stats()["entries"] == 0
        assert not os.path.exists(path)


class TestCompileCache:
    """JAX's persistent compilation cache: placeable from outside through
    JAX_COMPILATION_CACHE_DIR, otherwise one fixed directory inside the
    checkout."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_honours_env(self, tmp_path, monkeypatch):
        from repro.runtime import use_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_inside_checkout(self, monkeypatch):
        from repro.runtime import use_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first, second = use_compile_cache(), use_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == second == os.path.join(root, ".jax_compile_cache")
        assert jax.config.jax_compilation_cache_dir == first


class TestAutotunePersistence:
    """The block-size cache persists to disk keyed by (device_kind, op,
    shape_bucket, policy): a fresh process (simulated by clearing the
    in-memory cache) must reuse the winners without re-timing."""

    def test_save_load_roundtrip_skips_retiming(self, tmp_path, monkeypatch):
        path = str(tmp_path / "autotune.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
        kd.autotune_cache_clear()
        x = jax.random.normal(jax.random.PRNGKey(21), (64, 256))
        pol = ExecPolicy(kernel_backend="pallas", autotune=True)
        sm = kd.dispatch("softmax", pol)
        sm(x, policy=pol)
        assert kd.autotune_cache_stats()["misses"] == 1
        assert os.path.exists(path), "tuning winner was not persisted"
        # "restart": drop all in-process state; the disk entry must turn
        # the first lookup into a hit instead of a timing pass.
        kd.autotune_cache_clear()
        sm(x, policy=pol)
        stats = kd.autotune_cache_stats()
        assert stats["misses"] == 0, "disk-cached shape was re-timed"
        assert stats["hits"] == 1
        assert stats["disk_loaded"] >= 1

    def test_disabled_by_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "off")
        kd.autotune_cache_clear()
        assert kd.autotune_cache_path() is None
        x = jax.random.normal(jax.random.PRNGKey(22), (64, 256))
        pol = ExecPolicy(kernel_backend="pallas", autotune=True)
        kd.dispatch("softmax", pol)(x, policy=pol)
        kd.autotune_cache_clear()
        kd.dispatch("softmax", pol)(x, policy=pol)
        assert kd.autotune_cache_stats()["misses"] == 1, \
            "persistence leaked through REPRO_AUTOTUNE_CACHE=off"

    def test_corrupt_cache_file_ignored(self, tmp_path, monkeypatch):
        path = str(tmp_path / "autotune.json")
        with open(path, "w") as fh:
            fh.write("{not json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
        kd.autotune_cache_clear()
        assert kd.load_autotune_cache() == 0

    def test_concurrent_save_merges_not_clobbers(self, tmp_path,
                                                 monkeypatch):
        """Two serve processes racing the JSON: a save must fold in the
        entries a concurrent process persisted after our last read —
        last-writer-wins would silently drop the other engine's winners —
        and our own timing of the same key must take precedence."""
        import json
        path = str(tmp_path / "autotune.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
        kd.autotune_cache_clear()
        kd._AUTOTUNE_CACHE["ours"] = {"block_s": 256}
        kd._AUTOTUNE_CACHE["shared"] = {"block_s": 512}
        # "process B" wrote between our load and our save
        with open(path, "w") as fh:
            json.dump({"version": 1,
                       "entries": {"theirs": {"block_rows": 128},
                                   "shared": {"block_s": 1024}}}, fh)
        assert kd.save_autotune_cache() == path
        with open(path) as fh:
            entries = json.load(fh)["entries"]
        assert entries["ours"] == {"block_s": 256}
        assert entries["theirs"] == {"block_rows": 128}   # merged, not lost
        assert entries["shared"] == {"block_s": 512}      # in-process wins
        assert not [f for f in os.listdir(str(tmp_path))
                    if f.startswith(".autotune-")], "tmp file leaked"
        kd.autotune_cache_clear()

    def test_save_is_atomic_rename(self, tmp_path, monkeypatch):
        """A reader must never observe a torn file: the write lands via a
        same-directory tempfile + os.replace (asserted on the source — a
        behavioural check would need fault injection)."""
        import inspect
        src = inspect.getsource(kd.save_autotune_cache)
        assert "mkstemp" in src and "os.replace" in src
        # and a corrupt concurrent file must not break saving
        path = str(tmp_path / "autotune.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
        kd.autotune_cache_clear()
        kd._AUTOTUNE_CACHE["k"] = {"block_s": 256}
        with open(path, "w") as fh:
            fh.write("{torn write from a dying process")
        assert kd.save_autotune_cache() == path
        import json
        with open(path) as fh:
            assert json.load(fh)["entries"] == {"k": {"block_s": 256}}
        kd.autotune_cache_clear()


class TestAccumDtype:
    """accum_dtype is honored by the Pallas kernels (scratch statistics)
    and rejected wherever no kernel would honor it."""

    def test_rejected_on_non_pallas_backends(self):
        for kb in ("reference", "xla"):
            with pytest.raises(ValueError, match="accum_dtype"):
                ExecPolicy(kernel_backend=kb, accum_dtype="bfloat16")
        with pytest.raises(ValueError, match="accum_dtype"):
            resolve_policy(env={}, kernel_backend="xla",
                           accum_dtype="bfloat16")

    def test_unknown_value_rejected(self):
        with pytest.raises(ValueError, match="accum_dtype"):
            ExecPolicy(accum_dtype="float16")

    def test_flash_attention_bf16_accum_distinct_but_close(self):
        ks = jax.random.split(jax.random.PRNGKey(23), 3)
        q = jax.random.normal(ks[0], (1, 64, 4, 32))
        k = jax.random.normal(ks[1], (1, 64, 2, 32))
        v = jax.random.normal(ks[2], (1, 64, 2, 32))
        from repro.kernels.flash_attention.ops import flash_attention_policy
        f32 = flash_attention_policy(
            q, k, v, causal=True,
            policy=ExecPolicy(kernel_backend="pallas", block_q=32,
                              block_k=32))
        bf16 = flash_attention_policy(
            q, k, v, causal=True,
            policy=ExecPolicy(kernel_backend="pallas", block_q=32,
                              block_k=32, accum_dtype="bfloat16"))
        assert not np.array_equal(np.asarray(f32), np.asarray(bf16)), \
            "accum_dtype=bfloat16 compiled an identical program"
        np.testing.assert_allclose(np.asarray(bf16), np.asarray(f32),
                                   atol=5e-2, rtol=5e-2)


class TestEndToEnd:
    def test_model_forward_policy_flip(self):
        """One ExecPolicy switch flips the exp backend through the whole
        model: forward logits differ between exact and vexp policies but
        stay close (the envelope), and each policy is deterministic."""
        from repro.models import api
        cfg = get_config("gpt2-small").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        losses = {}
        for exp in ("exact", "vexp", "vexp_hw"):
            pol = resolve_policy(cfg, env={}, exp_backend=exp)
            losses[exp] = float(api.loss_fn(params, cfg, batch, policy=pol))
        assert losses["exact"] != losses["vexp"]   # backend really flipped
        for exp in ("vexp", "vexp_hw"):
            assert abs(losses[exp] - losses["exact"]) < 0.05, losses

    def test_serve_runs_under_all_policies(self):
        from repro.launch.serve import Server, Request
        from repro.models import api
        cfg = get_config("gpt2-small").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        for exp in ("exact", "vexp", "vexp_hw"):
            pol = resolve_policy(cfg, env={}, exp_backend=exp,
                                 kernel_backend="pallas")
            server = Server(cfg, params, policy=pol)
            reqs = [Request(0, rng.integers(0, cfg.vocab, (8,),
                                            dtype=np.int32), max_new=2)]
            out = server.run(reqs)
            assert len(out[0].out) == 2
