"""repro.analysis Layer-2 tests: the serving programs the engine
actually builds hold their lowered-program contracts —

* unsharded decode programs are collective-free, fully consume their
  donated carry, and keep the (state, positions) carry pytree stable
  (dtype/shape) across the step — for the KV, recurrent and hybrid
  families and the paged variants, under all three exp backends;
* the sharded decode program spends exactly ONE all_gather per layer
  (subprocess, 8 host devices);
* the planted fixtures (dtype-drifting carry, two-collective step,
  dropped donation) are each caught by the corresponding audit.

Audits run on *lowered* programs and ``eval_shape`` — no XLA
compilation, so the full family x backend matrix stays cheap.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import api
from repro.models.decode_state import _paged_programs, _programs
from repro.runtime import resolve_policy
from repro.analysis import jaxpr_audit as ja

pytestmark = pytest.mark.analysis

EXP_BACKENDS = ("exact", "vexp", "vexp_hw")
FAMILY_ARCH = {"kv": "gpt2-small", "recurrent": "mamba2-1.3b",
               "hybrid": "recurrentgemma-9b"}
FIX = Path(__file__).parent / "fixtures" / "analysis"

_cfg_cache, _params_cache = {}, {}


def _cfg(arch):
    if arch not in _cfg_cache:
        _cfg_cache[arch] = get_config(arch).reduced()
    return _cfg_cache[arch]


def _params(arch):
    if arch not in _params_cache:
        _params_cache[arch] = api.init_params(_cfg(arch),
                                              jax.random.PRNGKey(0))
    return _params_cache[arch]


def _load_fixture(name):
    spec = importlib.util.spec_from_file_location(name,
                                                  FIX / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _decode_args(arch, b=2, s=64):
    cfg = _cfg(arch)
    cache = api.init_cache(cfg, b, s)
    tok = jnp.zeros((b, 1), jnp.int32)
    pos = jnp.ones((b,), jnp.int32)
    live = jnp.ones((b,), jnp.int32)
    return (_params(arch), tok, cache, pos, live)


# ----------------------------------------------- engine programs (unsharded)

class TestEngineDecodePrograms:
    @pytest.mark.parametrize("exp", EXP_BACKENDS)
    @pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
    def test_decode_is_collective_free_donated_and_carry_stable(
            self, family, exp):
        """One parametrization per (family, exp backend): the decode
        program the slot engine runs must be collective-free, alias
        every donated (state, positions) leaf, and return its carry
        with identical treedef/dtypes/shapes."""
        arch = FAMILY_ARCH[family]
        cfg = _cfg(arch)
        pol = resolve_policy(cfg, env={}, exp_backend=exp)
        _, _, decode, _ = _programs(cfg, pol)
        args = _decode_args(arch)
        lowered = decode.lower(*args)
        txt = lowered.as_text()

        ja.assert_collective_budget(txt, {})           # zero collectives
        n_carry = len(jax.tree_util.tree_leaves(args[2])) + 1
        ja.assert_all_donated(lowered, n_carry)        # cache + positions
        ja.assert_carry_stable(decode, args, {2: 1, 3: 2})

    @pytest.mark.parametrize("exp", EXP_BACKENDS)
    def test_paged_decode_program(self, exp):
        """Paged KV decode: collective-free, carry-stable for the pool,
        tables and positions; positions always donate (the pool donates
        everywhere but XLA-CPU, where the page scatter materializes the
        pool regardless — mirrored here exactly as the builder does)."""
        arch = FAMILY_ARCH["kv"]
        cfg = _cfg(arch)
        b, s, page = 2, 64, 16
        ns = -(-s // page)
        pool = api.init_paged_cache(cfg, b, 1 + b * ns, page)
        tab = jnp.zeros((b, ns), jnp.int32)
        args = (_params(arch), jnp.zeros((b, 1), jnp.int32), pool, tab,
                jnp.ones((b,), jnp.int32), jnp.ones((b,), jnp.int32))

        pol = resolve_policy(cfg, env={}, exp_backend=exp)
        _, decode, _ = _paged_programs(cfg, pol, page)
        lowered = decode.lower(*args)
        txt = lowered.as_text()

        ja.assert_collective_budget(txt, {})
        pool_leaves = len(jax.tree_util.tree_leaves(pool))
        donated = (1 if jax.default_backend() == "cpu"
                   else pool_leaves + 1)
        ja.assert_all_donated(lowered, donated)
        # carry stability is unconditional — pool AND positions
        ja.assert_carry_stable(decode, args, {2: 1, 4: 2})

    @pytest.mark.parametrize("exp", EXP_BACKENDS)
    @pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
    def test_chunk_prefill_program(self, family, exp):
        """The resumable chunk-prefill program (PR-8) is held to the
        decode-step contracts: collective-free, fully donates its cache
        carry, and returns the pool pytree structurally unchanged —
        rows with ``clens == 0`` ride along bit-untouched, which starts
        with the carry coming back identical in treedef/shape/dtype."""
        arch = FAMILY_ARCH[family]
        cfg = _cfg(arch)
        pol = resolve_policy(cfg, env={}, exp_backend=exp)
        _, _, _, chunk = _programs(cfg, pol)
        b, c = 2, 8
        s = cfg.sliding_window or 64    # hybrid pool = its window
        cache = api.init_cache(cfg, b, s)
        args = (_params(arch), jnp.zeros((b, c), jnp.int32), cache,
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32))
        lowered = chunk.lower(*args)
        txt = lowered.as_text()

        ja.assert_collective_budget(txt, {})
        ja.assert_all_donated(lowered,
                              len(jax.tree_util.tree_leaves(cache)))
        ja.assert_carry_stable(chunk, args, {2: 1})

    @pytest.mark.parametrize("family", ("kv", "hybrid"))
    def test_paged_chunk_prefill_program(self, family):
        """Paged chunk prefill: collective-free and pool-carry-stable;
        donation mirrors the paged decode builder (the pool donates
        everywhere but XLA-CPU, where the page scatter materializes the
        pool regardless)."""
        arch = FAMILY_ARCH[family]
        cfg = _cfg(arch)
        b, page = 2, 8
        s = cfg.sliding_window or 64
        ns = -(-s // page)
        pool = api.init_paged_cache(cfg, b, 1 + b * ns, page)
        tab = jnp.zeros((b, ns), jnp.int32)
        pol = resolve_policy(cfg, env={})
        _, _, chunk = _paged_programs(cfg, pol, page)
        args = (_params(arch), jnp.zeros((b, 8), jnp.int32), pool, tab,
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32))
        lowered = chunk.lower(*args)
        txt = lowered.as_text()

        ja.assert_collective_budget(txt, {})
        donated = (0 if jax.default_backend() == "cpu"
                   else len(jax.tree_util.tree_leaves(pool)))
        ja.assert_all_donated(lowered, donated)
        ja.assert_carry_stable(chunk, args, {2: 1})

    W = 4                               # spec_k = 3 draft lanes + bonus

    def _spec_args(self, arch, b=2, s=64):
        cache = api.init_cache(_cfg(arch), b, s)
        toks = jnp.zeros((b, self.W), jnp.int32)
        pos0 = jnp.ones((b,), jnp.int32)
        rem = jnp.full((b,), 8, jnp.int32)
        live = jnp.ones((b,), jnp.int32)
        return (_params(arch), toks, cache, pos0, rem, live)

    @pytest.mark.parametrize("impl", ["scan", "chunk"])
    @pytest.mark.parametrize("exp", EXP_BACKENDS)
    def test_spec_verify_kv_program(self, exp, impl):
        """The speculative verify program (PR-10) is held to the decode
        contracts under both impls: collective-free, fully consumes the
        donated (cache, positions, remaining-budget) carry, and keeps
        it dtype/shape-stable — acceptance folds into the carry, so any
        drift here would defeat donation for EVERY burst."""
        from repro.models.decode_state import _spec_programs
        arch = FAMILY_ARCH["kv"]
        cfg = _cfg(arch)
        pol = resolve_policy(cfg, env={}, exp_backend=exp)
        verify = _spec_programs(cfg, pol, self.W, "kv", 64, impl=impl)
        args = self._spec_args(arch)
        lowered = verify.lower(*args)
        txt = lowered.as_text()

        ja.assert_collective_budget(txt, {})
        n = len(jax.tree_util.tree_leaves(args[2])) + 2
        ja.assert_all_donated(lowered, n)       # cache + pos + rem
        # verify returns (block, nlast, cache, pos, rem)
        ja.assert_carry_stable(verify, args, {2: 2, 3: 3, 4: 4})

    @pytest.mark.parametrize("family", ["recurrent", "hybrid"])
    def test_spec_verify_recurrent_program(self, family):
        """Recurrent/hybrid verify (two-scan: score + replay from the
        snapshot): collective-free; the snapshot c0 is deliberately NOT
        donated (the replay reads it twice) but positions and budget
        are; the replayed state must come back carry-stable."""
        from repro.models.decode_state import _spec_programs
        arch = FAMILY_ARCH[family]
        cfg = _cfg(arch)
        pol = resolve_policy(cfg, env={}, exp_backend="exact")
        cap = None if family == "recurrent" else 64
        verify = _spec_programs(cfg, pol, self.W, "recurrent", cap)
        args = self._spec_args(arch)
        lowered = verify.lower(*args)
        txt = lowered.as_text()

        ja.assert_collective_budget(txt, {})
        ja.assert_all_donated(lowered, 2)       # pos + rem only
        ja.assert_carry_stable(verify, args, {2: 2, 3: 3, 4: 4})

    @pytest.mark.parametrize("impl", ["scan", "chunk"])
    def test_spec_verify_paged_program(self, impl):
        """Paged verify: donation mirrors the paged decode builder (the
        pool donates everywhere but XLA-CPU); pool, tables, positions
        and budget all come back carry-stable, and the program never
        touches the allocator — it is pure device code."""
        from repro.models.decode_state import _spec_programs
        arch = FAMILY_ARCH["kv"]
        cfg = _cfg(arch)
        b, s, page = 2, 64, 16
        ns = -(-s // page)
        pool = api.init_paged_cache(cfg, b, 1 + b * ns, page)
        tab = jnp.zeros((b, ns), jnp.int32)
        pol = resolve_policy(cfg, env={}, exp_backend="exact")
        verify = _spec_programs(cfg, pol, self.W, "kv_paged", s,
                                page=page, impl=impl)
        args = (_params(arch), jnp.zeros((b, self.W), jnp.int32), pool,
                tab, jnp.ones((b,), jnp.int32),
                jnp.full((b,), 8, jnp.int32), jnp.ones((b,), jnp.int32))
        lowered = verify.lower(*args)
        txt = lowered.as_text()

        ja.assert_collective_budget(txt, {})
        donated = (2 if jax.default_backend() == "cpu"
                   else len(jax.tree_util.tree_leaves(pool)) + 2)
        ja.assert_all_donated(lowered, donated)
        ja.assert_carry_stable(verify, args, {2: 2, 4: 3, 5: 4})

    def test_paged_hybrid_decode_program(self):
        """The hybrid family through the paged program builder (its KV
        periods page; recurrent periods carry their snapshots)."""
        arch = FAMILY_ARCH["hybrid"]
        cfg = _cfg(arch)
        b, s, page = 2, 64, 16
        ns = -(-s // page)
        pool = api.init_paged_cache(cfg, b, 1 + b * ns, page)
        tab = jnp.zeros((b, ns), jnp.int32)
        args = (_params(arch), jnp.zeros((b, 1), jnp.int32), pool, tab,
                jnp.ones((b,), jnp.int32), jnp.ones((b,), jnp.int32))
        pol = resolve_policy(cfg, env={})
        _, decode, _ = _paged_programs(cfg, pol, page)
        ja.assert_collective_budget(decode.lower(*args).as_text(), {})
        ja.assert_carry_stable(decode, args, {2: 1, 4: 2})


# ------------------------------------------------------- sharded (8 devices)

@pytest.mark.slow
def test_sharded_decode_one_collective_per_layer_and_donation():
    """The PR-4 budget through the audit API: the engine's seq-sharded
    decode program spends exactly one all_gather (layers are scanned, so
    the loop body lowers once) and nothing else, and every donated
    carry leaf is aliased."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_AUTOTUNE_CACHE"] = "off"
        import sys
        sys.path.insert(0, {src!r})
        import json
        import numpy as np
        import jax
        from repro.configs import get_config
        from repro.models import api
        from repro.launch.serve import Server, Request
        from repro.launch.mesh import make_host_mesh
        from repro.runtime import resolve_policy
        from repro.analysis import jaxpr_audit as ja

        cfg = get_config("gpt2-small").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        pol = resolve_policy(cfg, env={{}}, kernel_backend="pallas")
        srv = Server(cfg, params, max_batch=2, max_seq=64,
                     mesh=make_host_mesh(1, 8), policy=pol, kv_mode="seq")
        rng = np.random.default_rng(0)
        srv.submit(Request(0, rng.integers(0, cfg.vocab, (5,),
                                           dtype=np.int32), 4))
        g = srv._groups["default"]
        g.admit()
        st = g.state
        args = (st.params_decode, g.last, st.data, st.pos_dev, g.live_dev)
        lowered = st._decode.lower(*args)
        txt = lowered.as_text()
        counts = ja.collective_counts(txt)
        ja.assert_collective_budget(txt, {{"all_gather": 1}})
        rep = ja.donation_report(
            lowered, len(jax.tree_util.tree_leaves(st.data)) + 1)
        stable = ja.carry_report(st._decode, args, {{2: 1, 3: 2}})
        print(json.dumps({{"counts": counts,
                           "donated": rep.fully_consumed,
                           "carry_msgs": stable}}))
    """).format(src=src)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["counts"] == {"all_gather": 1}
    assert res["donated"]
    assert res["carry_msgs"] == []


@pytest.mark.slow
def test_sharded_chunk_prefill_outputs_carry_pool_sharding():
    """PR-8 re-placement contract (subprocess, 8 host devices): the
    sharded chunk-prefill program's cache output carries exactly the
    pool sharding (``serve_cache_sharding``), so chunked admission
    writes prefill rows into the sharded pool IN PLACE — the engine
    performs no post-prefill ``device_put`` of cache rows. Also pins
    carry stability (sharding included: ``carry_report`` compares
    shardings on live arrays) and sanity-checks the audit itself
    rejects a deliberately wrong expectation."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_AUTOTUNE_CACHE"] = "off"
        import sys
        sys.path.insert(0, {src!r})
        import json
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import api
        from repro.launch.serve import Server, Request
        from repro.launch.mesh import make_host_mesh
        from repro.runtime import resolve_policy
        from repro.distributed.sharding import serve_cache_sharding
        from repro.analysis import jaxpr_audit as ja

        cfg = get_config("gpt2-small").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        pol = resolve_policy(cfg, env={{}}, kernel_backend="pallas",
                             prefill_chunk=8)
        srv = Server(cfg, params, max_batch=2, max_seq=64,
                     mesh=make_host_mesh(1, 8), policy=pol, kv_mode="seq")
        assert srv.kv_axis is not None
        rng = np.random.default_rng(0)
        out = srv.run([Request(i, rng.integers(0, cfg.vocab, (p,),
                                               dtype=np.int32), 4)
                       for i, p in enumerate((21, 5))])
        g = srv._groups["default"]
        st = g.state
        want = serve_cache_sharding(cfg, srv.mesh, srv.kv_axis)
        args = (st.params_decode, jnp.zeros((2, 8), jnp.int32), st.data,
                jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32))
        msgs = ja.output_sharding_report(st._chunk, 1, want, *args)
        ja.assert_output_sharding(st._chunk, 1, want, *args)
        # the audit must actually discriminate: a wrong expectation
        # (head-axis sharding instead of the pool's seq axis) fails
        wrong = {{k: NamedSharding(srv.mesh,
                                   P(None, None, None, "model", None))
                  for k in want}}
        bad = ja.output_sharding_report(st._chunk, 1, wrong, *args)
        # the live pool ended chunked serving under the pool sharding
        # (produced in place by the chunk program, never re-placed)
        pool_in_place = all(
            st.data[k].sharding.is_equivalent_to(want[k], st.data[k].ndim)
            for k in ("k", "v"))
        print(json.dumps({{
            "chunks": len(g.chunk_s),
            "served": sorted(len(r.out) for r in out),
            "msgs": msgs, "bad_nonempty": bool(bad),
            "pool_in_place": pool_in_place}}))
    """).format(src=src)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["chunks"] >= 3          # prompts streamed across ticks
    assert res["served"] == [4, 4]
    assert res["msgs"] == []
    assert res["bad_nonempty"]
    assert res["pool_in_place"]


# --------------------------------------------------------- planted fixtures

class TestPlantedProgramViolations:
    def _carry_args(self):
        params = {"w": jnp.ones((4,), jnp.float32)}
        state = {"h": jnp.zeros((2, 4), jnp.float32),
                 "conv": jnp.zeros((2, 3), jnp.float32)}
        return (params, jnp.zeros((2, 1), jnp.int32), state,
                jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32))

    def test_dtype_drifting_carry_caught(self):
        bad = _load_fixture("bad_carry")
        args = self._carry_args()
        msgs = ja.carry_report(bad.drifting_step, args, {2: 1, 3: 2})
        assert any("dtype" in m and "bfloat16" in m for m in msgs)
        with pytest.raises(ja.CarryStabilityError, match="dtype"):
            ja.assert_carry_stable(bad.drifting_step, args, {2: 1, 3: 2})

    def test_shape_drifting_carry_caught(self):
        bad = _load_fixture("bad_carry")
        with pytest.raises(ja.CarryStabilityError, match="shape"):
            ja.assert_carry_stable(bad.shape_drifting_step,
                                   self._carry_args(), {2: 1, 3: 2})

    def test_clean_fixture_carry_is_stable(self):
        clean = _load_fixture("clean")
        args = self._carry_args()
        assert ja.carry_report(clean.stable_step, args, {2: 1, 3: 2}) == []

    def test_two_collective_program_caught(self):
        """shard_map on a 1-device mesh still lowers real collective ops,
        so the budget check needs no multi-device subprocess."""
        bad = _load_fixture("bad_collectives")
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
        x = jnp.arange(8, dtype=jnp.float32)
        two = bad.build_two_collective_step(mesh)
        assert ja.collective_counts(two, x) == {"all_reduce": 2}
        with pytest.raises(ja.CollectiveBudgetError):
            ja.assert_collective_budget(two, {"all_reduce": 1}, x)
        one = bad.build_one_collective_step(mesh)
        ja.assert_collective_budget(one, {"all_reduce": 1}, x)

    @pytest.mark.filterwarnings("ignore::UserWarning")
    def test_dropped_donation_caught(self):
        """The PR-5 failure mode in miniature: the output dtype no longer
        matches the donated input aval, so the donation silently drops —
        and the audit fails it."""
        def drift(s):
            return s.astype(jnp.bfloat16) * 2
        f = jax.jit(drift, donate_argnums=(0,))
        s = jnp.zeros((8,), jnp.float32)
        rep = ja.donation_report(f, (0,), s)
        assert rep.donated_leaves == 1 and rep.aliased_params == 0
        with pytest.raises(ja.DonationError):
            ja.assert_all_donated(f, (0,), s)

    def test_consumed_donation_passes(self):
        f = jax.jit(lambda s: s * 2, donate_argnums=(0,))
        ja.assert_all_donated(f, (0,), jnp.zeros((8,), jnp.float32))
