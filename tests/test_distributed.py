"""Distribution tests.

In-process: sharding rules produce valid NamedShardings for every arch.
Sub-process (8 fake host devices, set via XLA_FLAGS before jax imports):
sharded train-step/decode numerically match single-device execution, and
the sequence-parallel (KV-sharded) decode path agrees with the replicated
one — the SPMD partial-softmax merge is exercised for real.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest
import jax

from repro.configs import REGISTRY, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh


class TestShardingRules:
    @pytest.mark.parametrize("arch", sorted(REGISTRY))
    def test_param_specs_match_structure(self, arch):
        cfg = get_config(arch)
        mesh = make_host_mesh()
        specs = shd.param_specs(cfg, mesh, fsdp=False)
        import jax.numpy as jnp
        shapes = jax.eval_shape(
            lambda: __import__("repro.models.api", fromlist=["api"])
            .init_params(cfg, jax.random.PRNGKey(0)))
        # structures must match exactly
        assert (jax.tree_util.tree_structure(specs)
                == jax.tree_util.tree_structure(
                    jax.tree.map(lambda _: 0, shapes)))
        # every spec must be applicable (rank <= leaf rank)
        flat_s = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        flat_l = jax.tree.leaves(shapes)
        for sp, leaf in zip(flat_s, flat_l):
            assert len(sp) <= leaf.ndim, f"{arch}: spec {sp} rank > {leaf.shape}"

    @pytest.mark.parametrize("arch", ["command-r-35b", "grok-1-314b"])
    def test_fsdp_augments(self, arch):
        cfg = get_config(arch)
        mesh = make_host_mesh()
        plain = shd.param_specs(cfg, mesh, fsdp=False)
        fsdp = shd.param_specs(cfg, mesh, fsdp=True)
        n_data = sum("data" in str(s) for s in jax.tree.leaves(
            fsdp, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
        n_plain = sum("data" in str(s) for s in jax.tree.leaves(
            plain, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
        assert n_data > n_plain

    def test_cache_specs_modes(self):
        cfg = get_config("phi3-medium-14b")
        mesh = make_host_mesh()   # (1,1): dp_size=1, so force modes
        seq = shd.cache_specs(cfg, mesh, 1, kv_mode="seq")
        assert "model" in str(seq["k"])
        bat = shd.cache_specs(cfg, mesh, 1024, kv_mode="batch")
        assert str(bat["k"]).count("model") == 0


@pytest.mark.slow
class TestFsdpMultiPod:
    """fsdp_augment must shard over *all* data axes: hardcoding "data"
    left the "pod" axis replicated on the multi-pod mesh — 2× the
    per-device parameter memory dp_axes implies."""

    def test_fsdp_uses_full_dp_tuple(self):
        res = _run_sub("""
        import json
        cfg = get_config("grok-1-314b")
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        specs = shd.param_specs(cfg, mesh, fsdp=True)
        flat = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        n_pod = sum("pod" in str(s) for s in flat)
        n_data = sum("data" in str(s) for s in flat)
        # every fsdp-augmented spec must name pod AND data together
        both = sum(("pod" in str(s)) == ("data" in str(s)) for s in flat)
        print(json.dumps({"n_pod": n_pod, "n_data": n_data,
                          "n": len(flat), "both": both}))
        """)
        assert res["n_pod"] > 0, "pod axis never participates in FSDP"
        assert res["n_pod"] == res["n_data"]
        assert res["both"] == res["n"]

    def test_fsdp_multipod_memory_and_numerics(self):
        """On a ("pod","data","model") mesh the fsdp-sharded parameters
        must (a) occupy 1/4 of the replicated per-device bytes for the
        augmented leaves and (b) leave a forward pass numerically
        unchanged."""
        res = _run_sub("""
        import json
        cfg = get_config("gpt2-small").reduced()
        # reduced dims are small; lower the fsdp threshold by checking
        # shardings directly on the big-enough leaves
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        loss1 = jax.jit(lambda p, b: api.loss_fn(p, cfg, b))(params, batch)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        specs = shd.param_specs(cfg, mesh, fsdp=True)
        with mesh:
            pp = jax.device_put(params, shd.named(mesh, specs))
            bb = jax.device_put(batch, NamedSharding(mesh, P(("pod",
                                                              "data"))))
            loss2 = jax.jit(lambda p, b: api.loss_fn(p, cfg, b))(pp, bb)
        # per-device fraction for leaves that picked up the dp tuple
        fracs = []
        for leaf, spec in zip(jax.tree.leaves(pp), jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P))):
            if "pod" in str(spec):
                shard = leaf.addressable_shards[0].data
                fracs.append(shard.size / leaf.size)
        print(json.dumps({"l1": float(loss1), "l2": float(loss2),
                          "n_aug": len(fracs),
                          "max_frac": max(fracs) if fracs else None}))
        """)
        assert abs(res["l1"] - res["l2"]) < 2e-2
        if res["n_aug"]:       # reduced dims may fall under the 1024 gate
            assert res["max_frac"] <= 0.25 + 1e-6


_SUBPROCESS_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import api
from repro.distributed import sharding as shd
from repro import optim

def mesh2x4():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
"""


def _run_sub(body: str) -> dict:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SUBPROCESS_PRELUDE.format(src=os.path.abspath(src)) \
        + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
class TestShardedExecution:
    def test_sharded_train_step_matches_single(self):
        res = _run_sub("""
        import json
        cfg = get_config("gpt2-small").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        opt_cfg = optim.OptConfig(total_steps=10, warmup_steps=0)
        opt = optim.init(params, opt_cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens, "labels": tokens}

        def step(p, o, b):
            loss, g = jax.value_and_grad(
                lambda p: api.loss_fn(p, cfg, b))(p)
            np_, no_, _ = optim.update(g, o, p, opt_cfg)
            return loss, np_

        loss1, p1 = jax.jit(step)(params, opt, batch)

        mesh = mesh2x4()
        ps = shd.param_specs(cfg, mesh)
        with mesh:
            pp = jax.device_put(params, shd.named(mesh, ps))
            oo = jax.device_put(opt, shd.named(mesh, shd.opt_specs(cfg, mesh, ps)))
            bb = jax.device_put(batch, NamedSharding(mesh, P("data")))
            loss2, p2 = jax.jit(step)(pp, oo, bb)
        d = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        print(json.dumps({"loss1": float(loss1), "loss2": float(loss2),
                          "max_param_delta": d}))
        """)
        assert abs(res["loss1"] - res["loss2"]) < 2e-2
        assert res["max_param_delta"] < 2e-2

    def test_seq_sharded_decode_matches_replicated(self):
        """Sequence-parallel flash-decode (KV cache sharded along S over
        'model') must equal the replicated decode — the partial-softmax
        merge as an SPMD collective."""
        res = _run_sub("""
        import json
        cfg = get_config("gpt2-small").reduced()
        b, s = 2, 32
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
        _, cache = api.prefill(params, cfg, {"tokens": toks})
        ck = api.init_cache(cfg, b, 40)["k"].at[:, :, :s].set(cache["k"])
        cv = jnp.zeros_like(ck).at[:, :, :s].set(cache["v"])
        cache = {"k": ck, "v": cv}
        tok = toks[:, -1:]
        f = lambda p, t, c, pos: api.decode_step(p, cfg, t, c, pos)
        ref, _ = jax.jit(f)(params, tok, cache, jnp.int32(s - 1))

        mesh = mesh2x4()
        with mesh:
            cs = {"k": P(None, None, "model"), "v": P(None, None, "model")}
            cc = jax.device_put(cache, shd.named(mesh, cs))
            pp = jax.device_put(params, shd.named(
                mesh, shd.param_specs(cfg, mesh)))
            out, _ = jax.jit(f)(pp, tok, cc, jnp.int32(s - 1))
        print(json.dumps({"delta": float(jnp.abs(ref - out).max())}))
        """)
        assert res["delta"] < 1e-2

    def test_moe_expert_parallel_matches(self):
        res = _run_sub("""
        import json
        cfg = get_config("dbrx-132b").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        loss1 = jax.jit(lambda p, b: api.loss_fn(p, cfg, b))(params, batch)
        mesh = mesh2x4()
        with mesh:
            pp = jax.device_put(params, shd.named(
                mesh, shd.param_specs(cfg, mesh)))
            bb = jax.device_put(batch, NamedSharding(mesh, P("data")))
            loss2 = jax.jit(lambda p, b: api.loss_fn(p, cfg, b))(pp, bb)
        print(json.dumps({"l1": float(loss1), "l2": float(loss2)}))
        """)
        assert abs(res["l1"] - res["l2"]) < 2e-2
