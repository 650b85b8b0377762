"""Layer 2: audits over *lowered programs* (imports JAX; runs in pytest).

Where the AST layer reads source, this layer reads what XLA will
actually execute. Three audits, each a report function plus an assert
wrapper that raises a typed ``AssertionError`` subclass:

* **collectives** — count and kinds of StableHLO collective ops in the
  lowered program. The serving contract (PR-4) is a hard budget: the
  packed sharded decode step is exactly ONE ``all_gather`` per layer,
  and unsharded programs are collective-free.
* **donation** — every ``donate_argnums`` buffer must actually be
  consumed (aliased to an output) by the compiled program. XLA only
  *warns* on an unconsumed donation at execution time; a dtype drift in
  the carry silently turns donation off and doubles decode-state memory
  (the PR-5 bf16 conv-state bug). Consumed donations show up as entries
  of the compiled module's ``input_output_alias``.
* **carry stability** — the decode carry pytree (state, positions) must
  come out of the step with the same treedef, dtypes, shapes (and
  shardings, when present) it went in with. Checked abstractly via
  ``jax.eval_shape``, so no device execution is needed.
* **output shardings** — a designated output of the COMPILED program
  must carry exactly an expected sharding pytree. The serving contract
  (PR-8): the sharded chunk-prefill program's cache output carries the
  pool sharding, so admitted rows are produced in place on the mesh and
  the engine never re-places them with a post-prefill ``device_put``.
  This one compiles (``eval_shape`` does not expose output shardings) —
  cheap at test shapes, and the jit cache makes it free on a program
  the engine already built.

All accept either a jitted callable plus example/abstract args, an
already-``.lower()``-ed object, or (for the text-based audits) the
program text itself — StableHLO for collectives, the compiled HLO for
donation — keeping them cheap to aim at any program the engine builds.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp  # noqa: F401  (callers pass jnp dtypes through us)

# StableHLO collective op names as they appear in lowered text. Matched
# with a trailing delimiter so e.g. `all_gather` never counts
# `all_gather_something`.
COLLECTIVE_KINDS = (
    "all_gather",
    "all_reduce",
    "all_to_all",
    "collective_permute",
    "collective_broadcast",
    "reduce_scatter",
)

_COLLECTIVE_RE = re.compile(
    r'"?stablehlo\.(' + "|".join(COLLECTIVE_KINDS) + r')"?[\s("]')


class AuditError(AssertionError):
    """Base for audit failures (AssertionError so pytest renders it)."""


class CollectiveBudgetError(AuditError):
    pass


class DonationError(AuditError):
    pass


class CarryStabilityError(AuditError):
    pass


class OutputShardingError(AuditError):
    pass


def lowered_text(target, *args, **kwargs) -> str:
    """StableHLO text for ``target``.

    ``target`` may be: the text itself (str), a ``Lowered`` object, or a
    callable — jitted callables are ``.lower(*args)``-ed directly, plain
    callables are wrapped in ``jax.jit`` first (fine for inspection; the
    wrapper is never executed)."""
    if isinstance(target, str):
        return target
    if hasattr(target, "as_text"):
        return target.as_text()
    if hasattr(target, "lower"):
        return target.lower(*args, **kwargs).as_text()
    return jax.jit(target).lower(*args, **kwargs).as_text()


# ------------------------------------------------------------- collectives

def collective_counts(target, *args, **kwargs) -> dict:
    """``{kind: count}`` over every collective in the lowered program
    (kinds with zero occurrences are omitted)."""
    text = lowered_text(target, *args, **kwargs)
    counts: dict = {}
    for m in _COLLECTIVE_RE.finditer(text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def assert_collective_budget(target, budget: dict, *args, **kwargs):
    """Assert the program's collectives are EXACTLY ``budget``
    (``{kind: count}``); kinds absent from the budget must not appear at
    all. ``budget={}`` asserts a collective-free program."""
    got = collective_counts(target, *args, **kwargs)
    want = {k: v for k, v in budget.items() if v}
    if got != want:
        raise CollectiveBudgetError(
            f"collective budget violated: program has {got or 'none'}, "
            f"budget allows {want or 'none'} — the serving contract is "
            f"a hard per-layer collective count, any drift is a perf "
            f"regression")
    return got


# ---------------------------------------------------------------- donation

# One entry of the compiled module's ``input_output_alias`` per donated
# parameter XLA paired with an output, e.g. ``{0}: (0, {}, may-alias)``.
# The lowered StableHLO cannot decide this: it marks every donated
# parameter ``jax.buffer_donor`` and leaves the pairing to the compiler,
# which drops a donation whose aval matches no output (a buffer_donor
# entry of the compiled module, not an alias).
_ALIAS_RE = re.compile(r"\(\d+, \{[^}]*\}, (?:may|must)-alias\)")


def compiled_text(target, *args, **kwargs) -> str:
    """Compiled HLO text for ``target``: the text itself (str), a
    ``Lowered`` or ``Compiled`` object, or a callable (jitted callables
    are lowered directly, plain ones wrapped in ``jax.jit`` first)."""
    if isinstance(target, str):
        return target
    if hasattr(target, "lower"):
        target = target.lower(*args, **kwargs)
    elif not hasattr(target, "as_text"):
        target = jax.jit(target).lower(*args, **kwargs)
    if hasattr(target, "compile"):
        target = target.compile()
    return target.as_text()


@dataclass
class DonationReport:
    donated_leaves: int            # array leaves in donated arg positions
    aliased_params: int            # params the compiled program aliases

    @property
    def fully_consumed(self) -> bool:
        return self.aliased_params >= self.donated_leaves


def donation_report(target, donate_argnums, *args, **kwargs):
    """How many donated buffers the compiled program actually consumes.

    ``target`` must be the jitted-with-donation callable (or its
    ``Lowered``/``Compiled``/compiled text); ``donate_argnums`` re-states
    the donated arg positions so the expected leaf count can be derived
    from ``args``. When ``target`` is already lowered or compiled, pass
    the expected leaf count directly as ``donate_argnums`` (int)."""
    if isinstance(donate_argnums, int):
        expected = donate_argnums
    else:
        expected = 0
        for i in donate_argnums:
            expected += len(jax.tree_util.tree_leaves(args[i]))
    header = compiled_text(target, *args, **kwargs).split("\n", 1)[0]
    aliased = len(_ALIAS_RE.findall(header))
    return DonationReport(donated_leaves=expected, aliased_params=aliased)


def assert_all_donated(target, donate_argnums, *args, **kwargs):
    rep = donation_report(target, donate_argnums, *args, **kwargs)
    if not rep.fully_consumed:
        raise DonationError(
            f"donation not consumed: {rep.donated_leaves} donated "
            f"buffer leaves but only {rep.aliased_params} aliased "
            f"outputs in the compiled program — an unconsumed donation "
            f"silently doubles decode-state memory (the PR-5 dtype-"
            f"drift class)")
    return rep


# ---------------------------------------------------------- carry stability

def _leaf_desc(leaf):
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = getattr(leaf, "dtype", None)
    sharding = getattr(leaf, "sharding", None)
    return shape, dtype, sharding


def _path_str(path) -> str:
    return jax.tree_util.keystr(path) or "<root>"


def carry_mismatches(carry_in, carry_out) -> list:
    """Human-readable mismatch list between two carry pytrees. Empty
    means the carry is stable (same treedef; every leaf keeps shape and
    dtype; shardings compared when both sides expose one)."""
    in_leaves, in_def = jax.tree_util.tree_flatten_with_path(carry_in)
    out_leaves, out_def = jax.tree_util.tree_flatten_with_path(carry_out)
    if in_def != out_def:
        return [f"carry treedef changed across the step: "
                f"{in_def} -> {out_def}"]
    out = []
    for (path, a), (_, b) in zip(in_leaves, out_leaves):
        (sa, da, ha), (sb, db, hb) = _leaf_desc(a), _leaf_desc(b)
        where = _path_str(path)
        if da != db:
            out.append(f"{where}: dtype {da} -> {db} (dtype drift "
                       f"defeats donation — the PR-5 bug class)")
        if sa != sb:
            out.append(f"{where}: shape {sa} -> {sb}")
        if ha is not None and hb is not None and ha != hb:
            out.append(f"{where}: sharding {ha} -> {hb}")
    return out


def carry_report(fn, args, carry_map: dict, kwargs=None) -> list:
    """Audit a step function's carry abstractly.

    ``carry_map`` maps input arg position -> output tuple index for each
    carried value (e.g. ``{2: 1, 3: 2}`` for
    ``decode_fn(params, tok, cache, pos, live) -> (logits, cache,
    pos')``). Runs under ``jax.eval_shape`` — abstract, no FLOPs, and
    donation on the jitted ``fn`` is ignored so the same program object
    the engine runs can be audited directly."""
    outs = jax.eval_shape(fn, *args, **(kwargs or {}))
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    msgs = []
    for argnum, outidx in sorted(carry_map.items()):
        for m in carry_mismatches(args[argnum], outs[outidx]):
            msgs.append(f"carry arg {argnum} -> out {outidx}: {m}")
    return msgs


def assert_carry_stable(fn, args, carry_map: dict, kwargs=None):
    msgs = carry_report(fn, args, carry_map, kwargs=kwargs)
    if msgs:
        raise CarryStabilityError(
            "decode carry is not stable across the step:\n  "
            + "\n  ".join(msgs))


# --------------------------------------------------------- output shardings

def output_shardings(target, *args, **kwargs):
    """Per-output sharding pytree of the COMPILED program.

    ``target`` may be a ``Compiled`` object, a ``Lowered`` object, a
    jitted callable, or a plain callable (wrapped in ``jax.jit``).
    Callables/Lowereds are compiled here — this audit genuinely needs
    the compiler's placement decision, which neither the jaxpr nor
    ``eval_shape`` exposes."""
    if hasattr(target, "output_shardings"):            # Compiled
        return target.output_shardings
    if hasattr(target, "lower"):                       # jitted callable
        target = target.lower(*args, **kwargs)
    elif not hasattr(target, "compile"):               # plain callable
        target = jax.jit(target).lower(*args, **kwargs)
    return target.compile().output_shardings


def output_sharding_report(fn, out_index, want, *args, **kwargs) -> list:
    """Mismatches between output ``out_index``'s compiled shardings and
    the expected sharding pytree ``want`` (same treedef as that output;
    pass ``out_index=None`` to compare the whole output tuple). Leaves
    compare via ``Sharding.is_equivalent_to`` at each output's rank —
    placement-equal shardings match even when spelled differently.
    Empty list == contract holds."""
    got = output_shardings(fn, *args, **kwargs)
    outs = jax.eval_shape(fn, *args, **kwargs)
    if out_index is not None:
        got, outs = got[out_index], outs[out_index]
    g_leaves, g_def = jax.tree_util.tree_flatten_with_path(got)
    w_leaves, w_def = jax.tree_util.tree_flatten(want)
    o_leaves = jax.tree_util.tree_leaves(outs)
    if g_def != w_def:
        return [f"output treedef differs from the expected sharding "
                f"tree: {g_def} != {w_def}"]
    msgs = []
    for (path, g), w, o in zip(g_leaves, w_leaves, o_leaves):
        same = (g.is_equivalent_to(w, o.ndim)
                if hasattr(g, "is_equivalent_to") else g == w)
        if not same:
            msgs.append(f"{_path_str(path)}: compiled output sharding "
                        f"{g} != expected {w}")
    return msgs


def assert_output_sharding(fn, out_index, want, *args, **kwargs):
    msgs = output_sharding_report(fn, out_index, want, *args, **kwargs)
    if msgs:
        raise OutputShardingError(
            "program output does not carry the expected sharding (rows "
            "would need a re-placement device_put — the copy this "
            "contract exists to forbid):\n  " + "\n  ".join(msgs))
    return output_shardings(fn, *args, **kwargs)
