"""Hot-path contract analyzer: the repo's serving invariants as checks.

The paper's wins come from keeping the Softmax/attention hot path free of
hidden overheads; at the program level this repo depends on the same
discipline — "one collective per layer, zero host syncs, donated
buffers" — and each of those contracts has already been violated once by
an innocent-looking change (a bf16 conv-state dtype drift that silently
defeated donation; a ``cow()`` refcount leak on an eviction path). This
package turns the contracts into CI-enforced checks, in two layers:

**Layer 1 — AST lint** (stdlib-only; no JAX import, runs anywhere):
source rules over ``src/repro/**`` driven by the hot-path registry
(``registry.hot_path`` marker + config lists):

* ``host-sync-in-hot-path`` — ``.item()``, ``jax.device_get``,
  ``block_until_ready``, ``np.asarray`` (and, at warn severity,
  ``int()/float()/bool()``) inside functions marked ``@hot_path``;
* ``refcount-pairing`` — raw ``.refs`` mutation outside the
  ``incref``/``decref`` primitives and allocation loops with no
  release-on-exception guard (the PR-6 ``cow()`` leak class);
* ``jit-retrace-hazard`` — mutable default arguments on jitted
  functions, ``functools.lru_cache`` keyed on array arguments;
* ``engine-family-branch`` / ``silent-fallback`` — the prose contracts
  (serve.py family-branch-free; ``decode_attention_policy`` has no
  reference fallback; core routing never gates on layout/window)
  promoted from source-string greps to real AST rules.

Findings diff against ``baseline.toml`` (every suppression carries a
justification); ``python -m repro.analysis src/repro`` exits nonzero on
anything new. See ``cli.py`` for flags.

**Layer 2 — jaxpr/lowering audit** (``jaxpr_audit``; imports JAX, runs
under pytest): takes a jitted callable + args and reports collective
count/kinds per lowered program (the PR-4 one-collective-per-layer
budget), donation consumption (every ``donate_argnums`` buffer actually
aliased in the compiled program), and carry stability (the decode carry
pytree keeps identical dtypes/shapes/shardings across the step — the
exact PR-5 bug class).

Import note: this ``__init__`` must stay stdlib-only — model modules
import ``repro.analysis.registry`` for the ``hot_path`` marker, so any
heavyweight import here would cycle or slow every model import.
``jaxpr_audit`` is exposed lazily for the same reason.
"""

from __future__ import annotations

from .findings import Finding, Severity, format_findings  # noqa: F401
from .registry import hot_path  # noqa: F401
from .rules import ALL_RULES, run_rules  # noqa: F401

__all__ = [
    "Finding", "Severity", "format_findings", "hot_path",
    "ALL_RULES", "run_rules", "jaxpr_audit",
]


def __getattr__(name):
    if name == "jaxpr_audit":            # lazy: pulls in jax
        import importlib
        return importlib.import_module(".jaxpr_audit", __name__)
    raise AttributeError(name)
