"""Runtime execution-policy layer.

``ExecPolicy`` is the single object that decides *how* the numerics run —
which exponential backend (exact transcendental vs. the paper's VEXP
approximation vs. the bit-exact hardware model), which kernel backend
(Pallas TPU kernels vs. pure-jnp reference vs. XLA-fused), block sizes, and
interpret/accumulation settings — resolved once from model-config fields,
environment variables, and per-call overrides, then threaded through core,
kernels, models, serving and training.
"""

from .policy import (ExecPolicy, resolve_policy, policy_from_env,
                     parse_policy_groups,
                     EXP_BACKENDS, KERNEL_BACKENDS, ENV_PREFIX)
from .compile_cache import use_compile_cache

__all__ = ["ExecPolicy", "resolve_policy", "policy_from_env",
           "parse_policy_groups",
           "EXP_BACKENDS", "KERNEL_BACKENDS", "ENV_PREFIX",
           "use_compile_cache"]
