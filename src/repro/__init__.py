"""repro: PaReNTT — parallel RNS + NTT long polynomial modular multiplication
(Tan, Chiu, Wang, Lao, Parhi, 2023) as a production JAX framework.

The public surface is the plan/execute pair::

    import repro

    pl = repro.plan(n=4096, t=6, v=30)     # resolve + upload everything once
    limbs = repro.polymul(pl, za, zb)      # (..., n, S) -> (..., n, L)

``repro.plan`` dispatches on modulus width internally (int64 Pallas for
v <= 31, digit-split wide for v <= 46, host bigint oracle beyond); the
returned ``Plan`` is a JAX pytree, so ``jax.jit(repro.polymul)`` /
``jax.vmap`` / ``shard_map`` treat it as an ordinary argument.  See
:mod:`repro.api` for the full contract.

The crypto core requires 64-bit integer arithmetic; enable x64 once at
package import (before anything touches jax.numpy).  All floating-point
model code states dtypes explicitly, so the x64 default does not leak
into LM layers.
"""
from jax import config as _config

_config.update("jax_enable_x64", True)

__version__ = "0.2.0"

from repro.errors import (  # noqa: E402
    BackendFailedError,
    CompileFailedError,
    DeadlineExceededError,
    EngineError,
    PlanError,
    QueueFullError,
    UnknownKnobError,
    UnservableConfigError,
)
from repro.api import (  # noqa: E402  (x64 must flip before jax.numpy use)
    BACKENDS,
    SCHEDULES,
    WIDTHS,
    Plan,
    PlanConfig,
    ScheduleSpec,
    compose,
    decompose,
    execute,
    from_limbs,
    intt,
    negacyclic_mul,
    ntt,
    plan,
    plan_key,
    polymul,
    polymul_ints,
    to_segments,
)


def verify_plan(pl, **kwargs):
    """Statically verify a Plan's kernel datapaths (overflow / envelope /
    lane / staticness).  Thin lazy wrapper over
    :func:`repro.analysis.verify.verify_plan` so importing ``repro`` does
    not pull the analysis stack."""
    from repro.analysis.verify import verify_plan as _vp

    return _vp(pl, **kwargs)


__all__ = [
    "BACKENDS",
    "BackendFailedError",
    "CompileFailedError",
    "DeadlineExceededError",
    "EngineError",
    "QueueFullError",
    "SCHEDULES",
    "WIDTHS",
    "Plan",
    "PlanConfig",
    "PlanError",
    "ScheduleSpec",
    "UnknownKnobError",
    "UnservableConfigError",
    "__version__",
    "compose",
    "decompose",
    "execute",
    "from_limbs",
    "intt",
    "negacyclic_mul",
    "ntt",
    "plan",
    "plan_key",
    "polymul",
    "polymul_ints",
    "to_segments",
    "verify_plan",
]
