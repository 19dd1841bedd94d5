"""Profile-driven autotuner (DESIGN.md §11).

The paper's central trade — clock cycles inversely proportional to the
level of parallelism — shows up here as the plan knobs ``backend``,
``schedule`` (kind + splits), ``row_blk`` and ``channel_grid``.  This
package chooses them from measurement instead of hand-picked defaults:

* :mod:`repro.tune.sweep` — enumerate the servable candidate configs per
  workload key ``(n, t, v, batch)`` (pruning unservable combos via the
  plan-error taxonomy) and measure each with warm-up-excluded compiled
  wall-clock (AOT ``jax.jit(...).lower(...).compile()`` — a real XLA:CPU
  compile today, Mosaic/TPU transparently when present), recording a
  candidate that cannot compile as failed rather than timing it;
* :mod:`repro.tune.table` — the persistent versioned JSON tuning table,
  keyed by device kind + workload key, consulted by
  ``repro.plan(..., tuning=...)`` at plan time (resolution order:
  explicit knob > tuning table > static default);
* :mod:`repro.tune.costcheck` — cross-check of the HLO cost model
  (:mod:`repro.launch.hlo_analyzer`) against the stopwatch: rank
  correlation of predicted vs measured ordering per workload, flagging
  candidates where the two disagree badly.

CLI front door: ``python -m repro.launch.autotune`` (sweep /
show-table / check / prune-stale).

Only the table surface is imported eagerly — the sweep harness pulls in
the full execution stack, so import :mod:`repro.tune.sweep` explicitly.
"""
from repro.tune.table import (
    DEFAULT_TABLE_PATH,
    TABLE_SCHEMA,
    TABLE_VERSION,
    TUNABLE_KNOBS,
    TuningTable,
    TuningTableError,
    device_kind,
    parse_workload_key,
    workload_key,
)

__all__ = [
    "DEFAULT_TABLE_PATH",
    "TABLE_SCHEMA",
    "TABLE_VERSION",
    "TUNABLE_KNOBS",
    "TuningTable",
    "TuningTableError",
    "device_kind",
    "parse_workload_key",
    "workload_key",
]
