"""repro.obs — jit-safe runtime observability (DESIGN.md §14).

Four pieces, one contract:

* :mod:`repro.obs.metrics` — device-resident registry (counters /
  gauges / histograms) whose record ops are pure ``jnp`` updates;
* :mod:`repro.obs.trace`   — the ``robust.*`` named scopes the compiled
  step carries into a device profile, the stats→plan→apply→select_plan
  span ring in the scan carry, host spans on the profiler's clock, and
  the Chrome-trace/Perfetto export at drain;
* :mod:`repro.obs.profile` — kernel launch-config records paired with
  the ``analysis/vmem`` prediction;
* :mod:`repro.obs.export`  — the host-side drain: ``obs.v1`` snapshots,
  serve percentiles, campaign phase digests.

In-graph code may *accumulate* into the registry/ring; only the export
layer may touch the host.  ``ObsConfig(enabled=False)`` (or ``obs=None``)
makes every instrumented step builder emit the bitwise-identical jaxpr
of the uninstrumented step — observability is free until switched on.

The observed state rides in ``TrainerState.mstate`` as a plain dict
``{"m": MetricsState, "t": TraceState | None}`` so it scans, shards and
checkpoints like any other carry (:func:`init_obs_state` seeds it; step
builders auto-seed at trace time when the slot is still ``None``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from repro.obs.metrics import (GRAD_NORM_EDGES, MetricsSpec, MetricsState,
                               ObsConfig, ema_gauge, inc, init_metrics,
                               init_suspicion, obs_on, observe, serve_spec,
                               set_gauge, train_spec, update_ema,
                               update_suspicion)
from repro.obs.trace import (PH_APPLY, PH_PLAN, PH_SELECT_PLAN, PH_STATS,
                             PHASES, SCOPE_PREFIX, SCOPES, SpanTracer,
                             TraceState, drain, export_chrome_trace,
                             init_trace, record, scope)
from repro.obs.profile import (KernelProfiler, KernelRecord, measure_vmem,
                               profile_points, record_kernel)
from repro.obs.export import (SCHEMA, metrics_to_json, percentiles,
                              phase_summary, serve_metrics, snapshot,
                              validate_snapshot, write_snapshot)

__all__ = [
    "GRAD_NORM_EDGES", "KernelProfiler", "KernelRecord", "MetricsSpec",
    "MetricsState", "ObsConfig", "PHASES", "PH_APPLY", "PH_PLAN",
    "PH_SELECT_PLAN", "PH_STATS", "SCHEMA", "SCOPES", "SCOPE_PREFIX",
    "SpanTracer", "TraceState",
    "drain", "ema_gauge", "export_chrome_trace", "inc", "init_metrics",
    "init_obs_state", "init_serve_obs", "init_suspicion", "init_trace",
    "init_train_obs", "measure_vmem", "metrics_to_json", "obs_on",
    "observe", "percentiles", "phase_summary", "profile_points", "record",
    "record_kernel", "scope",
    "serve_metrics", "serve_spec", "set_gauge", "snapshot", "train_spec",
    "update_ema", "update_suspicion", "validate_snapshot",
    "write_snapshot",
]


def init_obs_state(obs: Optional[ObsConfig],
                   spec: MetricsSpec) -> Optional[Dict[str, Any]]:
    """The ``mstate`` carry: ``None`` when obs is off (zero leaves)."""
    if not obs_on(obs):
        return None
    return {"m": init_metrics(spec),
            "t": init_trace(obs.ring) if obs.trace else None}


def init_train_obs(obs: Optional[ObsConfig], n_workers: int, *,
                   telemetry: bool = False) -> Optional[Dict[str, Any]]:
    """Seed the mstate both synchronous trainers expect.

    The sim engine calls this before ``lax.scan`` (a scan carry cannot
    change structure mid-trace); ``launch/train.py`` lets the step
    auto-seed instead — both paths land on the same spec.
    """
    return init_obs_state(obs, train_spec(n_workers, telemetry=telemetry))


def init_serve_obs(obs: Optional[ObsConfig], n_workers: int, tau: int, *,
                   telemetry: bool = False) -> Optional[Dict[str, Any]]:
    """Seed the mstate the async serve step expects."""
    return init_obs_state(
        obs, serve_spec(n_workers, tau, telemetry=telemetry))
