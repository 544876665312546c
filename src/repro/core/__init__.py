"""Tesserae core: graph-matching placement policies for DL cluster scheduling.

Public API:

* :class:`repro.core.scheduler.TesseraeScheduler` — the round scheduler
  (Listing 1) composing any :class:`~repro.core.policies.SchedulingPolicy`
  with the graph-based migration (§4.1) and packing (§4.2) policies.
* :class:`repro.core.simulator.Simulator` — round-based cluster simulator.
* :mod:`repro.core.matching` — LAP solvers (numpy Hungarian, scipy, JAX
  auction).
"""

from repro.core.cluster import ClusterSpec, PlacementPlan, count_migrations
from repro.core.jobs import JobSpec, JobState
from repro.core.migration import plan_migration
from repro.core.packing import pack_jobs
from repro.core.placement import place_without_packing
from repro.core.profiler import ThroughputProfile, register_model
from repro.core.scheduler import TesseraeScheduler, tiresias_single_packed_ok
from repro.core.simulator import SimConfig, Simulator

__all__ = [
    "ClusterSpec",
    "PlacementPlan",
    "count_migrations",
    "JobSpec",
    "JobState",
    "plan_migration",
    "pack_jobs",
    "place_without_packing",
    "ThroughputProfile",
    "register_model",
    "TesseraeScheduler",
    "tiresias_single_packed_ok",
    "SimConfig",
    "Simulator",
]
