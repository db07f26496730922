"""Derived observability: typed traffic snapshots and registry folds.

``TrafficSnapshot`` is the single typed observation the adaptive
controller reads each window (it used to be an ad-hoc dict built inside
the controller from engine internals).  ``fold_engine_metrics`` projects
an engine ``stats()`` dict onto the Prometheus registry as gauges.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

from .metrics import MetricsRegistry


@dataclass(frozen=True)
class TrafficSnapshot:
    """One observation window of serving traffic, as the controller sees it.

    lam: arrival rate (requests/s) over the rolling window.
    avg_prompt: mean prompt tokens of recent arrivals.
    avg_new: mean requested new tokens of recent arrivals.
    queued_tok: prompt tokens waiting in the admission queue.
    depth: forecast decode depth (active slots + expected arrivals
        over the controller horizon, capped at slot count).
    queue_len: requests waiting in the admission queue.
    active: currently active decode slots.
    violated: True when the rolling TTFT/TPOT percentiles breach SLOs.
    window_s: the rolling window the snapshot was computed over.
    """

    lam: float
    avg_prompt: float
    avg_new: float
    queued_tok: float
    depth: float
    queue_len: int
    active: int
    violated: bool
    window_s: float


def fold_engine_metrics(reg: MetricsRegistry, st: Dict[str, Any]) -> None:
    """Project an engine ``stats()`` dict onto registry gauges.

    Gauges are *set* (not accrued), so folding the same snapshot twice
    is idempotent — repeated exports in one window agree.
    """
    g = reg.gauge
    g("repro_throughput_tok_s", "generated tokens per second (window)").set(
        st.get("throughput_tok_s", 0.0))
    g("repro_slot_occupancy", "mean active-slot fraction per tick").set(
        st.get("slot_occupancy", 0.0))
    g("repro_tokens_per_step", "mean tokens committed per decode step").set(
        st.get("tokens_per_step", 0.0))
    g("repro_replans_total", "plan swaps this window").set(st.get("replans", 0))
    g("repro_migrations_total", "slot migrations this window").set(
        st.get("migrations", 0))
    g("repro_migration_copies_total", "KV copies during migration (0 = zero-copy)").set(
        st.get("migration_copies", 0))
    g("repro_ticks_total", "engine ticks this window").set(st.get("ticks", 0))
    for phase, secs in st.get("phase_time_s", {}).items():
        g("repro_phase_seconds", "host wall seconds per engine phase (window)",
          phase=phase).set(secs)
    util = st.get("utilization", {})
    for s, frac in util.get("stage_bubble_frac", {}).items():
        g("repro_stage_bubble_frac",
          "fraction of busy-pipeline ticks each prefill stage sat idle",
          stage=str(s)).set(frac)
    for r, occ in util.get("replica_occupancy", {}).items():
        g("repro_replica_occupancy",
          "mean occupied-slot fraction per decode replica",
          replica=str(r)).set(occ)
    g("repro_replica_load_spread",
      "max-min replica occupancy gap (0 = balanced)").set(
        util.get("replica_load_spread", 0.0))
    g("repro_spec_acceptance_rate", "accepted / proposed draft tokens").set(
        util.get("spec_acceptance_rate", 0.0))
    g("repro_prefix_hit_rate", "warm-prefix admissions / total admissions").set(
        util.get("prefix_hit_rate", 0.0))
    cache = st.get("cache")
    if cache:
        g("repro_cache_blocks_in_use", "paged KV blocks currently allocated").set(
            cache.get("blocks_in_use", 0))
        g("repro_cache_peak_blocks", "peak concurrent paged KV blocks").set(
            cache.get("peak_blocks_in_use", 0))
        g("repro_kv_capacity_x", "effective KV capacity multiplier (int8)").set(
            cache.get("kv_capacity_x", 1.0))
