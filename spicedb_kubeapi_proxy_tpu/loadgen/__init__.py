"""Open-loop, trace-shaped load generation (the chaos campaign's traffic).

A closed loop waits for each response before issuing the next request,
so the offered load collapses to whatever the server can absorb and the
system is never actually behind. Production traffic is open-loop —
watch storms, fleet-wide ``kubectl get`` waves, operator reconcile
loops fire on their own schedule whether or not the proxy is
keeping up — and that is the regime in which sheds, errors and lateness
have to be recorded as outcomes.

- :mod:`.schedule` — arrival-time schedules: Poisson baseline modulated
  by named burst phases, Zipf-skewed tenants, one seeded RNG (identical
  seed ⇒ identical schedule, byte for byte).
- :mod:`.driver` — the open-loop driver: fires each arrival at its
  scheduled time and NEVER waits for a response before the next one;
  sheds/errors/lateness are recorded, not absorbed.
"""

from .driver import DriverReport, OpenLoopDriver, OpOutcome
from .schedule import (
    Arrival,
    BurstPhase,
    ScheduleConfig,
    build_schedule,
    trace_shaped_config,
)

__all__ = [
    "Arrival",
    "BurstPhase",
    "DriverReport",
    "OpenLoopDriver",
    "OpOutcome",
    "ScheduleConfig",
    "build_schedule",
    "trace_shaped_config",
]
