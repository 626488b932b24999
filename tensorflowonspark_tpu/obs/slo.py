"""Declarative SLOs + multi-window burn-rate evaluation over History.

"p99 within budget under X" used to be restated as a hand-rolled
assert at each site; this module makes the objective declarative and
the evaluation uniform, so serve_model ``/statusz`` and the router's
shed annotations gate on the SAME evaluator.

An :class:`SLO` names an objective over metrics that ``obs.history``
already retains:

- ``kind="latency"``: a histogram metric; the *bad fraction* of a
  window is the share of observations slower than ``objective``
  seconds (interpolated from cumulative bucket deltas).
- ``kind="error_rate"`` / ``kind="availability"``: a bad-event counter
  over a total counter; the bad fraction is ``bad / total`` deltas.

**Burn rate** is the classic multi-window form: ``bad_fraction /
budget`` computed over a fast and a slow trailing window; a *breach*
requires BOTH to exceed their thresholds (fast catches the spike, slow
filters the blip). Verdicts are emitted three ways on every
:meth:`SLOEvaluator.evaluate`:

- ``slo_burn_rate{slo,window}`` gauge (both windows, every cycle);
- ``slo_breaches_total{slo}`` counter (rising edge only);
- a ``slo_breach`` flight-recorder event, plus an async
  ``dump_now("slo_breach:<name>")`` on the rising edge — a breach is
  an incident, and the black box should hold the moment it began.

No data (an empty window) evaluates to burn 0.0 — an idle service is
in budget, and the evaluator must not false-fire at startup.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Mapping

from tensorflowonspark_tpu.obs import flightrec
from tensorflowonspark_tpu.obs.history import History
from tensorflowonspark_tpu.obs.registry import Registry, default_registry

__all__ = ["SLO", "SLOEvaluator", "default_serving_slos", "router_slos"]

_KINDS = ("latency", "error_rate", "availability")


@dataclass(frozen=True)
class SLO:
    """One objective. ``budget`` is the allowed bad fraction (0.01 =
    99% of requests must be good); burn 1.0 = consuming budget exactly
    at the sustainable rate."""

    name: str
    kind: str
    metric: str  # histogram (latency) / bad-event counter (rates)
    objective: float = 0.0  # latency bound, seconds (latency kind only)
    budget: float = 0.01
    total_metric: str | None = None  # denominator counter (rate kinds)
    labels: Mapping[str, str] | None = None
    total_labels: Mapping[str, str] | None = None
    fast_window_s: float = 60.0
    slow_window_s: float = 300.0
    fast_burn: float = 14.0
    slow_burn: float = 6.0
    description: str = ""

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(
                f"SLO {self.name!r}: kind must be one of {_KINDS}, "
                f"got {self.kind!r}"
            )
        if self.kind == "latency" and self.objective <= 0:
            raise ValueError(
                f"latency SLO {self.name!r} needs objective > 0 seconds"
            )
        if self.kind != "latency" and not self.total_metric:
            raise ValueError(
                f"{self.kind} SLO {self.name!r} needs total_metric"
            )
        if self.budget <= 0 or self.budget >= 1:
            raise ValueError(
                f"SLO {self.name!r}: budget must be in (0, 1), "
                f"got {self.budget}"
            )


def default_serving_slos(
    ttft_objective_s: float = 2.5,
    ttft_budget: float = 0.05,
    error_budget: float = 0.02,
    fast_window_s: float = 60.0,
    slow_window_s: float = 300.0,
) -> tuple[SLO, ...]:
    """serve_model's per-replica objectives, over the engine's own
    registry metrics (one replica, no router in the loop)."""
    return (
        SLO(
            name="ttft",
            kind="latency",
            metric="engine_ttft_seconds",
            objective=ttft_objective_s,
            budget=ttft_budget,
            fast_window_s=fast_window_s,
            slow_window_s=slow_window_s,
            description="time-to-first-token within objective",
        ),
        SLO(
            name="engine_errors",
            kind="error_rate",
            metric="engine_requests_failed_total",
            total_metric="engine_requests_total",
            budget=error_budget,
            fast_window_s=fast_window_s,
            slow_window_s=slow_window_s,
            description="engine-failed requests within error budget",
        ),
    )


def router_slos(
    latency_objective_s: float,
    latency_budget: float = 0.05,
    shed_budget: float = 0.02,
    fast_window_s: float = 60.0,
    slow_window_s: float = 300.0,
    fast_burn: float = 14.0,
    slow_burn: float = 6.0,
) -> tuple[SLO, ...]:
    """Fleet-level objectives over the router's registry."""
    return (
        SLO(
            name="fleet_latency",
            kind="latency",
            metric="router_request_seconds",
            objective=latency_objective_s,
            budget=latency_budget,
            fast_window_s=fast_window_s,
            slow_window_s=slow_window_s,
            fast_burn=fast_burn,
            slow_burn=slow_burn,
            description="routed request latency within objective",
        ),
        SLO(
            name="fleet_availability",
            kind="availability",
            metric="router_shed_total",
            total_metric="router_requests_total",
            budget=shed_budget,
            fast_window_s=fast_window_s,
            slow_window_s=slow_window_s,
            fast_burn=fast_burn,
            slow_burn=slow_burn,
            description="admission sheds within availability budget",
        ),
    )


@dataclass
class Verdict:
    """One SLO's evaluation at one instant (JSON-safe via vars())."""

    slo: str
    kind: str
    breached: bool
    burn_fast: float
    burn_slow: float
    bad_fraction_fast: float | None
    budget: float
    objective: float
    detail: dict[str, Any] = field(default_factory=dict)

    def as_dict(self) -> dict[str, Any]:
        return {
            "slo": self.slo,
            "kind": self.kind,
            "breached": self.breached,
            "burn_fast": self.burn_fast,
            "burn_slow": self.burn_slow,
            "bad_fraction_fast": self.bad_fraction_fast,
            "budget": self.budget,
            "objective": self.objective,
            **self.detail,
        }


class SLOEvaluator:
    """Evaluates a set of SLOs against one History on demand."""

    def __init__(
        self,
        slos: tuple[SLO, ...] | list[SLO],
        history: History,
        registry: Registry | None = None,
    ):
        names = [s.name for s in slos]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate SLO names: {names}")
        self.slos = tuple(slos)
        self.history = history
        reg = registry if registry is not None else default_registry()
        self._g_burn = reg.gauge(
            "slo_burn_rate",
            "error-budget burn rate per SLO and window (1.0 = "
            "sustainable consumption)",
        )
        self._m_breach = reg.counter(
            "slo_breaches_total",
            "multi-window SLO breach onsets (rising edges)",
        )
        self._lock = threading.Lock()
        self._breached: dict[str, bool] = {}  # guarded-by: self._lock
        self._last: list[Verdict] = []  # guarded-by: self._lock
        self._evals = 0  # guarded-by: self._lock

    # -- math ---------------------------------------------------------

    def _bad_fraction(self, slo: SLO, window_s: float, now) -> float | None:
        h = self.history
        if slo.kind == "latency":
            frac = h.fraction_le(
                slo.metric, slo.objective, dict(slo.labels or {}) or None,
                window_s=window_s, now=now,
            )
            return None if frac is None else max(0.0, 1.0 - frac)
        bad = h.delta(
            slo.metric, dict(slo.labels or {}) or None,
            window_s=window_s, now=now,
        )
        total = h.delta(
            slo.total_metric,
            dict(slo.total_labels or slo.labels or {}) or None,
            window_s=window_s, now=now,
        )
        if slo.kind == "availability":
            # sheds never reach the request counter: the offered load
            # is admitted + shed
            total += bad
        if total <= 0:
            return None
        return max(0.0, min(1.0, bad / total))

    # -- evaluation ---------------------------------------------------

    def evaluate(self, now: float | None = None) -> list[Verdict]:
        now = time.time() if now is None else float(now)
        verdicts: list[Verdict] = []
        onsets: list[Verdict] = []
        for slo in self.slos:
            bf = self._bad_fraction(slo, slo.fast_window_s, now)
            bs = self._bad_fraction(slo, slo.slow_window_s, now)
            burn_fast = 0.0 if bf is None else bf / slo.budget
            burn_slow = 0.0 if bs is None else bs / slo.budget
            breached = burn_fast >= slo.fast_burn and burn_slow >= slo.slow_burn
            self._g_burn.set(burn_fast, slo=slo.name, window="fast")
            self._g_burn.set(burn_slow, slo=slo.name, window="slow")
            v = Verdict(
                slo=slo.name,
                kind=slo.kind,
                breached=breached,
                burn_fast=round(burn_fast, 4),
                burn_slow=round(burn_slow, 4),
                bad_fraction_fast=None if bf is None else round(bf, 6),
                budget=slo.budget,
                objective=slo.objective,
            )
            verdicts.append(v)
            with self._lock:
                was = self._breached.get(slo.name, False)
                self._breached[slo.name] = breached
            if breached and not was:
                self._m_breach.inc(slo=slo.name)
                flightrec.note(
                    "slo_breach",
                    slo=slo.name,
                    slo_kind=slo.kind,
                    burn_fast=v.burn_fast,
                    burn_slow=v.burn_slow,
                    budget=slo.budget,
                )
                onsets.append(v)
        with self._lock:
            self._last = list(verdicts)
            self._evals += 1
        for v in onsets:
            # a breach onset is an incident: persist the black box —
            # on a daemon thread, the dump's IO must not sit on the
            # evaluation (often a request-path pump) thread
            threading.Thread(
                target=flightrec.dump_now,
                args=(f"slo_breach:{v.slo}",),
                daemon=True,
            ).start()
        return verdicts

    # -- read surface -------------------------------------------------

    def last_verdicts(self) -> list[Verdict]:
        with self._lock:
            return list(self._last)

    def breaching(self) -> list[str]:
        """Names of SLOs currently in breach (last evaluation)."""
        with self._lock:
            return sorted(k for k, v in self._breached.items() if v)

    def statusz(self) -> dict[str, Any]:
        """The JSON block serve_model ``/statusz`` exposes."""
        with self._lock:
            last = list(self._last)
            evals = self._evals
        return {
            "evaluations": evals,
            "breaching": sorted(
                v.slo for v in last if v.breached
            ),
            "slos": [v.as_dict() for v in last],
        }
