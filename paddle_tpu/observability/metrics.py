"""Metrics core: Counter / Gauge / Histogram families + MetricRegistry.

Reference being replaced (SURVEY.md §5): the runtime counter side of
``StatRegistry``/STAT_ADD (platform/monitor.h:80/133) — process-wide
named int/float stats — generalized the way 2026 serving/training
stacks need it: typed instruments (monotonic counters, set-anything
gauges, bucketed histograms with percentile readout), label sets per
family, and one process-wide registry every exporter reads from.

Host-side by design, like the reference's monitor: device-side numbers
(HBM per-op, kernel times) live in the XProf trace; these metrics cover
the framework events the trace can't see across a whole run — TTFT per
request, checkpoint bytes, restart counts — and feed the exporters in
``observability.exporters`` (Prometheus text, JSONL reporter).

Everything here is stdlib-only so any module (core, io, inference) can
import it without cycles or deferred-import tricks.
"""

from __future__ import annotations

import bisect
import math
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

Number = Union[int, float]

# Prometheus' classic default latency ladder (seconds); callers sizing
# for token rates or byte counts pass their own boundaries.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

# throughput ladder (tokens/sec, examples/sec): a toy decode on the
# CPU can sit at single digits, a full pod at 1e6+
RATE_BUCKETS: Tuple[float, ...] = (
    1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
    2500.0, 5000.0, 10000.0, 100000.0, 1000000.0)

# checkpoint / transfer sizes
BYTE_BUCKETS: Tuple[float, ...] = (
    1e4, 1e5, 1e6, 1e7, 1e8, 1e9, 1e10, 1e11)

# fractions of a whole (occupancy, pool utilization)
RATIO_BUCKETS: Tuple[float, ...] = (
    0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0)

LabelValues = Tuple[str, ...]


def _format_labels(names: Sequence[str], values: LabelValues) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{v}"' for n, v in zip(names, values))
    return "{" + inner + "}"


class _Child:
    """One (label-values) series inside a family. Families with no
    labels have exactly one child, keyed by the empty tuple."""

    def __init__(self, family: "MetricFamily", values: LabelValues):
        self._family = family
        self._lock = family._lock
        self.label_values = values


class CounterChild(_Child):
    def __init__(self, family, values):
        super().__init__(family, values)
        self._value: float = 0.0

    def inc(self, value: Number = 1) -> None:
        if value < 0:
            raise ValueError(
                f"counter {self._family.name} cannot decrease "
                f"(inc({value})); use a Gauge")
        with self._lock:
            self._value += value

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class GaugeChild(_Child):
    def __init__(self, family, values):
        super().__init__(family, values)
        self._value: float = 0.0

    def set(self, value: Number) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, value: Number = 1) -> None:
        with self._lock:
            self._value += value

    def dec(self, value: Number = 1) -> None:
        self.inc(-value)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class HistogramChild(_Child):
    """Cumulative-bucket histogram (Prometheus semantics: each bucket
    upper bound ``le`` is INCLUSIVE, an observation equal to a boundary
    lands in that boundary's bucket) plus exact count/sum/min/max, so
    percentile readout never needs the raw stream."""

    def __init__(self, family, values):
        super().__init__(family, values)
        self._bounds: List[float] = list(family.buckets)
        # one count per finite bound + the +Inf overflow slot
        self._counts: List[int] = [0] * (len(self._bounds) + 1)
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf

    def observe(self, value: Number) -> None:
        v = float(value)
        idx = bisect.bisect_left(self._bounds, v)
        with self._lock:
            self._counts[idx] += 1
            self._count += 1
            self._sum += v
            self._min = min(self._min, v)
            self._max = max(self._max, v)

    # -- readout --------------------------------------------------------
    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    @property
    def mean(self) -> float:
        with self._lock:
            return self._sum / self._count if self._count else 0.0

    def bucket_counts(self) -> List[Tuple[float, int]]:
        """CUMULATIVE (le, count) pairs ending with (+inf, total)."""
        with self._lock:
            out, cum = [], 0
            for bound, c in zip(self._bounds, self._counts):
                cum += c
                out.append((bound, cum))
            out.append((math.inf, self._count))
            return out

    def quantile(self, q: float) -> float:
        """Estimate the q-quantile (0..1) from the buckets by linear
        interpolation inside the bucket holding the target rank,
        clamped to the observed [min, max] so boundary-exact
        observations report exactly (covered by tests)."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile {q} outside [0, 1]")
        with self._lock:
            if self._count == 0:
                return 0.0
            rank = q * self._count
            cum = 0.0
            lo = self._min
            for bound, c in zip(self._bounds, self._counts):
                if cum + c >= rank and c > 0:
                    hi = min(bound, self._max)
                    frac = (rank - cum) / c
                    est = lo + (hi - lo) * frac
                    return min(max(est, self._min), self._max)
                if c > 0:
                    lo = bound
                cum += c
            return self._max  # target rank fell in the +Inf bucket

    def percentiles(self, ps: Iterable[float] = (50, 90, 99)
                    ) -> Dict[str, float]:
        return {f"p{g:g}": self.quantile(g / 100.0) for g in ps}


_CHILD_TYPES = {"counter": CounterChild, "gauge": GaugeChild,
                "histogram": HistogramChild}


class MetricFamily:
    """A named metric + its label dimensions; ``labels(...)`` vends the
    per-series child. Unlabeled families proxy the child's methods so
    ``registry.counter("x").inc()`` reads naturally."""

    def __init__(self, name: str, kind: str, help: str = "",
                 label_names: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.name = name
        self.kind = kind
        self.help = help
        self.label_names = tuple(label_names)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._lock = threading.Lock()
        self._children: Dict[LabelValues, _Child] = {}

    def labels(self, *values, **kw) -> _Child:
        if kw:
            if values:
                raise ValueError("pass label values positionally OR by "
                                 "keyword, not both")
            values = tuple(str(kw[n]) for n in self.label_names)
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name} expects labels {self.label_names}, "
                f"got {values}")
        with self._lock:
            child = self._children.get(values)
            if child is None:
                child = _CHILD_TYPES[self.kind](self, values)
                self._children[values] = child
            return child

    def children(self) -> List[_Child]:
        with self._lock:
            return list(self._children.values())

    # -- unlabeled convenience proxies ----------------------------------
    def _default(self) -> _Child:
        if self.label_names:
            raise ValueError(
                f"{self.name} has labels {self.label_names}; call "
                f".labels(...) first")
        return self.labels()

    def inc(self, value: Number = 1):
        self._default().inc(value)

    def dec(self, value: Number = 1):
        self._default().dec(value)          # gauges only

    def set(self, value: Number):
        self._default().set(value)          # gauges only

    def observe(self, value: Number):
        self._default().observe(value)      # histograms only

    @property
    def value(self) -> float:
        return self._default().value

    @property
    def count(self) -> int:
        return self._default().count

    @property
    def sum(self) -> float:
        return self._default().sum

    @property
    def mean(self) -> float:
        return self._default().mean

    def quantile(self, q: float) -> float:
        return self._default().quantile(q)

    def percentiles(self, ps=(50, 90, 99)) -> Dict[str, float]:
        return self._default().percentiles(ps)

    def bucket_counts(self):
        return self._default().bucket_counts()


class MetricRegistry:
    """Process-wide metric store (the StatRegistry superset). One
    default instance (``default_registry()``) backs core.monitor's
    STAT_ADD facade and everything the exporters dump; tests construct
    private registries to stay isolated."""

    _instance: Optional["MetricRegistry"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._mu = threading.Lock()
        self._families: Dict[str, MetricFamily] = {}

    @classmethod
    def instance(cls) -> "MetricRegistry":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    # -- family constructors (get-or-create, idempotent) ----------------
    def _family(self, name: str, kind: str, help: str,
                label_names: Sequence[str],
                buckets: Sequence[float] = DEFAULT_BUCKETS
                ) -> MetricFamily:
        with self._mu:
            fam = self._families.get(name)
            if fam is None:
                fam = MetricFamily(name, kind, help, label_names, buckets)
                self._families[name] = fam
                return fam
        if fam.kind != kind:
            raise ValueError(
                f"metric {name!r} already registered as {fam.kind}, "
                f"requested {kind}")
        if tuple(label_names) != fam.label_names:
            raise ValueError(
                f"metric {name!r} registered with labels "
                f"{fam.label_names}, requested {tuple(label_names)}")
        return fam

    def counter(self, name: str, help: str = "",
                label_names: Sequence[str] = ()) -> MetricFamily:
        return self._family(name, "counter", help, label_names)

    def gauge(self, name: str, help: str = "",
              label_names: Sequence[str] = ()) -> MetricFamily:
        return self._family(name, "gauge", help, label_names)

    def histogram(self, name: str, help: str = "",
                  label_names: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS
                  ) -> MetricFamily:
        return self._family(name, "histogram", help, label_names, buckets)

    def get(self, name: str) -> Optional[MetricFamily]:
        with self._mu:
            return self._families.get(name)

    def families(self) -> List[MetricFamily]:
        with self._mu:
            return list(self._families.values())

    def unregister(self, name: str) -> None:
        with self._mu:
            self._families.pop(name, None)

    def reset(self) -> None:
        """Drop every family — test isolation and the StatRegistry
        ``reset()`` contract."""
        with self._mu:
            self._families.clear()

    # -- flat readout ----------------------------------------------------
    def snapshot(self, percentiles: Sequence[float] = (50, 90, 99)
                 ) -> Dict[str, float]:
        """Flatten to ``{series_name: scalar}``: counters/gauges report
        their value; histograms expand to _count/_sum/_mean/_pNN. The
        shape BENCH rows and the JSONL reporter embed."""
        out: Dict[str, float] = {}
        for fam in self.families():
            for child in fam.children():
                key = fam.name + _format_labels(fam.label_names,
                                                child.label_values)
                if fam.kind in ("counter", "gauge"):
                    out[key] = child.value
                else:
                    out[key + "_count"] = child.count
                    out[key + "_sum"] = child.sum
                    out[key + "_mean"] = child.mean
                    for p in percentiles:
                        out[f"{key}_p{p:g}"] = child.quantile(p / 100.0)
        return out


def default_registry() -> MetricRegistry:
    return MetricRegistry.instance()
