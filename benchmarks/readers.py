"""Per-layer metrics: each is one data file under ``layer_metrics/`` naming
its layer, unit, source, the end-to-end metric it moves, the traffic kinds
that report it, and how it is read. The
readers below are the source kinds; a reader that finds nothing to read
returns None and the metric is left out of the line.

- ``histogram_delta``: mean of a Prometheus histogram over the window,
  ``delta(_sum) / delta(_count) * scale``.
- ``stats_ratio``: ``sum(delta(num keys)) / sum(delta(den keys)) * scale``
  over ``TpuEngine.stats()``; ``den`` may be ``"window_s"`` or ``"one"``.
- ``client``: a number the benchmark's own clients took (dotted path).
- ``trace``: a number of the profiler-trace reduction (``trace_reduce.py``).
"""

from __future__ import annotations

import json
import os

PREFIX = "redpanda_tpu_"


def parse_prometheus(text: str) -> dict[str, float]:
    """series (name with its labels, prefix dropped) -> value."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        if series.startswith(PREFIX):
            series = series[len(PREFIX):]
        try:
            out[series] = float(value)
        except ValueError:
            continue
    return out


def metric_total(metrics: dict[str, float], name: str, labels: dict | None = None) -> float:
    """Sum over every series of ``name`` whose labels include ``labels``."""
    total = 0.0
    for series, value in metrics.items():
        base, _, rest = series.partition("{")
        if base != name:
            continue
        if labels and not all(f'{k}="{v}"' in rest for k, v in labels.items()):
            continue
        total += value
    return total


def _histogram_delta(d: dict, ctx: dict):
    def delta(suffix: str) -> float:
        name = d["metric"] + suffix
        return (metric_total(ctx["after"]["metrics"], name, d.get("labels"))
                - metric_total(ctx["before"]["metrics"], name, d.get("labels")))

    count = delta("_count")
    if count <= 0:
        return None
    return delta("_sum") / count * d.get("scale", 1.0)


def _stats_ratio(d: dict, ctx: dict):
    def delta(keys) -> float:
        if keys == "window_s":
            return ctx["window_s"]
        if keys == "one":
            return 1.0
        return sum(
            float(ctx["after"]["stats"].get(k, 0.0)) - float(ctx["before"]["stats"].get(k, 0.0))
            for k in keys
        )

    den = delta(d["den"])
    if den <= 0:
        return None
    return delta(d["num"]) / den * d.get("scale", 1.0)


def dotted(obj, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj


def _client(d: dict, ctx: dict):
    return dotted(ctx["client"], d["path"])


def _trace(d: dict, ctx: dict):
    if ctx["trace"] is None:
        return None
    return dotted(ctx["trace"], d["path"])


KINDS = {
    "histogram_delta": _histogram_delta,
    "stats_ratio": _stats_ratio,
    "client": _client,
    "trace": _trace,
}


def load_definitions(directory: str) -> list[dict]:
    out = []
    for fn in sorted(os.listdir(directory)):
        if fn.endswith(".json"):
            with open(os.path.join(directory, fn)) as f:
                d = json.load(f)
            if d["name"] + ".json" != fn:
                raise ValueError(f"{fn} defines {d['name']!r}")
            out.append(d)
    return out


def read_all(directory: str, *, kind: str, before: dict, after: dict,
             client: dict, trace: dict | None, window_s: float) -> dict:
    """{name: {"value", "unit"}} for every metric of this traffic kind whose
    reader found something."""
    ctx = {"before": before, "after": after, "client": client, "trace": trace,
           "window_s": window_s}
    out = {}
    for d in load_definitions(directory):
        if kind not in d["traffic"]:
            continue
        value = KINDS[d["read"]["kind"]](d["read"], ctx)
        if value is not None:
            out[d["name"]] = {"value": value, "unit": d["unit"]}
    return out
