"""slodiff: judge one SLO/BENCH artifact against another, inside noise bands.

ROADMAP item 6's release-flow cap: "the driver runs `loadgen --scenario
mixed_64p --backend proc` per PR and diffs SLO_r0N.json like BENCH, with
aa_skew_pct-style noise bands wired into the comparison — observability
PRs stop being unjudged by definition." This module is that diff.

Usage::

    python -m tools.slodiff SLO_r10.json SLO_r14.json [--noise-band-pct 20]
    python -m tools.slodiff SLO_r14.json SLO_r17.json --json

Verdict vocabulary:

- **PASS**    — no worse than the baseline (or better) on this item.
- **WEATHER** — worse, but inside the noise band: the same-code A/A skew
  measured on the box (``aa_skew_pct`` when the artifacts carry it, the
  ``--noise-band-pct`` knob otherwise) is larger than the move, so the
  delta is indistinguishable from weather.
- **REGRESS** — worse beyond the band, or a hard status flip
  (an objective that PASSed the baseline now FAILs).

The overall verdict is the worst item verdict; ``NO_DATA`` items (an
objective idle in either window) judge nothing. Exit code: 0 for
PASS/WEATHER, 1 for REGRESS — WEATHER is reported loudly but does not
fail a release, because failing on weather just teaches people to rerun
until green.

Artifact kinds are sniffed: an SLO report carries ``objectives`` (+
``throughput``); a BENCH artifact carries ``metric``/``value`` (+
config sub-rates), possibly wrapped under ``parsed`` by the driver.
"""

from __future__ import annotations

import argparse
import json
import sys

DEFAULT_BAND_PCT = 20.0

PASS, WEATHER, REGRESS, NO_DATA = "PASS", "WEATHER", "REGRESS", "NO_DATA"
_RANK = {NO_DATA: -1, PASS: 0, WEATHER: 1, REGRESS: 2}

# BENCH config blocks judged by their rate (higher = better); the headline
# "value" is judged the same way.
_BENCH_RATE_KEY = "record_batches_per_sec"


def _load(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    # driver wrapping: {"n":…, "cmd":…, "parsed": {…}}
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        doc = doc["parsed"]
    return doc


def _verdict_lower_better(old, new, band_pct: float) -> tuple[str, float]:
    """Latency-style item: a higher new value is worse. Returns
    (verdict, delta_pct); delta > 0 means worse."""
    if not old or old <= 0 or new is None:
        return NO_DATA, 0.0
    delta_pct = (new - old) / old * 100.0
    if delta_pct <= 0:
        return PASS, delta_pct
    return (WEATHER if delta_pct <= band_pct else REGRESS), delta_pct


def _verdict_higher_better(old, new, band_pct: float) -> tuple[str, float]:
    """Throughput-style item: a lower new value is worse."""
    if not old or old <= 0 or new is None:
        return NO_DATA, 0.0
    delta_pct = (new - old) / old * 100.0
    if delta_pct >= 0:
        return PASS, delta_pct
    return (WEATHER if -delta_pct <= band_pct else REGRESS), delta_pct


def _worst(verdicts) -> str:
    worst = NO_DATA
    any_v = False
    for v in verdicts:
        any_v = True
        if _RANK[v] > _RANK[worst]:
            worst = v
    # a diff that judged NOTHING must not read as a clean pass — an
    # all-NO_DATA comparison (wrong artifact pair, every objective idle)
    # says so instead
    return worst if any_v else NO_DATA


# ================================================================ SLO diff
def diff_slo(old: dict, new: dict, band_pct: float) -> dict:
    """Objective-by-objective diff of two SLO_r0N.json reports."""
    old_by = {o["name"]: o for o in old.get("objectives", [])}
    items = []
    for o in new.get("objectives", []):
        name = o["name"]
        base = old_by.get(name)
        entry = {
            "name": name,
            "metric": o.get("metric"),
            **({"labels": o["labels"]} if o.get("labels") else {}),
            "quantile": o.get("quantile"),
            "threshold_ms": o.get("threshold_ms"),
            "old_status": (base or {}).get("status"),
            "new_status": o.get("status"),
            "old_observed_ms": (base or {}).get("observed_ms"),
            "new_observed_ms": o.get("observed_ms"),
        }
        if base is not None and (
            base.get("metric") != o.get("metric")
            or (base.get("labels") or {}) != (o.get("labels") or {})
        ):
            # the NAME matches but the series does not (a relabeled
            # stage, a repointed metric): comparing the observed values
            # would be apples-to-oranges — say so instead of judging
            entry["verdict"] = NO_DATA
            entry["detail"] = (
                "objective series changed: "
                f"{base.get('metric')}{base.get('labels') or {}} -> "
                f"{o.get('metric')}{o.get('labels') or {}}"
            )
        elif base is None or "NO_DATA" in (o.get("status"), base.get("status")):
            entry["verdict"] = NO_DATA
            entry["detail"] = (
                "no baseline objective" if base is None
                else "objective idle in one window"
            )
        elif base.get("status") == "PASS" and o.get("status") == "FAIL":
            # a hard flip is a regression regardless of the band: the SLO
            # threshold is the contract, not a point estimate
            entry["verdict"] = REGRESS
            entry["detail"] = "status flipped PASS -> FAIL"
            entry["delta_pct"] = round(
                _verdict_lower_better(
                    base.get("observed_ms"), o.get("observed_ms"), band_pct
                )[1], 2,
            )
        else:
            v, delta = _verdict_lower_better(
                base.get("observed_ms"), o.get("observed_ms"), band_pct
            )
            if base.get("status") == "FAIL" and o.get("status") == "PASS":
                v = PASS  # recovered: latency delta is secondary
                entry["detail"] = "status recovered FAIL -> PASS"
            entry["verdict"] = v
            entry["delta_pct"] = round(delta, 2)
        items.append(entry)
    # throughput: the scenario's offered/served rates (higher = better)
    thr_items = []
    for key in ("produced_records_per_s", "produce_ops_per_s"):
        old_v = (old.get("throughput") or {}).get(key)
        new_v = (new.get("throughput") or {}).get(key)
        v, delta = _verdict_higher_better(old_v, new_v, band_pct)
        thr_items.append({
            "name": key, "verdict": v, "delta_pct": round(delta, 2),
            "old": old_v, "new": new_v,
        })
    verdict = _worst(
        [i["verdict"] for i in items] + [i["verdict"] for i in thr_items]
    )
    out = {
        "kind": "slo",
        "objectives": items,
        "throughput": thr_items,
        "verdict": verdict,
    }
    # load-confounding caveat: closed-loop latency scales with offered
    # load, so "p99 worse while throughput ROSE beyond the band" is an
    # ambiguous reading, not clean evidence of a code regression — say so
    # on the diff's face (the judge should re-run at matched load or
    # bracket with a same-code A/A control)
    prod = next(
        (t for t in thr_items if t["name"] == "produced_records_per_s"),
        None,
    )
    if (
        prod is not None
        and prod["verdict"] == PASS
        and (prod.get("delta_pct") or 0) > band_pct
        and any(i["verdict"] == REGRESS for i in items)
    ):
        out["caveats"] = [
            f"candidate served {prod['delta_pct']:+.1f}% more offered "
            f"load than the baseline (closed-loop clients): latency "
            f"REGRESS verdicts above are load-confounded — judge at "
            f"matched load or against a same-code A/A control"
        ]
    return out


# ================================================================ BENCH diff
def _bench_rates(doc: dict) -> dict[str, float]:
    rates = {}
    if isinstance(doc.get("value"), (int, float)):
        rates["headline"] = float(doc["value"])
    for key, sub in doc.items():
        if isinstance(sub, dict) and isinstance(
            sub.get(_BENCH_RATE_KEY), (int, float)
        ):
            rates[key] = float(sub[_BENCH_RATE_KEY])
    return rates


def diff_bench(old: dict, new: dict, band_pct: float | None) -> dict:
    """Config-by-config diff of two BENCH_r0N.json artifacts. The band
    defaults to the LARGER of the two runs' measured same-code A/A skew
    (each artifact judges with the noise of its own box/day)."""
    aa = [
        float(d["aa_skew_pct"])
        for d in (old, new)
        if isinstance(d.get("aa_skew_pct"), (int, float))
    ]
    band = band_pct if band_pct is not None else (
        max(aa) if aa else DEFAULT_BAND_PCT
    )
    old_rates, new_rates = _bench_rates(old), _bench_rates(new)
    items = []
    for key in sorted(set(old_rates) | set(new_rates)):
        v, delta = _verdict_higher_better(
            old_rates.get(key), new_rates.get(key), band
        )
        items.append({
            "name": key, "verdict": v, "delta_pct": round(delta, 2),
            "old": old_rates.get(key), "new": new_rates.get(key),
        })
    return {
        "kind": "bench",
        "band_pct": round(band, 2),
        "aa_skew_pcts": aa,
        "configs": items,
        "verdict": _worst(i["verdict"] for i in items),
    }


# ================================================================ entry
def diff_artifacts(
    old: dict, new: dict, band_pct: float | None = None
) -> dict:
    """Sniff the artifact kind and diff. ``band_pct=None`` lets BENCH
    artifacts use their own measured A/A skew; SLO reports carry no A/A
    control, so they take the default band."""
    if "objectives" in new or "objectives" in old:
        out = diff_slo(
            old, new, band_pct if band_pct is not None else DEFAULT_BAND_PCT
        )
        out["band_pct"] = (
            band_pct if band_pct is not None else DEFAULT_BAND_PCT
        )
    elif "value" in new or "value" in old or "metric" in new:
        out = diff_bench(old, new, band_pct)
    else:
        raise ValueError(
            "unrecognized artifact shape: neither an SLO report "
            "(objectives) nor a BENCH artifact (metric/value)"
        )
    out["old_scenario"] = old.get("scenario") or old.get("metric")
    out["new_scenario"] = new.get("scenario") or new.get("metric")
    return out


def render(diff: dict, old_path: str, new_path: str) -> str:
    lines = [
        f"slodiff {old_path} -> {new_path}  "
        f"[band {diff.get('band_pct', '?')}%]",
    ]
    rows = diff.get("objectives") or []
    for r in rows:
        if r["verdict"] == NO_DATA:
            lines.append(
                f"  {r['verdict']:<8}{r['name']:<28}{r.get('detail', '')}"
            )
            continue
        lines.append(
            f"  {r['verdict']:<8}{r['name']:<28}"
            f"{r.get('old_observed_ms')}ms -> {r.get('new_observed_ms')}ms "
            f"({r.get('delta_pct', 0):+.1f}%)"
            + (f"  [{r['detail']}]" if r.get("detail") else "")
        )
    for r in diff.get("throughput") or []:
        lines.append(
            f"  {r['verdict']:<8}{r['name']:<28}"
            f"{r.get('old')} -> {r.get('new')} "
            f"({r.get('delta_pct', 0):+.1f}%)"
        )
    for r in diff.get("configs") or []:
        lines.append(
            f"  {r['verdict']:<8}{r['name']:<28}"
            f"{r.get('old')} -> {r.get('new')} rb/s "
            f"({r.get('delta_pct', 0):+.1f}%)"
        )
    for c in diff.get("caveats") or []:
        lines.append(f"  CAVEAT: {c}")
    lines.append(f"verdict: {diff['verdict']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("old", help="baseline artifact (SLO_r0N.json / BENCH)")
    p.add_argument("new", help="candidate artifact")
    p.add_argument(
        "--noise-band-pct", type=float, default=None, metavar="PCT",
        help=f"worse-but-within-this-band reads WEATHER, beyond it "
             f"REGRESS (default: the artifacts' own aa_skew_pct for "
             f"BENCH, {DEFAULT_BAND_PCT}%% for SLO reports)",
    )
    p.add_argument("--json", action="store_true", help="raw JSON diff")
    args = p.parse_args(argv)
    diff = diff_artifacts(
        _load(args.old), _load(args.new), args.noise_band_pct
    )
    if args.json:
        print(json.dumps(diff, indent=2, sort_keys=True))
    else:
        print(render(diff, args.old, args.new))
    return 1 if diff["verdict"] == REGRESS else 0


if __name__ == "__main__":
    sys.exit(main())
