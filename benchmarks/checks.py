"""The comparison that decides ``correct`` and the reduction from what the
clients saw to the end-to-end numbers. Pure functions of lists and times:
no socket, no broker, nothing of the program.
"""

from __future__ import annotations

import bisect
import json
import math

import wire


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    if not sorted_values:
        raise ValueError("percentile of no samples")
    k = max(math.ceil(q / 100.0 * len(sorted_values)) - 1, 0)
    return sorted_values[k]


def decode_tail(batches: dict[int, list[tuple[float, int, bytes]]], crc32c=None):
    """What the consumer fetched -> (values[p], arrivals[p]); arrivals[p] is
    [(time, records fetched up to and including that batch)], ascending."""
    got: dict[int, list[bytes | None]] = {}
    arrivals: dict[int, list[tuple[float, int]]] = {}
    for p, part in batches.items():
        values: list[bytes | None] = []
        arr = []
        for t, _count, raw in part:
            values.extend(wire.decode_batch(raw, crc32c)[1])
            arr.append((t, len(values)))
        got[p], arrivals[p] = values, arr
    return got, arrivals


def compare(expected: dict[int, list[bytes]], got: dict[int, list[bytes | None]]) -> dict:
    """Record for record, per partition, in order, exactly once, byte-equal.
    Every count below has the limit 0."""
    missing = extra = different = 0
    first = None
    for p in sorted(expected):
        e, g = expected[p], got.get(p, [])
        if e == g:
            continue
        n = min(len(e), len(g))
        bad = [k for k in range(n) if e[k] != g[k]]
        different += len(bad)
        missing += max(len(e) - len(g), 0)
        extra += max(len(g) - len(e), 0)
        if first is None:
            first = {"partition": p, "index": bad[0] if bad else n,
                     "expected": len(e), "got": len(g),
                     "same_records_in_another_order": sorted(e) == sorted(x or b"" for x in g)}
    return {
        "records_expected": sum(map(len, expected.values())),
        "records_fetched": sum(map(len, got.values())),
        "records_different": different,
        "records_missing": missing,
        "records_extra": extra,
        "first_mismatch": first,
    }


def compare_ok(result: dict) -> bool:
    return not (result["records_different"] or result["records_missing"]
                or result["records_extra"])


def control_verdicts(expected, got) -> dict:
    """The control: the fetched output with one guarantee of the
    configuration broken in the middle of the fullest partition, as a
    system that lost, repeated, reordered or corrupted one record would
    have left it. Each must come out as not correct (True here = caught)."""
    p = max(got, key=lambda q: len(got[q]))
    g = got[p]
    if len(g) < 3:
        return {"too_small": True}
    k = len(g) // 2
    flipped = bytearray(g[k])
    flipped[len(flipped) // 2] ^= 0x01
    broken = {
        "one_missing": g[:k] + g[k + 1 :],
        "one_duplicated": g[: k + 1] + g[k:],
        "one_reordered": g[:k] + [g[k + 1], g[k]] + g[k + 2 :],
        "one_flipped_byte": g[:k] + [bytes(flipped)] + g[k + 1 :],
    }
    return {
        name: not compare_ok(compare(expected, {**got, p: values}))
        for name, values in broken.items()
    }


def passed_at(kept: list[int], arrivals: list[tuple[float, int]], t: float,
              n_inputs: int | None = None) -> int:
    """Input records of one partition whose outcome a consumer has seen by
    time ``t``: everything up to and including the input of the newest
    output fetched (the records before it that the reference drops are
    passed too). With ``n_inputs``, a partition whose every kept output has
    arrived has passed all its inputs: nothing more will ever show."""
    k = bisect.bisect_right(arrivals, (t, math.inf))
    fetched = arrivals[k - 1][1] if k else 0
    if n_inputs is not None and fetched >= len(kept):
        return n_inputs
    if fetched == 0:
        return 0
    return kept[min(fetched, len(kept)) - 1] + 1


def transform_rate(kept, arrivals, n_inputs: dict[int, int], t0: float, t1: float,
                   fixed_work: bool, t_complete: float | None) -> dict:
    """records/s. Fixed work (a backlog of N): N / T when everything was
    passed T seconds after t0 and before the window closed, else the
    records passed by t1 over the window. A live stream: records passed
    between t0 and t1 over the window."""
    seconds = t1 - t0
    if fixed_work:
        total = sum(n_inputs.values())
        if t_complete is not None and t_complete <= t1:
            return {"value": total / (t_complete - t0), "records": total,
                    "seconds": t_complete - t0, "drained": True}
        done = sum(passed_at(kept[p], arrivals[p], t1, n_inputs[p]) for p in kept)
        return {"value": done / seconds, "records": done, "seconds": seconds,
                "drained": False}
    done = sum(
        passed_at(kept[p], arrivals[p], t1) - passed_at(kept[p], arrivals[p], t0)
        for p in kept
    )
    return {"value": done / seconds, "records": done, "seconds": seconds,
            "drained": False}


def reduce_window(*, kept, acked, arrivals, producer_logs, stream, records_per_batch,
                  t0, t1, fixed_work, t_complete) -> dict:
    """End-to-end numbers of one window from the clients' records."""
    out: dict = {}
    n_inputs = {p: acked.get(p, 0) for p in kept}
    out["transform_rate"] = transform_rate(
        kept, arrivals, n_inputs, t0, t1, fixed_work, t_complete
    )
    # producer side: batches due inside the window
    due: dict[tuple[int, int], float] = {}
    ack_ms, lag_ms = [], []
    offered = errors = 0
    # what the producers fed: acknowledged batches due in the window or, for
    # fixed work, seeded into the backlog before it
    fed_batches = fed_bytes = 0
    for path in producer_logs:
        with open(path) as f:
            for name, p, k, t_due, t_sent, t_ack, err, batch_bytes in json.load(f):
                if name != stream:
                    continue
                in_window = t0 <= t_due < t1
                if err == 0 and (fixed_work or in_window):
                    fed_batches += 1
                    fed_bytes += batch_bytes
                if not in_window:
                    continue
                offered += 1
                due[(p, k)] = t_due
                lag_ms.append((t_sent - t_due) * 1e3)
                if err == 0:
                    ack_ms.append((t_ack - t_due) * 1e3)
                else:
                    errors += 1
    out["batches_offered"] = offered
    out["produce_errors"] = errors
    if fed_batches:
        out["input_wire_bytes_per_rec"] = fed_bytes / (fed_batches * records_per_batch)
    if not due:
        return out
    # consumer side: each kept record of those batches, due -> fetched
    e2e_ms = []
    unseen = 0
    for p, indices in kept.items():
        arr = arrivals[p]
        counts = [c for _, c in arr]
        for j, i in enumerate(indices):
            t_due = due.get((p, i // records_per_batch))
            if t_due is None:
                continue
            k = bisect.bisect_right(counts, j)
            if k == len(arr):
                unseen += 1
                continue
            e2e_ms.append((arr[k][0] - t_due) * 1e3)
    e2e_ms.sort()
    ack_ms.sort()
    lag_ms.sort()
    out["kept_in_window"] = len(e2e_ms) + unseen
    out["kept_unseen"] = unseen
    out["produce_rate"] = (offered - errors) * records_per_batch / (t1 - t0)
    if e2e_ms:
        out["e2e_ms"] = {str(q): percentile(e2e_ms, q) for q in (50, 95, 99)}
    if ack_ms:
        out["ack_ms"] = {str(q): percentile(ack_ms, q) for q in (50, 95, 99)}
    out["generator_lag_ms"] = {str(q): percentile(lag_ms, q) for q in (50, 99)}
    return out
