"""rpk — the operator CLI.

Parity with src/go/rpk (pkg/cli/cmd): broker lifecycle, topic CRUD +
produce/consume, ACLs, users, wasm (transform) deploy/remove/generate,
cluster info, config get/set, debug bundle, generate
grafana-dashboard/prometheus-config, and tune (the autotune story —
reported as informational here: kernel tuning is outside this runtime's
scope, docs/www/autotune.md).

Usage: python -m redpanda_tpu <command> ...   (or the `rpk` console entry)
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import json
import os
import subprocess
import sys

DEFAULT_BROKERS = "127.0.0.1:9092"
DEFAULT_ADMIN = "127.0.0.1:9644"


def _parse_brokers(s: str) -> list[tuple[str, int]]:
    out = []
    for hp in s.split(","):
        host, _, port = hp.strip().partition(":")
        out.append((host, int(port or 9092)))
    return out


async def _client(args):
    from redpanda_tpu.kafka.client.client import KafkaClient

    sasl = (args.user, args.password) if getattr(args, "user", None) else None
    return await KafkaClient(_parse_brokers(args.brokers), sasl=sasl).connect()


async def _admin_request(args, method: str, path: str, body=None, query=None):
    import json as _json
    import urllib.parse

    from redpanda_tpu.http import HttpClient

    # user-supplied segments (names etc.) must be percent-encoded for the
    # request line; structural separators stay intact. Query VALUES go via
    # `query` (urlencode: one correct encoding) — pre-encoding them into
    # `path` would double-encode '%' here.
    path = urllib.parse.quote(path, safe="/?&=")
    if query:
        path += ("&" if "?" in path else "?") + urllib.parse.urlencode(query)
    async with HttpClient(f"http://{args.admin_api}") as c:
        headers = {}
        payload = b""
        if body is not None:
            payload = _json.dumps(body).encode()
            headers["content-type"] = "application/json"
        resp = await c.request(method, path, headers=headers, body=payload)
        try:
            return resp.status, _json.loads(resp.body)
        except Exception:
            return resp.status, resp.body.decode("utf-8", "replace")


# ================================================================ redpanda start
async def cmd_start(args) -> int:
    import logging

    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(levelname)s %(name)s %(message)s"
    )
    from redpanda_tpu.app import Application
    from redpanda_tpu.config import Configuration

    cfg = Configuration()
    if args.config:
        cfg.load_yaml(args.config)
    for kv in args.set or []:
        k, _, v = kv.partition("=")
        cfg.set(k, v)
    app = await Application(cfg).start()
    print(
        f"redpanda_tpu started: kafka {cfg.kafka_api_host}:{app.kafka_server.port}, "
        f"admin {cfg.admin_api_host}:{app.admin.port}"
    )
    try:
        await app.run_forever()
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    return 0


# ================================================================ topics
async def cmd_topic(args) -> int:
    client = await _client(args)
    try:
        if args.topic_cmd == "create":
            configs = dict(kv.split("=", 1) for kv in (args.topic_config or []))
            await client.create_topic(
                args.name, partitions=args.partitions,
                replication=args.replicas, configs=configs or None,
            )
            print(f"created topic {args.name}")
        elif args.topic_cmd == "delete":
            await client.delete_topic(args.name)
            print(f"deleted topic {args.name}")
        elif args.topic_cmd == "list":
            md = await client.refresh_metadata()
            for t in sorted(md["topics"], key=lambda t: t["name"]):
                if t["error_code"] == 0:
                    print(f"{t['name']}\t{len(t.get('partitions') or [])} partitions")
        elif args.topic_cmd == "describe":
            md = await client.refresh_metadata([args.name], auto_create=False)
            t = next((t for t in md["topics"] if t["name"] == args.name), None)
            if t is None or t["error_code"] != 0:
                print(f"topic not found: {args.name}", file=sys.stderr)
                return 1
            print(json.dumps(t, indent=2))
        elif args.topic_cmd == "produce":
            data = sys.stdin.buffer.read() if args.value == "-" else args.value.encode()
            off = await client.produce(args.name, args.partition, [(args.key.encode() if args.key else None, data)])
            print(f"produced to {args.name}/{args.partition} at offset {off}")
        elif args.topic_cmd == "consume":
            offset = args.offset
            if offset < 0:
                offset = await client.earliest_offset(args.name, args.partition)
            n = 0
            while n < args.num:
                batches, hwm = await client.fetch(args.name, args.partition, offset, max_wait_ms=500)
                if not batches:
                    if offset >= hwm:
                        break
                    continue
                for b in batches:
                    for r in b.records():
                        print(json.dumps({
                            "offset": b.header.base_offset + r.offset_delta,
                            "key": r.key.decode("utf-8", "replace") if r.key else None,
                            "value": r.value.decode("utf-8", "replace") if r.value else None,
                        }))
                        n += 1
                        if n >= args.num:
                            break
                    offset = b.last_offset + 1
        return 0
    finally:
        await client.close()


# ================================================================ acl
async def cmd_acl(args) -> int:
    from redpanda_tpu.kafka.protocol import messages as m
    from redpanda_tpu.security.acl import (
        AclOperation, AclPermission, PatternType, ResourceType,
    )

    client = await _client(args)
    try:
        conn = await client.any_connection()
        if args.acl_cmd == "create":
            resp = await conn.request(m.CREATE_ACLS, {"creations": [{
                "resource_type": int(ResourceType[args.resource]),
                "resource_name": args.resource_name,
                "resource_pattern_type": int(PatternType.literal),
                "principal": args.principal if args.principal.startswith("User:") else f"User:{args.principal}",
                "host": args.host,
                "operation": int(AclOperation[args.operation]),
                "permission_type": int(AclPermission.deny if args.deny else AclPermission.allow),
            }]})
            code = resp["results"][0]["error_code"]
            print("created" if code == 0 else f"failed: error {code}")
            return 0 if code == 0 else 1
        if args.acl_cmd == "list":
            resp = await conn.request(m.DESCRIBE_ACLS, {
                "resource_type_filter": int(ResourceType.any),
                "resource_name_filter": None,
                "pattern_type_filter": int(PatternType.any),
                "principal_filter": None, "host_filter": None,
                "operation": int(AclOperation.any),
                "permission_type": int(AclPermission.any),
            })
            for res in resp["resources"]:
                for acl in res["acls"]:
                    print(
                        f"{ResourceType(res['resource_type']).name}:{res['resource_name']}\t"
                        f"{acl['principal']}\t{AclOperation(acl['operation']).name}\t"
                        f"{AclPermission(acl['permission_type']).name}"
                    )
        return 0
    finally:
        await client.close()


# ================================================================ wasm (transforms)
_TRANSFORM_TEMPLATE = {
    "name": "my-transform",
    "input_topics": ["source-topic"],
    # TransformSpec wire form (ops/transforms.py to_json); this example
    # keeps records containing `"level":"error"` and projects two fields
    "spec": {
        "name": "errors-only",
        "ops": [
            {"op": "filter_contains", "pattern": '"level":"error"',
             "negate": False, "nonnum_suffix": False},
            {"op": "map_project", "fields": [
                {"kind": "int", "key": "code"},
                {"kind": "str", "key": "msg", "max_len": 32},
            ]},
        ],
    },
}


async def cmd_wasm(args) -> int:
    if args.wasm_cmd == "generate":
        print(json.dumps(_TRANSFORM_TEMPLATE, indent=2))
        return 0
    from redpanda_tpu.coproc import wasm_event
    from redpanda_tpu.models.fundamental import COPROC_INTERNAL_TOPIC

    client = await _client(args)
    try:
        if args.wasm_cmd == "deploy":
            # rpk shares the reactor checker with the broker: read the spec
            # off-loop even though the CLI loop has nothing else scheduled
            doc = json.loads(await asyncio.to_thread(_read_text, args.file))
            if "py_source" in doc:
                # sandboxed python transform (validated client-side here
                # and again on every broker at enable time)
                rec = wasm_event.make_py_deploy_record(
                    doc["name"], doc["py_source"], doc["input_topics"],
                    policy=doc.get("policy", "skip"),
                )
            else:
                rec = wasm_event.make_deploy_record(
                    doc["name"], json.dumps(doc["spec"]), doc["input_topics"]
                )
        else:  # remove
            rec = wasm_event.make_remove_record(args.name)
        from redpanda_tpu.models.record import RecordBatch

        batch = wasm_event.deploy_batch([rec])
        await client.produce_batches(COPROC_INTERNAL_TOPIC, 0, [batch])
        print(f"{args.wasm_cmd} event produced to {COPROC_INTERNAL_TOPIC}")
        return 0
    finally:
        await client.close()


# ================================================================ cluster / user / config
async def cmd_cluster(args) -> int:
    if getattr(args, "cluster_cmd", None) == "rebalance":
        # each node sheds its own excess leaderships; hit every admin given
        total = []
        failures = 0
        for admin in (args.admin_apis or args.admin_api).split(","):
            ns = argparse.Namespace(**{**vars(args), "admin_api": admin.strip()})
            status, body = await _admin_request(
                ns, "POST", "/v1/partitions/rebalance_leaders"
            )
            if status != 200:
                print(f"{admin}: error {status} {body}", file=sys.stderr)
                failures += 1
                continue
            total.extend(body.get("transferred", []))
            print(f"{admin}: moved {len(body.get('transferred', []))}, "
                  f"leader counts {body.get('leader_counts')}")
        print(f"total transferred: {len(total)}")
        # nonzero when ANY node could not rebalance: scripted callers must
        # not read a partial pass as success
        return 1 if failures else 0
    status, brokers = await _admin_request(args, "GET", "/v1/brokers")
    if status != 200:
        print(f"admin api error {status}", file=sys.stderr)
        return 1
    print(f"{'ID':<5}{'HOST':<20}{'KAFKA':<22}{'STATUS':<10}")
    for b in brokers:
        print(
            f"{b['node_id']:<5}{b['host']:<20}"
            f"{b['kafka_host']}:{b['kafka_port']:<15}{b['membership_status']:<10}"
        )
    return 0


async def cmd_user(args) -> int:
    if args.user_cmd == "create":
        status, body = await _admin_request(
            args, "POST", "/v1/security/users",
            {"username": args.name, "password": args.new_password,
             "algorithm": args.mechanism},
        )
    elif args.user_cmd == "delete":
        status, body = await _admin_request(args, "DELETE", f"/v1/security/users/{args.name}")
    else:  # list
        status, body = await _admin_request(args, "GET", "/v1/security/users")
    print(json.dumps(body, indent=2) if status == 200 else f"error {status}: {body}")
    return 0 if status == 200 else 1


async def cmd_config(args) -> int:
    if args.config_cmd == "get":
        status, body = await _admin_request(args, "GET", "/v1/config")
        if status != 200:
            return 1
        if args.key:
            print(json.dumps(body.get(args.key)))
        else:
            print(json.dumps(body, indent=2, sort_keys=True))
        return 0
    print("config set requires editing the yaml + restart (needs_restart properties)", file=sys.stderr)
    return 1


# ================================================================ debug / generate / tune
def _write_text(path: str, data: str) -> None:
    """Blocking file write, called via asyncio.to_thread from the async
    CLI commands (RCT103: no blocking I/O on the loop)."""
    with open(path, "w") as f:
        f.write(data)


async def cmd_debug(args) -> int:
    """debug diagnostics: bundle (tar.gz of admin state), trace (render
    the broker's recent pandaprobe spans), coproc (engine breaker +
    fault-domain stats), governor (decision journal + per-domain posture),
    slo (objective verdicts + breach exemplars), failpoints (honey-badger
    arm/disarm)."""
    import io
    import tarfile
    import time

    if args.debug_cmd == "trace":
        if getattr(args, "cluster", False):
            # pandascope: the cluster-assembled view — one trace stitched
            # across every broker it touched (admin fans out to peers)
            path = (
                f"/v1/trace/cluster/{args.id}"
                if args.id is not None
                else f"/v1/trace/cluster?limit={args.limit}"
            )
            status, body = await _admin_request(args, "GET", path)
            if status != 200:
                print(f"admin api returned {status}: {body}")
                return 1
            if args.json:
                print(json.dumps(body, indent=2))
                return 0
            try:
                from tools.traceview import render_report, render_trace
            except ImportError:
                print(json.dumps(body, indent=2))
                return 0
            if args.id is not None:
                if body.get("unreachable"):
                    print(
                        f"(partial view: nodes {body['unreachable']} "
                        f"unreachable)"
                    )
                print(render_trace(body))
                return 0
            unreachable = [
                t["node"] for t in body.get("targets", [])
                if not t.get("reachable")
            ]
            if unreachable:
                print(f"(partial view: nodes {unreachable} unreachable)")
            if not body.get("traces"):
                print(
                    "no assembled cluster traces (slow ring empty — "
                    "nothing breached the slow threshold yet)"
                )
                return 0
            print(render_report(body, max_traces=args.limit))
            return 0
        path = (
            f"/v1/trace/slow?limit={args.limit}"
            if args.slow
            else f"/v1/trace/recent?limit={args.limit}"
        )
        status, body = await _admin_request(args, "GET", path)
        if status != 200:
            print(f"admin api returned {status}: {body}")
            return 1
        if args.json:
            print(json.dumps(body, indent=2))
            return 0
        if args.slow:
            spans = body.get("spans", [])
            if not spans:
                print(f"no spans over {body.get('threshold_ms')} ms")
            for s in spans:
                extra = {
                    k: v for k, v in s.items()
                    if k not in ("trace_id", "name", "start_us", "dur_us", "thread")
                }
                print(
                    f"{s['name']:<28}{s['dur_us'] / 1000.0:>10.2f}ms  "
                    f"trace={s['trace_id']} thread={s['thread']} {extra or ''}"
                )
            return 0
        try:
            from tools.traceview import render_report
        except ImportError:  # rpk installed without the tools tree
            print(json.dumps(body, indent=2))
            return 0
        if not body.get("enabled") and not body.get("traces"):
            print("tracer is disabled and the ring is empty; enable with "
                  "`trace_enabled: true` in the broker config")
            return 0
        print(render_report(body, max_traces=args.limit))
        return 0

    if args.debug_cmd == "coproc":
        status, body = await _admin_request(args, "GET", "/v1/coproc/status")
        if status != 200:
            print(f"admin api returned {status}: {body}")
            return 1
        if args.json:
            print(json.dumps(body, indent=2, sort_keys=True))
            return 0
        if not body.get("enabled"):
            print("coproc disabled (set coproc_enable: true)")
            return 0
        dev = body.get("device") or {}
        print(
            f"device:  platform={dev.get('platform', '?')} "
            f"device_kind={dev.get('device_kind', '?')} "
            f"count={dev.get('count', '?')}"
        )
        if dev.get("warning"):
            print(f"  WARNING: {dev['warning']}")
        native = body.get("native") or {}
        print(
            "native:  "
            + ("loaded" if native.get("loaded") else "NOT LOADED")
            + (f" — {native['build_error']}" if native.get("build_error") else "")
        )
        b = body.get("breaker") or {}
        print(
            f"breaker: {b.get('state', '?'):<10} trips={b.get('trips', 0)} "
            f"consecutive_failures={b.get('consecutive_failures', 0)}"
            f"/{b.get('threshold', '?')} cooldown={b.get('cooldown_ms', '?')}ms"
        )
        print(f"scripts: {', '.join(body.get('scripts') or []) or '(none)'}")
        ra = body.get("read_ahead") or {}
        if ra.get("read_us"):
            # coproc_tick_latency_us{phase="read_hidden"} over {phase="read"}
            print(
                f"read-ahead: {ra['read_hidden_us'] / ra['read_us']:.0%} of the "
                f"pacemaker's read ran inside the previous tick's engine phase "
                f"({ra['ticks']} ticks; ~0% on a live stream, which leaves no "
                f"backlog to read ahead of)"
            )
        ta = body.get("tick_account") or {}
        if ta.get("ticks"):
            # coproc_tick_latency_us{phase=}: the engine phase as the sum of
            # its legs, ms a productive tick; the worker's two calls and
            # what no t_* stage inside them covers, ms a launch (stats'
            # t_submit / t_harvest / t_submit_self / t_harvest_self)
            st = body.get("stats") or {}
            tick_ms = {
                k[:-3]: v / ta["ticks"] / 1000.0
                for k, v in ta.items() if k.endswith("_us")
            }
            launches = max(st.get("n_launches", 0), 1)
            call_ms = {
                k: st.get("t_" + k, 0.0) / launches * 1000.0
                for k in ("submit", "submit_self", "harvest", "harvest_self")
            }
            print(
                f"tick:    engine {tick_ms['engine']:.2f} ms a tick = prepare "
                f"{tick_ms['engine_prepare']:.2f} + out {tick_ms['handoff_out']:.2f} "
                f"+ run {tick_ms['engine_run']:.2f} + back {tick_ms['handoff_back']:.2f} "
                f"+ read-ahead wait {tick_ms['read_ahead_wait']:.2f} ({ta['ticks']} "
                f"ticks; engine also counts ticks that timed out or were shed); the "
                f"worker's run a launch: submit {call_ms['submit']:.2f} (self "
                f"{call_ms['submit_self']:.2f}) + harvest {call_ms['harvest']:.2f} "
                f"(self {call_ms['harvest_self']:.2f}) ms"
            )
        ap = body.get("append") or {}
        if ap.get("framings"):
            # storage_append_crossing_batches: _sum over _count
            print(
                f"append:  {ap['batches'] / ap['framings']:.1f} batches a framing "
                f"call of the log's appends ({ap['framings']} calls; one native "
                f"crossing frames a list, the per-batch loop reads 1.0)"
            )
        mesh = body.get("mesh")
        if mesh:
            print(
                f"mesh:    {mesh.get('devices', '?')} devices, "
                f"decision={mesh.get('decision')}, "
                f"launches={mesh.get('launches', 0)}, "
                f"demotions={mesh.get('demotions', 0)}, "
                f"rows_per_device={mesh.get('rows_per_device')}"
            )
        stats = body.get("stats") or {}
        for sid, ladder in sorted((stats.get("programs_ready") or {}).items()):
            # coproc_programs_ready{script=}: a payload script's row buckets
            # whose device program was built ahead of need, at the deploy
            buckets = ladder.get("buckets") or []
            span = f" ({buckets[0]}-{buckets[-1]} rows)" if buckets else ""
            print(
                f"programs: script {sid}: {len(buckets)} row buckets ready{span} "
                f"of a ladder to {ladder.get('top')} rows, {ladder.get('state')}; "
                f"built off the serving path ({int(stats.get('n_precompiles', 0))} "
                f"programs in {stats.get('t_precompile', 0.0):.1f} s), "
                f"{int(stats.get('n_launch_cuts', 0))} launches cut to a ready bucket"
            )
            if ladder.get("strides"):
                # the narrower staged rows the script's launches have shown,
                # each with a ladder of its own (coproc_split_launches_total)
                shown = ", ".join(
                    f"{stride} B ({len(st.get('buckets') or [])} buckets, {st.get('state')})"
                    for stride, st in sorted(
                        ladder["strides"].items(), key=lambda kv: int(kv[0])
                    )
                )
                print(
                    f"strides:  script {sid}: rows also staged at {shown}; "
                    f"{int(stats.get('n_split_launches', 0))} launches staged in "
                    f"parts by width class ({int(stats.get('n_parts', 0))} parts in "
                    f"all, {int(stats.get('n_wide_rows', 0))} rows over 1,024 B)"
                )
        if stats.get("n_json_rows"):
            # coproc_json_rows_total{outcome="read|malformed|path_miss"}
            print(
                f"json:    {int(stats['n_json_rows'])} rows read as JSON by a "
                f"structural program (map_project_json); dropped "
                f"{int(stats.get('n_json_malformed_rows', 0))} as not one sound "
                f"object, {int(stats.get('n_json_path_miss_rows', 0))} for a path "
                f"absent or of a value its field cannot hold"
            )
        shown = {
            k: v for k, v in sorted(stats.items())
            if k.startswith(("t_", "n_", "bytes_")) or k == "host_workers"
        }
        for k, v in shown.items():
            v = round(v, 6) if isinstance(v, float) else v
            print(f"  {k:<28}{v}")
        for k in (
            "columnar_backend", "columnar_probe", "parse_path",
            "colcache", "arena", "staging_arena", "uncompress_arena",
            "seal_arena", "breakers", "lockwatch",
            "leakwatch", "mesh_error", "device_launches_by_script",
        ):
            if stats.get(k) is not None:
                print(f"  {k:<28}{stats[k]}")
        return 0 if native.get("loaded") else 1

    if args.debug_cmd == "profile":
        if args.perfetto:
            query = {"launches": str(args.launches)}
            if args.federated:
                query["federated"] = "1"
            status, body = await _admin_request(
                args, "GET", "/v1/profile/timeline", query=query
            )
            if status != 200:
                print(f"admin api returned {status}: {body}")
                return 1
            data = json.dumps(body)
            await asyncio.to_thread(_write_text, args.perfetto, data)
            events = body.get("traceEvents") or []
            extra = ""
            if body.get("unreachable"):
                extra = f" (PARTIAL: unreachable {body['unreachable']})"
            n_counters = sum(1 for e in events if e.get("ph") == "C")
            tracks = len({e["name"] for e in events if e.get("ph") == "C"})
            print(
                f"wrote {args.perfetto}: {len(events)} events, "
                f"{body.get('launches', 0)} launches, "
                f"{body.get('journal_events', '?')} journal instants, "
                f"{n_counters} counter samples on {tracks} trend tracks"
                f"{extra} — load it at https://ui.perfetto.dev"
            )
            return 0
        status, body = await _admin_request(args, "GET", "/v1/profile")
        if status != 200:
            print(f"admin api returned {status}: {body}")
            return 1
        if args.json:
            print(json.dumps(body, indent=2, sort_keys=True))
            return 0
        rec = body.get("recorder") or {}
        prof = body.get("profiler") or {}
        tracing = (
            "on" if body.get("tracing")
            else "OFF — timelines stay empty; set trace_enabled: true"
        )
        print(
            f"flight recorder: {'on' if body.get('enabled') else 'off'} "
            f"(tracing {tracing})"
        )
        print(
            f"  spans {rec.get('spans', 0)}/{rec.get('capacity', 0)} "
            f"(committed {rec.get('spans_recorded', 0)}), "
            f"launches {rec.get('launches', 0)}"
        )
        print(
            f"wall profiler: "
            f"{'running' if prof.get('running') else 'off'} "
            f"hz={prof.get('hz', 0)} samples={prof.get('samples', 0)} "
            f"stacks={prof.get('distinct_stacks', 0)}"
        )
        if args.top:
            rows = body.get("top") or []
            if not rows:
                print("no profile samples (set profile_hz, e.g. 19)")
                return 0
            print(f"{'SAMPLES':>8}  {'AFFINITY':<12}{'THREAD':<26}FRAME")
            for r in rows:
                print(
                    f"{r.get('samples', 0):>8}  "
                    f"{r.get('affinity', '?'):<12}"
                    f"{r.get('thread', '?'):<26}{r.get('frame', '?')}"
                )
            return 0
        totals = body.get("stage_totals_s") or {}
        if totals:
            own = body.get("self_totals_s") or {}
            print(f"stage totals (s, ring window):{'TOTAL':>23}{'SELF':>12}")
            ordered = sorted(totals.items(), key=lambda kv: -kv[1])
            for k, v in ordered[:16]:
                print(f"  {k:<40}{v:>12.6f}{own.get(k, v):>12.6f}")
        for st in body.get("loop_stalls") or []:
            print(
                f"loop stall {st.get('dur_us', 0) / 1000.0:.0f} ms at "
                f"{st.get('start', 0):.3f} in {st.get('phase') or '?'}: "
                f"{' > '.join(st.get('stack') or []) or 'no stack taken'}"
            )
        return 0

    if args.debug_cmd == "trend":
        query = {}
        if getattr(args, "series", None):
            query["series"] = args.series
        if getattr(args, "limit", 0):
            query["limit"] = str(args.limit)
        if getattr(args, "federated", False):
            query["federated"] = "1"
        status, body = await _admin_request(
            args, "GET", "/v1/history", query=query or None
        )
        if status != 200:
            print(f"admin api returned {status}: {body}")
            return 1
        if args.json:
            print(json.dumps(body, indent=2, sort_keys=True))
            return 0

        def _render_node(doc: dict, indent: str = "") -> None:
            wins = doc.get("windows") or []
            print(
                f"{indent}history: {doc.get('windows_retained', 0)} windows "
                f"(interval {doc.get('interval_s', '?')}s, "
                f"recorder {'on' if doc.get('recorder_running') else 'OFF'}, "
                f"{doc.get('bytes', 0)}/{doc.get('bytes_max', 0)} bytes, "
                f"evicted {doc.get('evicted_total', 0)})"
            )
            print(
                f"{indent}breaches: {doc.get('breaches_total', 0)} journaled "
                f"(governor trend domain; `rpk debug governor` shows them)"
            )
            ewma = doc.get("ewma") or {}
            latest = wins[-1].get("tracks", {}) if wins else {}
            names = sorted(set(latest) | set(ewma))
            if names:
                print(
                    f"{indent}{'TRACK':<44}{'LATEST':>12}{'EWMA':>12}"
                    f"{'BAND':>12}  STATE"
                )
            for name in names:
                st = ewma.get(name) or {}
                cur = latest.get(name)
                print(
                    f"{indent}{name:<44}"
                    f"{cur if cur is not None else '-':>12}"
                    f"{st.get('mean', '-'):>12}"
                    f"{st.get('band', '-'):>12}  "
                    f"{'BREACHED' if st.get('breached') else 'ok'}"
                )

        if args.federated:
            if body.get("unreachable"):
                print(f"PARTIAL: unreachable {body['unreachable']}")
            for node in sorted(body.get("nodes") or {}, key=str):
                print(f"node {node}:")
                _render_node(body["nodes"][node], indent="  ")
            return 0
        _render_node(body)
        return 0

    if args.debug_cmd == "resources":
        query = {"federated": "1"} if args.federated else None
        status, body = await _admin_request(
            args, "GET", "/v1/resources", query=query
        )
        if status != 200:
            print(f"admin api returned {status}: {body}")
            return 1
        if args.json:
            print(json.dumps(body, indent=2, sort_keys=True))
            return 0
        if args.federated:
            print(
                f"cluster pressure: {body.get('pressure', '?')}"
                + (
                    f" (worst node {body['pressure_node']})"
                    if body.get("pressure_node") else ""
                )
                + (
                    f"  PARTIAL: unreachable {body['unreachable']}"
                    if body.get("unreachable") else ""
                )
            )
            accounts = body.get("accounts") or {}
            if accounts:
                print(
                    f"{'ACCOUNT':<16}{'HELD':>12}{'PEAK':>12}{'LIMIT':>12}"
                    f"{'WORST-OCC':>11}  NODE"
                )
            for name, a in sorted(accounts.items()):
                print(
                    f"{name:<16}{a.get('held_bytes', 0):>12}"
                    f"{a.get('peak_bytes', 0):>12}"
                    f"{a.get('limit_bytes', 0):>12}"
                    f"{a.get('max_occupancy', 0):>11.1%}  "
                    f"{a.get('max_occupancy_node') or '-'}"
                )
            for node in sorted(body.get("nodes") or {}):
                nb = body["nodes"][node]
                print(
                    f"node {node}: pressure={nb.get('pressure', '?')} "
                    f"max_occ={nb.get('max_occupancy', 0):.1%} "
                    f"in {nb.get('max_occupancy_account') or '(none)'}"
                )
            return 0
        if not body.get("enabled"):
            print("no budget plane installed (bare broker?)")
            return 0
        print(
            f"pressure: {body.get('pressure', '?')} "
            f"(max occupancy {body.get('max_occupancy', 0):.1%} in "
            f"{body.get('max_occupancy_account') or '(none)'}; warn at "
            f"{body.get('warn_pct', 0):.0%}, critical at "
            f"{body.get('critical_pct', 0):.0%})"
        )
        print(f"total:    {body.get('total_bytes', 0)} bytes")
        accounts = body.get("accounts") or {}
        if accounts:
            print(
                f"{'ACCOUNT':<16}{'HELD':>12}{'PEAK':>12}{'LIMIT':>12}"
                f"{'OCC':>8}"
            )
        for name, a in sorted(accounts.items()):
            print(
                f"{name:<16}{a.get('held_bytes', 0):>12}"
                f"{a.get('peak_bytes', 0):>12}{a.get('limit_bytes', 0):>12}"
                f"{a.get('occupancy', 0):>8.1%}"
            )
        for key in ("produce_admission", "coproc_admission"):
            ctl = body.get(key)
            if ctl:
                print(
                    f"{key}: admitted={ctl.get('admitted', 0)} "
                    f"sheds={ctl.get('sheds', 0)} "
                    f"throttle={ctl.get('base_throttle_ms', '?')}-"
                    f"{ctl.get('max_throttle_ms', '?')}ms"
                )
        auto = body.get("autotune")
        if auto:
            print(
                f"autotune: enabled={auto.get('enabled')} "
                f"group_ticks={auto.get('group_ticks')}"
                f"/{auto.get('group_ticks_cap')} "
                f"launch_depth={auto.get('launch_depth')}"
                f"/{auto.get('launch_depth_cap')} "
                f"hold={auto.get('hold_s')}s "
                f"last_move_on={auto.get('evidence') or '(none)'}"
            )
        return 0

    if args.debug_cmd == "governor":
        query = {"limit": str(args.limit)}
        if args.domain:
            query["domain"] = args.domain
        status, body = await _admin_request(
            args, "GET", "/v1/governor", query=query
        )
        if status != 200:
            print(f"admin api returned {status}: {body}")
            return 1
        if args.json:
            print(json.dumps(body, indent=2, sort_keys=True))
            return 0
        posture = body.get("posture")
        if posture:
            print("posture:")
            for dom in ("columnar_backend", "device_lz4", "harvest_path"):
                print(f"  {dom:<20}{posture.get(dom) or '(undecided)'}")
            for dom, b in sorted((posture.get("breakers") or {}).items()):
                print(
                    f"  breaker[{dom}]".ljust(22)
                    + f"{b.get('state', '?')} trips={b.get('trips', 0)} "
                    f"consecutive={b.get('consecutive_failures', 0)}"
                    f"/{b.get('threshold', '?')}"
                )
            for dom, ms in sorted(
                (posture.get("deadlines_ms") or {}).items()
            ):
                print(f"  deadline[{dom}]".ljust(22) + f"{ms}ms")
            auto = posture.get("autotune")
            if auto:
                print(
                    "  autotune".ljust(22)
                    + f"group_ticks={auto.get('group_ticks')}"
                    f"/{auto.get('group_ticks_cap')} "
                    f"launch_depth={auto.get('launch_depth')}"
                    f"/{auto.get('launch_depth_cap')} "
                    f"last_move_on={auto.get('evidence') or '(none)'}"
                )
        else:
            print("no live coproc engine (journal below is process-wide)")
        summary = body.get("summary") or {}
        print(
            f"journal: {summary.get('entries', 0)} entries "
            f"(seq {summary.get('seq', 0)}, "
            f"{summary.get('dropped', 0)} dropped, "
            f"capacity {summary.get('capacity', 0)})"
        )
        entries = body.get("journal") or []
        if entries:
            print(f"{'SEQ':>5}  {'DOMAIN':<18}{'VERDICT':<12}REASON")
        for e in entries:
            print(
                f"{e['seq']:>5}  {e['domain']:<18}{e['verdict']:<12}"
                f"{e['reason']}"
            )
            inputs = e.get("inputs") or {}
            if inputs:
                print(f"{'':>7}inputs: {json.dumps(inputs, sort_keys=True)}")
        return 0

    if args.debug_cmd == "slo":
        # mark names are user input riding a query string: sent via the
        # `query` dict so they get exactly ONE correct encoding (a name
        # with '&'/'=' must not split the query; pre-quoting into the path
        # would get '%' re-encoded by _admin_request)
        if args.set_mark is not None:
            query = {"name": args.set_mark}
            if getattr(args, "federated", False):
                query["federated"] = "1"
            status, body = await _admin_request(
                args, "POST", "/v1/slo/mark", query=query
            )
            if status != 200:
                print(f"admin api returned {status}: {body}")
                return 1
            if body.get("federated"):
                print(
                    f"federated mark {body['mark']!r} set over nodes "
                    f"{body.get('nodes')}"
                    + (
                        f" (unreachable: {body['unreachable']})"
                        if body.get("unreachable") else ""
                    )
                )
            else:
                print(
                    f"mark {body['mark']!r} set over {body['series']} series"
                )
            return 0
        query = {}
        if args.mark:
            query["mark"] = args.mark
        if getattr(args, "federated", False):
            query["federated"] = "1"
        status, body = await _admin_request(
            args, "GET", "/v1/slo", query=query or None,
        )
        if status != 200:
            print(f"admin api returned {status}: {body}")
            return 1
        if args.json:
            print(json.dumps(body, indent=2, sort_keys=True))
            return 0
        verdict = "PASS" if body.get("pass") else "FAIL"
        print(
            f"scenario {body.get('scenario')}: {verdict} "
            f"({body.get('failed', 0)} failed, {body.get('no_data', 0)} no-data; "
            f"window {body.get('window')})"
        )
        fed_meta = body.get("federation")
        if fed_meta is not None:
            line = (
                f"federated over nodes {fed_meta.get('nodes')}"
            )
            if fed_meta.get("unreachable"):
                line += (
                    f" — PARTIAL: {fed_meta['unreachable']} unreachable"
                )
            print(line)
        print(
            f"{'OBJECTIVE':<24}{'METRIC':<30}{'Q':>5}{'OBSERVED':>12}"
            f"{'THRESHOLD':>12}{'SAMPLES':>9}  STATUS"
        )
        for o in body.get("objectives", []):
            obs = o.get("observed_ms")
            print(
                f"{o['name']:<24}{o['metric']:<30}"
                f"{('p%g' % o['quantile']):>5}"
                f"{(('%.2fms' % obs) if obs is not None else '-'):>12}"
                f"{('%gms' % o['threshold_ms']):>12}"
                f"{o.get('samples', 0):>9}  {o['status']}"
            )
            for ex in (o.get("exemplars") or [])[:5]:
                print(
                    f"    breach exemplar: trace={ex['trace_id']} "
                    f"{ex['value_us'] / 1000.0:.2f}ms "
                    f"(bucket <= {ex['bucket_us'] / 1000.0:.2f}ms) — "
                    f"`rpk debug trace --slow` resolves it "
                    f"(--cluster --id {ex['trace_id']} assembles it)"
                )
            for node, nv in sorted((o.get("per_node") or {}).items()):
                obs_n = nv.get("observed_ms")
                print(
                    f"    node {node}: "
                    f"{(('%.2fms' % obs_n) if obs_n is not None else '-')} "
                    f"({nv.get('samples', 0)} samples, {nv.get('status')})"
                )
        if body.get("exemplars_enabled") is False:
            # local reports only: the federated report has no exemplar
            # layer at all (exemplar rings are per-process)
            print(
                "note: tracer disabled — breaches carry no exemplars "
                "(set trace_enabled: true)"
            )
        return 0

    if args.debug_cmd == "failpoints":
        if args.fp_cmd == "list":
            status, body = await _admin_request(args, "GET", "/v1/failure-probes")
            if status != 200:
                print(f"admin api returned {status}: {body}")
                return 1
            armed = body.get("armed") or {}
            counts = body.get("counts") or {}
            print(f"honey badger enabled: {body.get('enabled', False)}")
            for module, probes_ in sorted((body.get("modules") or {}).items()):
                for probe in probes_:
                    effect = armed.get(module, {}).get(probe, "-")
                    rem = counts.get(module, {}).get(probe)
                    if rem is not None:
                        effect = f"{effect} (x{rem} left)"
                    print(f"  {module + '.' + probe:<40}{effect}")
            return 0
        if args.fp_cmd == "arm":
            path = f"/v1/failure-probes/{args.module}/{args.probe}/{args.type}"
            query = {}
            if args.count is not None:
                query["count"] = str(args.count)
            if getattr(args, "delay_ms", None) is not None:
                query["delay_ms"] = str(args.delay_ms)
            status, body = await _admin_request(
                args, "PUT", path, query=query or None
            )
        else:  # disarm
            status, body = await _admin_request(
                args, "DELETE",
                f"/v1/failure-probes/{args.module}/{args.probe}",
            )
        if status != 200:
            print(f"admin api returned {status}: {body}")
            return 1
        print(json.dumps(body))
        return 0

    bundle: dict[str, object] = {}
    for name, path in [
        ("config.json", "/v1/config"),
        ("brokers.json", "/v1/brokers"),
        ("partitions.json", "/v1/partitions"),
        ("metrics.txt", "/metrics"),
        ("traces.json", "/v1/trace/recent"),
        # pandascope cluster view: the slow ring's traces assembled across
        # every broker they touched + the merged multi-node scrape
        ("cluster_traces.json", "/v1/trace/cluster"),
        ("federated_metrics.json", "/v1/federation/metrics"),
        ("coproc.json", "/v1/coproc/status"),
        ("governor.json", "/v1/governor"),
        ("resources.json", "/v1/resources"),
        # pandapulse: profiler/recorder status + the launch timeline (the
        # Perfetto-loadable artifact — open timeline.json at ui.perfetto.dev)
        ("profile.json", "/v1/profile"),
        ("timeline.json", "/v1/profile/timeline"),
        # pandatrend: the metrics-history ring (per-window rates/quantiles
        # + EWMA band state) — what `rpk debug trend` renders
        ("history.json", "/v1/history"),
        ("slo.json", "/v1/slo"),
        ("failpoints.json", "/v1/failure-probes"),
    ]:
        status, body = await _admin_request(args, "GET", path)
        bundle[name] = body if status == 200 else {"error": status}
    out = args.output or f"debug-bundle-{int(time.time())}.tar.gz"
    with tarfile.open(out, "w:gz") as tar:
        for name, content in bundle.items():
            data = (
                content.encode() if isinstance(content, str)
                else json.dumps(content, indent=2).encode()
            )
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
    print(f"wrote {out}")
    return 0


def cmd_generate(args) -> int:
    if args.generate_cmd == "k8s-manifests":
        from redpanda_tpu.cli.k8s import generate_manifests

        print(generate_manifests(
            name=args.name, namespace=args.namespace,
            replicas=args.replicas, image=args.image, storage=args.storage,
        ))
        return 0
    if args.generate_cmd == "prometheus-config":
        print(json.dumps({
            "scrape_configs": [{
                "job_name": "redpanda_tpu",
                "static_configs": [{"targets": [args.admin_api]}],
                "metrics_path": "/metrics",
            }]
        }, indent=2))
    else:  # grafana-dashboard
        print(json.dumps({
            "title": "redpanda_tpu",
            "panels": [
                {"title": "Partitions", "expr": "redpanda_tpu_partitions_total"},
                {"title": "Topics", "expr": "redpanda_tpu_topics_total"},
                {"title": "Produce latency", "expr": "redpanda_tpu_kafka_produce_latency_us_bucket"},
                {"title": "Fetch latency", "expr": "redpanda_tpu_kafka_fetch_latency_us_bucket"},
                {"title": "Storage append latency", "expr": "redpanda_tpu_storage_append_latency_us_bucket"},
                {"title": "Raft replicate latency", "expr": "redpanda_tpu_raft_replicate_latency_us_bucket"},
                {"title": "Coproc stage latency", "expr": "redpanda_tpu_coproc_stage_latency_us_bucket"},
                {"title": "Device link bytes", "expr": "redpanda_tpu_coproc_device_transfer_bytes_total"},
            ],
        }, indent=2))
    return 0


def cmd_tune(args) -> int:
    """Checker/tunable autotune (tuners/check.go + checked_tunable.go):
    each tuner reads real kernel state, reports ok/would-tune/unsupported,
    and mutates when permitted; --dry-run stops after the check."""
    from redpanda_tpu.cli.tuners import all_tuners, format_outcomes, run_tuners

    known = [t.name for t in all_tuners()]
    if args.tuner == "list":
        print("\n".join(known))
        return 0
    names = None if args.tuner == "all" else [args.tuner]
    if names and names[0] not in known:
        print(f"unknown tuner {names[0]!r}; `rpk tune list` shows them", file=sys.stderr)
        return 1
    outcomes = run_tuners(
        names,
        root=args.root,
        dry_run=args.dry_run,
        ballast_path=args.ballast_path,
        ballast_size=args.ballast_size,
    )
    print(format_outcomes(outcomes, args.dry_run))
    # exit 1 when anything errored or an apply failed verification
    bad = any(o.error or (o.applied and o.post_ok is False) for o in outcomes)
    return 1 if bad else 0


def cmd_iotune(args) -> int:
    """Benchmark the data dir and persist io-config.json (the reference's
    `rpk iotune` io-properties flow); `start` publishes the numbers."""
    from redpanda_tpu.cli.iotune import measure, write_io_config

    data_dir = args.directory
    print(f"iotune: characterizing {data_dir} ...")
    try:
        result = measure(
            data_dir,
            file_bytes=args.probe_mb << 20,
            fsync_iters=args.fsync_iters,
        )
        path = write_io_config(data_dir, result)
    except OSError as e:
        # permission denied / disk full mid-probe: clean refusal, not a
        # traceback (the default directory needs broker-level privileges)
        print(f"iotune: cannot characterize {data_dir}: {e}", file=sys.stderr)
        return 1
    print(f"  seq write : {result['seq_write_mb_s']:.1f} MB/s")
    print(f"  seq read  : {result['seq_read_mb_s']:.1f} MB/s")
    f = result["fsync_4k"]
    print(f"  fsync 4k  : p50 {f['p50_ms']} ms, p99 {f['p99_ms']} ms")
    print(f"written {path}")
    return 0


# ================================================================ arg parsing
def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="rpk", description=__doc__)
    p.add_argument("--brokers", default=DEFAULT_BROKERS, help="host:port[,host:port]")
    p.add_argument("--admin-api", default=DEFAULT_ADMIN)
    p.add_argument("--user", help="SASL username")
    p.add_argument("--password", help="SASL password")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a broker")
    sp.add_argument("--config", help="redpanda.yaml path")
    sp.add_argument("--set", action="append", help="key=value override")

    tp = sub.add_parser("topic", help="topic operations")
    tsub = tp.add_subparsers(dest="topic_cmd", required=True)
    tc = tsub.add_parser("create")
    tc.add_argument("name")
    tc.add_argument("-p", "--partitions", type=int, default=1)
    tc.add_argument("-r", "--replicas", type=int, default=1)
    tc.add_argument("-c", "--topic-config", action="append", help="key=value")
    td = tsub.add_parser("delete")
    td.add_argument("name")
    tsub.add_parser("list")
    tde = tsub.add_parser("describe")
    tde.add_argument("name")
    tpr = tsub.add_parser("produce")
    tpr.add_argument("name")
    tpr.add_argument("value", help="record value ('-' = stdin)")
    tpr.add_argument("-p", "--partition", type=int, default=0)
    tpr.add_argument("-k", "--key", default=None)
    tco = tsub.add_parser("consume")
    tco.add_argument("name")
    tco.add_argument("-p", "--partition", type=int, default=0)
    tco.add_argument("-o", "--offset", type=int, default=-1)
    tco.add_argument("-n", "--num", type=int, default=10)

    ap = sub.add_parser("acl", help="acl operations")
    asub = ap.add_subparsers(dest="acl_cmd", required=True)
    ac = asub.add_parser("create")
    ac.add_argument("--resource", choices=["topic", "group", "cluster", "transactional_id"], required=True)
    ac.add_argument("--resource-name", required=True)
    ac.add_argument("--principal", required=True)
    ac.add_argument("--operation", required=True)
    ac.add_argument("--host", default="*")
    ac.add_argument("--deny", action="store_true")
    asub.add_parser("list")

    wp = sub.add_parser("wasm", help="inline transform operations")
    wsub = wp.add_subparsers(dest="wasm_cmd", required=True)
    wsub.add_parser("generate", help="print a transform template")
    wd = wsub.add_parser("deploy")
    wd.add_argument("file", help="transform JSON (see wasm generate)")
    wr = wsub.add_parser("remove")
    wr.add_argument("name")

    cp = sub.add_parser("cluster", help="cluster info + leadership balance")
    csub = cp.add_subparsers(dest="cluster_cmd")
    csub.add_parser("info")
    crb = csub.add_parser("rebalance", help="spread partition leaderships")
    crb.add_argument(
        "--admin-apis",
        help="comma-separated admin endpoints, one per broker "
        "(each node sheds its own excess)",
    )

    up = sub.add_parser("user", help="SCRAM users (admin api)")
    usub = up.add_subparsers(dest="user_cmd", required=True)
    uc = usub.add_parser("create")
    uc.add_argument("name")
    uc.add_argument("--new-password", required=True)
    uc.add_argument("--mechanism", default="SCRAM-SHA-256")
    ud = usub.add_parser("delete")
    ud.add_argument("name")
    usub.add_parser("list")

    cfp = sub.add_parser("config", help="configuration")
    cfsub = cfp.add_subparsers(dest="config_cmd", required=True)
    cg = cfsub.add_parser("get")
    cg.add_argument("key", nargs="?")
    cfsub.add_parser("set")

    dp = sub.add_parser("debug", help="diagnostics")
    dsub = dp.add_subparsers(dest="debug_cmd", required=True)
    db = dsub.add_parser("bundle")
    db.add_argument("-o", "--output")
    dt = dsub.add_parser("trace", help="recent pandaprobe spans (admin api)")
    dt.add_argument("--slow", action="store_true", help="slow-request log only")
    dt.add_argument("--limit", type=int, default=10, help="traces/spans to fetch")
    dt.add_argument("--json", action="store_true", help="raw JSON, no rendering")
    dt.add_argument(
        "--cluster", action="store_true",
        help="pandascope: assemble traces across every broker they "
             "touched (admin fans out to peers; no --id = the slow "
             "ring's traces)",
    )
    dt.add_argument(
        "--id", type=int, default=None, metavar="TRACE_ID",
        help="with --cluster: assemble this one trace id",
    )
    dc = dsub.add_parser(
        "coproc",
        help="engine breaker + fault-domain + stage stats (t_<stage> seconds: "
             "explode*, pack, dispatch > h2d, fetch > wait_h2d + wait_program "
             "+ wait_d2h, rebuild / frame_gather, seal, ...); read-ahead: the "
             "share of the pacemaker's read hidden under the previous tick's "
             "engine phase (coproc_tick_latency_us phase=read_hidden over read); "
             "append: batches a framing call of the log's appends "
             "(storage_append_crossing_batches)",
    )
    dc.add_argument("--json", action="store_true", help="raw JSON, no rendering")
    dres = dsub.add_parser(
        "resources",
        help="budget plane: account occupancy, pressure, admission + "
             "autotune state (admin api)",
    )
    dres.add_argument("--json", action="store_true", help="raw JSON, no rendering")
    dres.add_argument(
        "--federated", action="store_true",
        help="merge every node's budget-account occupancy (admin fans "
             "out to peers; occupancy/pressure report the worst node)",
    )
    dprof = dsub.add_parser(
        "profile",
        help="pandapulse flight recorder + wall profiler (admin api)",
    )
    dprof.add_argument("--json", action="store_true", help="raw JSON, no rendering")
    dprof.add_argument(
        "--perfetto", default=None, metavar="OUT.json",
        help="write the Chrome trace-event launch timeline (governor "
             "verdicts + admission episodes as instant events); load it "
             "at https://ui.perfetto.dev",
    )
    dprof.add_argument(
        "--top", action="store_true",
        help="wall-profile leaf-frame attribution table (needs profile_hz)",
    )
    dprof.add_argument(
        "--launches", type=int, default=0,
        help="with --perfetto: newest N launches (0 = every launch in the ring)",
    )
    dprof.add_argument(
        "--federated", action="store_true",
        help="with --perfetto: assemble the cluster timeline across "
             "every broker (like rpk debug trace --cluster)",
    )
    dtrend = dsub.add_parser(
        "trend",
        help="pandatrend metrics history: per-window rates/quantiles, "
             "EWMA bands + breach state (admin api GET /v1/history)",
    )
    dtrend.add_argument("--json", action="store_true", help="raw JSON, no rendering")
    dtrend.add_argument(
        "--series", default=None,
        help="substring filter over series keys (counters/gauges/hists/tracks)",
    )
    dtrend.add_argument(
        "--limit", type=int, default=0,
        help="newest N windows only (0 = the whole retained ring)",
    )
    dtrend.add_argument(
        "--federated", action="store_true",
        help="fan out to every broker's admin: per-node window rings "
             "side by side (windows never merge across wall clocks)",
    )
    dgov = dsub.add_parser(
        "governor",
        help="coproc decision journal + per-domain posture (admin api)",
    )
    dgov.add_argument("--json", action="store_true", help="raw JSON, no rendering")
    dgov.add_argument(
        "--limit", type=int, default=32, help="journal entries to fetch"
    )
    dgov.add_argument(
        "--domain", default=None,
        help="filter the journal to one decision domain",
    )
    dslo = dsub.add_parser(
        "slo", help="SLO verdicts over the pandaprobe histograms (admin api)"
    )
    dslo.add_argument("--json", action="store_true", help="raw JSON, no rendering")
    dslo.add_argument(
        "--mark", default=None,
        help="judge only observations since this named baseline",
    )
    dslo.add_argument(
        "--set-mark", default=None, metavar="NAME",
        help="snapshot a named baseline instead of evaluating",
    )
    dslo.add_argument(
        "--federated", action="store_true",
        help="judge the objectives over the merged multi-node /metrics "
             "scrape (node-labeled drill-down) instead of this broker's "
             "registry",
    )
    dfp = dsub.add_parser(
        "failpoints", help="list/arm/disarm honey-badger failure probes"
    )
    fpsub = dfp.add_subparsers(dest="fp_cmd", required=True)
    fpsub.add_parser("list")
    fpa = fpsub.add_parser("arm")
    fpa.add_argument("module")
    fpa.add_argument("probe")
    fpa.add_argument(
        "type", choices=["exception", "delay", "wedge", "terminate", "corrupt"],
    )
    fpa.add_argument(
        "--count", type=int, default=None,
        help="auto-disarm after N injections (1 = one-shot)",
    )
    fpa.add_argument(
        "--delay-ms", type=int, default=None, dest="delay_ms",
        help="size the injected delay (the knob lives in the broker "
             "process; remote chaos drivers have no other way to set it)",
    )
    fpd = fpsub.add_parser("disarm")
    fpd.add_argument("module")
    fpd.add_argument("probe")

    gp = sub.add_parser("generate", help="monitoring + deployment configs")
    gsub = gp.add_subparsers(dest="generate_cmd", required=True)
    gsub.add_parser("grafana-dashboard")
    gsub.add_parser("prometheus-config")
    gk = gsub.add_parser("k8s-manifests")
    gk.add_argument("--name", default="redpanda-tpu")
    gk.add_argument("--namespace", default="default")
    gk.add_argument("--replicas", type=int, default=3)
    gk.add_argument("--image", default="redpanda-tpu:latest")
    gk.add_argument("--storage", default="20Gi")

    tns = sub.add_parser("tune", help="check and apply kernel tuners (autotune)")
    tns.add_argument(
        "tuner", nargs="?", default="all",
        help="'all', 'list', or one tuner name",
    )
    tns.add_argument(
        "--dry-run", action="store_true",
        help="report required changes without mutating anything",
    )
    tns.add_argument(
        "--root", default="/",
        help="filesystem root for /proc and /sys (tests/containers)",
    )
    tns.add_argument("--ballast-path", default=None)
    tns.add_argument("--ballast-size", type=int, default=None)
    iop = sub.add_parser("iotune", help="benchmark the data dir, write io-config.json")
    # default must match the broker's data_directory default so a stock
    # `rpk iotune` + `redpanda start` pair actually connects
    iop.add_argument("--directory", default="/var/lib/redpanda_tpu")
    iop.add_argument("--probe-mb", type=int, default=64, help="probe file size")
    iop.add_argument("--fsync-iters", type=int, default=50)

    cnp = sub.add_parser("container", help="local multi-broker dev cluster")
    cnsub = cnp.add_subparsers(dest="container_cmd")
    # --dir goes on every SUBparser so `rpk container start --dir X` works
    # (options on the parent are only accepted before the subcommand)
    cns = cnsub.add_parser("start")
    cns.add_argument("-n", "--nodes", type=int, default=1)
    cns.add_argument("--dir", help="cluster state directory")
    cns.add_argument(
        "--set", action="append", metavar="K=V",
        help="extra broker config overrides (repeatable), e.g. coproc_enable=1",
    )
    for name in ("status", "stop", "purge"):
        cnsub.add_parser(name).add_argument("--dir", help="cluster state directory")

    plp = sub.add_parser("plugin", help="external rpk-<name> plugins")
    plsub = plp.add_subparsers(dest="plugin_cmd")
    plsub.add_parser("list")
    return p


def _find_plugins() -> dict[str, str]:
    """rpk-<name> executables on PATH (the reference's plugin discovery,
    src/go/rpk plugin system: any `rpk-foo` binary serves `rpk foo`)."""
    out: dict[str, str] = {}
    for d in os.environ.get("PATH", "").split(os.pathsep):
        try:
            entries = os.listdir(d or ".")
        except OSError:
            continue
        for e in entries:
            if e.startswith("rpk-"):
                path = os.path.join(d or ".", e)
                if os.access(path, os.X_OK) and e[4:] not in out:
                    out[e[4:]] = path
    return out


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    parser = build_parser()
    # plugin fallback BEFORE parsing: `rpk foo ...` execs `rpk-foo ...`
    # when foo is not a built-in; the built-in set is derived from the
    # parser itself so a new subcommand can never silently lose to a
    # same-named plugin
    known = next(
        a.choices.keys()
        for a in parser._subparsers._group_actions  # noqa: SLF001
        if hasattr(a, "choices")
    )
    if argv and not argv[0].startswith("-") and argv[0] not in known:
        plugin = _find_plugins().get(argv[0])
        if plugin is not None:
            return subprocess.call([plugin, *argv[1:]])
    args = parser.parse_args(argv)
    if args.cmd == "container":
        from redpanda_tpu.cli.container import cmd_container

        return cmd_container(args)
    if args.cmd == "plugin":
        for name, path in sorted(_find_plugins().items()):
            print(f"{name:<20} {path}")
        return 0
    table = {
        "start": cmd_start,
        "topic": cmd_topic,
        "acl": cmd_acl,
        "wasm": cmd_wasm,
        "cluster": cmd_cluster,
        "user": cmd_user,
        "config": cmd_config,
    }
    if args.cmd == "debug":
        return asyncio.run(cmd_debug(args))
    if args.cmd == "generate":
        return cmd_generate(args)
    if args.cmd == "tune":
        return cmd_tune(args)
    if args.cmd == "iotune":
        return cmd_iotune(args)
    return asyncio.run(table[args.cmd](args))


def _read_text(path: str) -> str:
    with open(path) as f:
        return f.read()


if __name__ == "__main__":
    sys.exit(main())
