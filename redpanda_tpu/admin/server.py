"""Admin HTTP API.

Parity with redpanda/admin_server.cc:
- GET  /v1/config                      (:218 config get; secrets redacted)
- PUT  /v1/config/log_level/{logger}   (:226-263 runtime log level w/ expiry)
- GET  /v1/brokers                     (broker membership view)
- GET  /v1/partitions                  (local partition inventory)
- POST /v1/raft/{group}/transfer_leadership             (:301)
- GET  /v1/raft/heartbeat_acks         (config-5 batched ack tally + the
  device plane's measured probe stats)
- POST /v1/partitions/kafka/{t}/{p}/transfer_leadership (:486)
- GET/POST/DELETE /v1/security/users   (:401-483 SCRAM CRUD)
- GET  /v1/failure-probes, PUT /v1/failure-probes/{m}/{p}/{type}[?count=N]
  (:948; types exception|delay|wedge|terminate, count=N auto-disarms after
  N injections, DELETE disarms — rpk debug failpoints)
- GET  /v1/coproc/status               (engine breaker + fault-domain stats;
  rpk debug coproc)
- GET  /v1/governor[?limit=N&domain=D] (coproc decision journal + per-domain
  posture/breakers/deadlines; rpk debug governor — no reference analogue,
  the reference's autotune decisions are log-only)
- GET  /v1/slo[?mark=N], POST /v1/slo/mark[?name=N]  (SLO verdicts over the
  pandaprobe histograms + named baseline marks; rpk debug slo — no
  reference analogue, the ducktape suite judges latency externally)
- GET  /metrics                        (:148-151 prometheus)
- GET  /v1/trace/recent, /v1/trace/slow (pandaprobe span traces; no
  reference analogue — seastar requests never leave their shard, ours
  cross the engine's harvester thread)
- GET  /v1/trace/id/{tid}              (this node's spans for one trace)
- GET  /v1/trace/cluster[/{tid}]       (pandascope: the trace assembled
  across every broker it touched — fan-out over each node's admin; no id
  = assemble the local slow ring's traces; rpk debug trace --cluster)
- GET  /v1/federation/metrics          (merged multi-node /metrics scrape,
  HdrHists merged bucket-by-bucket, node label preserved)
- GET  /v1/slo?federated=1             (the SLO spec judged over the
  federated scrape; POST /v1/slo/mark?federated=1 brackets cluster-wide
  incident windows; rpk debug slo --federated)
- GET  /v1/resources                   (resource_mgmt budget plane: account
  occupancy/peaks, pressure signal, admission + autotune state; rpk debug
  resources — the loadgen overload gate judges peak occupancy from it)
- POST /v1/archival/run_once, GET /v1/archival/status (drive one tiered-
  storage reconcile+upload pass / inspect uploaded-segment state; 409 when
  cloud_storage_enabled is false)
- GET  /v1/status/ready
Served on the owned HTTP server (the reference uses seastar httpd with swagger routes).
"""

from __future__ import annotations

import asyncio
import json
import logging

from redpanda_tpu.http import web

from redpanda_tpu.finjector import honey_badger
from redpanda_tpu.metrics import registry

logger = logging.getLogger("rptpu.admin")


class AdminServer:
    def __init__(
        self,
        broker,
        config=None,  # config.Configuration
        group_manager=None,  # raft.GroupManager (multi-node)
        controller=None,  # cluster.Controller (multi-node)
        host: str = "127.0.0.1",
        port: int = 9644,
        require_auth: bool = False,
        auth_token: str | None = None,
        tls=None,
    ) -> None:
        self.tls = tls  # security.tls.ReloadableTlsContext | None
        # listener-name -> ReloadableTlsContext for /v1/tls/reload (the app
        # fills this after wiring every listener)
        self.tls_contexts: dict[str, object] = {}
        self.broker = broker
        self.config = config
        self.gm = group_manager
        self.controller = controller
        self.host = host
        self.port = port
        # Auth: when enabled, every mutating/sensitive route needs either
        # `Authorization: Bearer <auth_token>` or HTTP basic credentials
        # verified against the broker's SCRAM store. /metrics and
        # /v1/status/ready stay open (scrapers/probes). When disabled the
        # admin port MUST NOT be exposed beyond localhost: it can create
        # superusers and arm failure probes.
        self.require_auth = require_auth
        self.auth_token = auth_token
        # archival scheduler (tiered storage): wired by the application
        # AFTER start when cloud_storage_enabled — /v1/archival/* answers
        # 409 otherwise
        self.archival = None
        self._runner: web.AppRunner | None = None
        self._log_level_restores: dict[str, tuple[int, asyncio.TimerHandle]] = {}
        self._federated_slo = None  # lazy: observability.federation

    # ------------------------------------------------------------ auth
    _OPEN_PATHS = ("/metrics", "/v1/status/ready")

    def _authorized(self, req: web.Request) -> bool:
        if not self.require_auth or req.path in self._OPEN_PATHS:
            return True
        hdr = req.headers.get("Authorization", "")
        if self.auth_token and hdr == f"Bearer {self.auth_token}":
            return True
        if hdr.startswith("Basic "):
            import base64 as _b64

            from redpanda_tpu.security.scram import verify_password

            try:
                user, _, pw = _b64.b64decode(hdr[6:]).decode().partition(":")
            except Exception:
                return False
            cred = self.broker.security.credentials.get(user)
            return cred is not None and verify_password(cred, pw)
        return False

    @web.middleware
    async def _auth_middleware(self, req: web.Request, handler):
        if not self._authorized(req):
            return web.json_response(
                {"error": "unauthorized"},
                status=401,
                headers={"WWW-Authenticate": 'Basic realm="redpanda-admin"'},
            )
        return await handler(req)

    # ------------------------------------------------------------ lifecycle
    async def start(self) -> "AdminServer":
        app = web.Application(middlewares=[self._auth_middleware])
        app.add_routes([
            web.get("/v1/config", self._get_config),
            web.put("/v1/config/log_level/{name}", self._set_log_level),
            web.get("/v1/brokers", self._get_brokers),
            web.put("/v1/brokers/{node_id}/decommission", self._decommission),
            web.put("/v1/brokers/{node_id}/recommission", self._recommission),
            web.get("/v1/partitions", self._get_partitions),
            web.post("/v1/raft/{group}/transfer_leadership", self._raft_transfer),
            web.get("/v1/raft/heartbeat_acks", self._raft_heartbeat_acks),
            web.post(
                "/v1/partitions/kafka/{topic}/{partition}/transfer_leadership",
                self._partition_transfer,
            ),
            web.post("/v1/partitions/rebalance_leaders", self._rebalance_leaders),
            web.get("/v1/security/users", self._list_users),
            web.post("/v1/security/users", self._create_user),
            web.delete("/v1/security/users/{user}", self._delete_user),
            web.put("/v1/security/users/{user}", self._update_user),
            web.post("/v1/tls/reload", self._reload_tls),
            web.get("/v1/data-policies", self._list_policies),
            web.put("/v1/data-policies/{topic}", self._set_policy),
            web.delete("/v1/data-policies/{topic}", self._delete_policy),
            web.get("/v1/failure-probes", self._list_probes),
            web.put("/v1/failure-probes/{module}/{probe}/{type}", self._set_probe),
            web.delete("/v1/failure-probes/{module}/{probe}", self._unset_probe),
            web.get("/v1/coproc/status", self._coproc_status),
            web.get("/v1/governor", self._governor),
            web.get("/v1/resources", self._resources),
            web.post("/v1/archival/run_once", self._archival_run_once),
            web.get("/v1/archival/status", self._archival_status),
            web.get("/v1/slo", self._slo),
            web.post("/v1/slo/mark", self._slo_mark),
            web.get("/v1/slo/exemplars", self._slo_exemplars),
            web.get("/v1/profile", self._profile),
            web.get("/v1/profile/timeline", self._profile_timeline),
            web.get("/v1/history", self._history),
            web.get("/metrics", self._metrics),
            web.get("/v1/trace/recent", self._trace_recent),
            web.get("/v1/trace/slow", self._trace_slow),
            web.get("/v1/trace/id/{trace_id}", self._trace_by_id),
            web.get("/v1/trace/cluster", self._trace_cluster_slow),
            web.get("/v1/trace/cluster/{trace_id}", self._trace_cluster),
            web.get("/v1/federation/metrics", self._federation_metrics),
            web.get("/v1/status/ready", self._ready),
        ])
        from redpanda_tpu.utils.http_server import start_site

        self._runner, self.port = await start_site(
            app, self.host, self.port, logger, "admin api",
            ssl_context=self.tls.server_context if self.tls is not None else None,
        )
        return self

    async def stop(self) -> None:
        for _, handle in self._log_level_restores.values():
            handle.cancel()
        self._log_level_restores.clear()
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    # ------------------------------------------------------------ config
    async def _get_config(self, req: web.Request) -> web.Response:
        if self.config is not None:
            return web.json_response(self.config.to_dict(redact=True))
        cfg = self.broker.config
        return web.json_response({k: v for k, v in vars(cfg).items() if not k.startswith("_")})

    async def _set_log_level(self, req: web.Request) -> web.Response:
        name = req.match_info["name"]
        level_name = req.query.get("level", "info").upper()
        expiry_s = int(req.query.get("expires", "300"))
        level = getattr(logging, level_name, None)
        if not isinstance(level, int):
            return web.json_response({"error": f"unknown level {level_name}"}, status=400)
        lg = logging.getLogger(name)
        old = lg.level
        lg.setLevel(level)
        # auto-restore, like admin_server.cc's expiring override (:226-263)
        existing = self._log_level_restores.pop(name, None)
        if existing is not None:
            old = existing[0]
            existing[1].cancel()
        loop = asyncio.get_running_loop()
        handle = loop.call_later(expiry_s, self._restore_level, name)
        self._log_level_restores[name] = (old, handle)
        return web.json_response({"logger": name, "level": level_name, "expires_s": expiry_s})

    def _restore_level(self, name: str) -> None:
        entry = self._log_level_restores.pop(name, None)
        if entry is not None:
            logging.getLogger(name).setLevel(entry[0])

    # ------------------------------------------------------------ views
    async def _get_brokers(self, req: web.Request) -> web.Response:
        if self.controller is not None:
            return web.json_response([
                {
                    "node_id": b.node_id, "host": b.host, "port": b.port,
                    "kafka_host": b.kafka_host, "kafka_port": b.kafka_port,
                    "membership_status": b.state.name,
                }
                for b in self.controller.members.all_brokers()
            ])
        cfg = self.broker.config
        return web.json_response([
            {
                "node_id": cfg.node_id, "host": cfg.advertised_host,
                "port": cfg.advertised_port, "kafka_host": cfg.advertised_host,
                "kafka_port": cfg.advertised_port, "membership_status": "active",
            }
        ])

    async def _membership(self, req: web.Request, op: str) -> web.Response:
        """Drain (or restore) a broker: replicated through the controller,
        reconciled cluster-wide (members_backend decommission semantics,
        commands.h:164-173). Works against ANY node — the broker's
        dispatcher forwards to the controller leader."""
        if self.controller is None:
            return web.json_response(
                {"error": "not a clustered broker"}, status=400
            )
        node_id = int(req.match_info["node_id"])
        dispatcher = getattr(self.broker, "controller_dispatcher", None)
        from redpanda_tpu.cluster.service import OP_DECOMMISSION, OP_RECOMMISSION

        opcode = OP_DECOMMISSION if op == "decommission" else OP_RECOMMISSION
        try:
            if dispatcher is not None:
                # frontend op, NOT the raw command: the leader-side
                # decommission kicks replica moves + the drain watcher
                await dispatcher.topic_op(opcode, {"node_id": node_id})
            elif op == "decommission":
                await self.controller.decommission_node(node_id)
            else:
                await self.controller.recommission_node(node_id)
        except Exception as e:
            return web.json_response({"error": str(e)}, status=500)
        return web.json_response({op: node_id})

    async def _decommission(self, req: web.Request) -> web.Response:
        return await self._membership(req, "decommission")

    async def _recommission(self, req: web.Request) -> web.Response:
        return await self._membership(req, "recommission")

    async def _get_partitions(self, req: web.Request) -> web.Response:
        out = []
        for ntp, p in self.broker.partition_manager.partitions().items():
            out.append({
                "ns": ntp.ns, "topic": ntp.topic, "partition": ntp.partition,
                "leader": p.leader_id, "is_leader": p.is_leader(),
                "high_watermark": p.high_watermark,
                "start_offset": p.start_offset,
            })
        return web.json_response(out)

    async def _ready(self, req: web.Request) -> web.Response:
        return web.json_response({"status": "ready"})

    # ------------------------------------------------------------ leadership
    async def _raft_transfer(self, req: web.Request) -> web.Response:
        if self.gm is None:
            return web.json_response({"error": "not clustered"}, status=400)
        group = int(req.match_info["group"])
        target = int(req.query.get("target", "-1"))
        c = self.gm.consensus_for(group)
        if c is None:
            return web.json_response({"error": f"unknown group {group}"}, status=404)
        ok = await c.do_transfer_leadership(target)
        return web.json_response({"success": bool(ok)})

    async def _raft_heartbeat_acks(self, req: web.Request) -> web.Response:
        """Per-group ack counts from the last heartbeat tick's batched
        tally (BASELINE config 5 vote half, ``raft_device_vote_tally``)
        plus the device plane's measured host-vs-device probe stats —
        the operator's view of whether the batched reduction runs and
        where."""
        from redpanda_tpu.raft import device_plane

        acks = {}
        if self.gm is not None:
            acks = {
                str(g): n
                for g, n in self.gm.heartbeats.last_tick_acks.items()
            }
        return web.json_response({
            "enabled": device_plane.vote_tally_enabled(),
            "last_tick_acks": acks,
            "plane": device_plane.default_plane().stats(),
        })

    async def _partition_transfer(self, req: web.Request) -> web.Response:
        if self.gm is None:
            return web.json_response({"error": "not clustered"}, status=400)
        topic = req.match_info["topic"]
        partition = int(req.match_info["partition"])
        target = int(req.query.get("target", "-1"))
        p = self.broker.get_partition(topic, partition)
        consensus = getattr(p, "consensus", None)
        if p is None or not hasattr(consensus, "do_transfer_leadership"):
            return web.json_response({"error": "unknown or non-raft partition"}, status=404)
        ok = await consensus.do_transfer_leadership(target)
        return web.json_response({"success": bool(ok)})

    async def _rebalance_leaders(self, req: web.Request) -> web.Response:
        """Shed THIS broker's excess leaderships toward under-loaded peers
        (leadership rebalancing via transfer_leadership, SURVEY §5; each
        node can only initiate transfers for groups it leads, so the
        operator — rpk cluster rebalance — calls every node's admin)."""
        if self.controller is None:
            return web.json_response({"error": "not clustered"}, status=400)
        mdc = getattr(self.broker, "metadata_cache", None)
        me = self.broker.config.node_id
        # cluster-wide leader counts over raft-backed partitions
        counts: dict[int, int] = {
            b.node_id: 0 for b in self.controller.members.all_brokers()
        }
        # a decommissioning node is absent from all_brokers() but may still
        # lead groups it should shed; it must count itself without KeyError
        counts.setdefault(me, 0)
        led_here = []  # (ntp, consensus, replicas)
        for md in self.broker.topic_table.topics().values():
            for pa in md.assignments.values():
                if pa.group < 0:
                    continue
                p = self.broker.partition_manager.get(pa.ntp)
                consensus = getattr(p, "consensus", None)
                if (
                    p is not None
                    and p.is_leader()
                    and hasattr(consensus, "do_transfer_leadership")
                ):
                    # this node's own count comes from live raft state, NOT
                    # the gossip cache: under load dissemination lags by
                    # seconds, and a stale self-count makes the node believe
                    # it is already at fair and refuse to shed
                    counts[me] += 1
                    led_here.append((pa.ntp, consensus, list(pa.replicas)))
                else:
                    leader = mdc.get_leader(pa.ntp) if mdc else pa.leader
                    if leader == me:
                        # gossip says we lead it but raft says we don't:
                        # stale entry — we cannot know the real leader, so
                        # leave it uncounted rather than inflate our count
                        continue
                    if leader in counts:
                        counts[leader] += 1
        fair = max(1, round(sum(counts.values()) / len(counts)))
        transferred = []
        for ntp, consensus, replicas in led_here:
            if counts.get(me, 0) <= fair:
                break
            # most under-loaded replica of THIS partition takes it
            candidates = [r for r in replicas if r != me and r in counts]
            if not candidates:
                continue
            target = min(candidates, key=lambda r: counts[r])
            if counts[target] >= counts[me] - 1:
                continue  # transfer would not improve balance
            try:
                ok = await consensus.do_transfer_leadership(target)
            except Exception as e:
                # transfer already in flight / target mid-replica-move:
                # skip this partition, keep balancing the rest
                logger.debug("rebalance transfer %s -> %d skipped: %s",
                             ntp, target, e)
                continue
            if ok:
                counts[me] -= 1
                counts[target] += 1
                transferred.append(
                    {"ns": ntp.ns, "topic": ntp.topic, "partition": ntp.partition,
                     "to": target}
                )
        return web.json_response({"transferred": transferred, "leader_counts": counts})

    # ------------------------------------------------------------ users
    async def _list_users(self, req: web.Request) -> web.Response:
        return web.json_response(self.broker.security.credentials.users())

    async def _create_user(self, req: web.Request) -> web.Response:
        from redpanda_tpu.security import SecurityManager

        body = await req.json()
        try:
            cmd = SecurityManager.create_user_cmd(
                body["username"], body["password"],
                body.get("algorithm", "SCRAM-SHA-256"),
            )
        except KeyError as e:
            return web.json_response({"error": f"missing field {e}"}, status=400)
        try:
            await self.broker.replicate_security_cmd(cmd)
        except Exception as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"created": body["username"]})

    async def _update_user(self, req: web.Request) -> web.Response:
        from redpanda_tpu.security import SecurityManager

        body = await req.json()
        cmd = SecurityManager.update_user_cmd(
            req.match_info["user"], body["password"],
            body.get("algorithm", "SCRAM-SHA-256"),
        )
        try:
            await self.broker.replicate_security_cmd(cmd)
        except Exception as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"updated": req.match_info["user"]})

    async def _delete_user(self, req: web.Request) -> web.Response:
        from redpanda_tpu.security import SecurityManager

        try:
            await self.broker.replicate_security_cmd(
                SecurityManager.delete_user_cmd(req.match_info["user"])
            )
        except Exception as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"deleted": req.match_info["user"]})

    # ------------------------------------------------------------ failure probes
    async def _reload_tls(self, req: web.Request) -> web.Response:
        """Hot certificate reload on every TLS listener
        (application.cc:704-719 credential reload)."""
        reloaded = []
        for name, ctx in self.tls_contexts.items():
            try:
                if ctx is not None and ctx.reload():
                    reloaded.append(name)
            except Exception as e:
                return web.json_response(
                    {"error": f"{name}: {e}", "reloaded": reloaded}, status=500
                )
        return web.json_response({"reloaded": reloaded})

    # ------------------------------------------------------------ data policy
    async def _list_policies(self, req: web.Request) -> web.Response:
        return web.json_response(
            {
                t: {"name": p.name, "spec": p.spec_json}
                for t, p in self.broker.data_policies.policies().items()
            }
        )

    async def _set_policy(self, req: web.Request) -> web.Response:
        topic = req.match_info["topic"]
        body = await req.json()
        try:
            await self.broker.set_data_policy(
                topic, body.get("name", "policy"), body["spec"]
            )
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"status": "ok"})

    async def _delete_policy(self, req: web.Request) -> web.Response:
        await self.broker.delete_data_policy(req.match_info["topic"])
        return web.json_response({"status": "ok"})

    async def _list_probes(self, req: web.Request) -> web.Response:
        return web.json_response(
            {
                "enabled": honey_badger.enabled,
                "modules": honey_badger.modules(),
                "armed": honey_badger.armed(),
                # remaining injections for count-limited (one-shot) probes
                "counts": honey_badger.armed_counts(),
            }
        )

    async def _set_probe(self, req: web.Request) -> web.Response:
        module = req.match_info["module"]
        probe = req.match_info["probe"]
        typ = req.match_info["type"]
        # arming a name nothing ever injects must fail loudly, not 200:
        # a typo'd module would silently neuter a whole fault campaign
        known = honey_badger.modules()
        if module not in known or probe not in known[module]:
            return web.json_response(
                {"error": f"unknown probe {module}.{probe}", "modules": known},
                status=404,
            )
        count = None
        if "count" in req.query:
            try:
                count = int(req.query["count"])
                if count < 1:
                    raise ValueError(count)
            except ValueError:
                return web.json_response(
                    {"error": f"count must be a positive integer, got "
                              f"{req.query['count']!r}"},
                    status=400,
                )
        delay_ms = None
        if "delay_ms" in req.query:
            # the injected-delay knob is process-local state; a REMOTE
            # chaos driver (multi-process loadgen, rpk) has no other way
            # to size the fault it is arming in this broker
            try:
                delay_ms = int(req.query["delay_ms"])
                if delay_ms < 1:
                    raise ValueError(delay_ms)
            except ValueError:
                return web.json_response(
                    {"error": f"delay_ms must be a positive integer, got "
                              f"{req.query['delay_ms']!r}"},
                    status=400,
                )
        honey_badger.enable()
        if delay_ms is not None:
            honey_badger.delay_ms = delay_ms
        if typ == "exception":
            honey_badger.set_exception(module, probe, count)
        elif typ == "delay":
            honey_badger.set_delay(module, probe, count)
        elif typ == "wedge":
            honey_badger.set_wedge(module, probe, count)
        elif typ == "terminate":
            honey_badger.set_termination(module, probe, count)
        elif typ == "corrupt":
            honey_badger.set_corrupt(module, probe, count)
        else:
            return web.json_response({"error": f"unknown type {typ}"}, status=400)
        body = {"armed": f"{module}.{probe}", "type": typ}
        if count is not None:
            body["count"] = count
        if delay_ms is not None:
            body["delay_ms"] = delay_ms
        return web.json_response(body)

    async def _unset_probe(self, req: web.Request) -> web.Response:
        module = req.match_info["module"]
        probe = req.match_info["probe"]
        # same posture as arming: a typo'd disarm answered 200 would leave
        # the real probe silently armed and the operator believing the
        # broker healthy
        known = honey_badger.modules()
        if module not in known or probe not in known[module]:
            return web.json_response(
                {"error": f"unknown probe {module}.{probe}", "modules": known},
                status=404,
            )
        honey_badger.unset(module, probe)
        if not honey_badger.armed():
            # last probe disarmed: drop the registry back to its zero-cost
            # disabled state, or every probe site keeps paying the enabled
            # check + injection lookup until process restart
            honey_badger.disable()
        return web.json_response({"disarmed": f"{module}.{probe}"})

    # ------------------------------------------------------------ resources
    async def _resources(self, req: web.Request) -> web.Response:
        """The budget plane (resource_mgmt): per-account occupancy/peaks,
        the pressure signal, admission controller stats and the autotune
        launch knobs — what `rpk debug resources` renders and the loadgen
        overload gate judges (peak occupancy must stay <= budget)."""
        if req.query.get("federated", "").lower() in ("1", "true", "yes"):
            # the read-side federation plane: every node's account
            # occupancy merged (limits/held/peaks sum; occupancy and
            # pressure report the worst node) — `rpk debug resources
            # --federated`, and the occupancy column for cluster timelines
            from redpanda_tpu.observability import federation

            body = await federation.assemble_cluster_resources(
                self._admin_targets(), headers=self._peer_headers()
            )
            return web.json_response(body)
        plane = getattr(self.broker, "budget_plane", None)
        if plane is None:
            return web.json_response(
                {"enabled": False, "hint": "no budget plane installed"}
            )
        body = {"enabled": True, **plane.snapshot()}
        ctrl = getattr(self.broker, "produce_admission", None)
        if ctrl is not None:
            body["produce_admission"] = ctrl.snapshot()
        api = getattr(self.broker, "coproc_api", None)
        if api is not None:
            body["coproc_admission"] = api.engine.stats().get("admission")
            body["autotune"] = api.engine.governor.autotune_snapshot()
        return web.json_response(body)

    # ------------------------------------------------------------ archival
    async def _archival_run_once(self, req: web.Request) -> web.Response:
        """Drive one reconcile+upload pass NOW (tiered-storage scenarios:
        loadgen archives closed segments on demand instead of waiting for
        the scheduler cadence). Returns the number of segment uploads."""
        arch = self.archival
        if arch is None:
            return web.json_response(
                {"error": "archival disabled (cloud_storage_enabled=false)"},
                status=409,
            )
        uploads = await arch.run_once()
        return web.json_response({"uploads": uploads})

    async def _archival_status(self, req: web.Request) -> web.Response:
        arch = self.archival
        if arch is None:
            return web.json_response({"enabled": False})
        return web.json_response({
            "enabled": True,
            "interval_s": arch.interval_s,
            "archivers": {
                str(ntp): {
                    "uploaded_segments": len(a.manifest.segments),
                    "last_uploaded_offset": a.manifest.last_uploaded_offset,
                }
                for ntp, a in arch.archivers.items()
            },
        })

    # ------------------------------------------------------------ coproc
    async def _coproc_status(self, req: web.Request) -> web.Response:
        """Engine fault/breaker/stage state for `rpk debug coproc` — the
        operator's one-stop view of whether the device path is healthy or
        the engine is running demoted on the host fallback."""
        api = getattr(self.broker, "coproc_api", None)
        if api is None:
            return web.json_response(
                {"enabled": False, "hint": "coproc_enable is false"}
            )
        from redpanda_tpu import native
        from redpanda_tpu.observability.probes import (
            COPROC_ENGINE_PHASES,
            coproc_tick_hist,
            storage_append_crossing_batches_hist,
        )

        stats = api.engine.stats()
        return web.json_response({
            "enabled": True,
            # platform / device_kind / count the engine's programs run on
            # (+ a warning when no accelerator was found and the CPU
            # backend was not asked for)
            "device": stats.pop("device", None),
            # the hot byte paths' native library: a failed build shows
            # here, never as a silent switch to the numpy twins
            "native": native.status(),
            "scripts": api.active_scripts(),
            # the pacemaker's read-ahead, from the tick histogram's sums:
            # read_hidden_us / read_us is the share of its read that ran
            # inside the previous tick's engine phase (a backlog engages it)
            "read_ahead": {
                "ticks": coproc_tick_hist["tick"].hist.count,
                "read_us": coproc_tick_hist["read"].hist.sum,
                "read_hidden_us": coproc_tick_hist["read_hidden"].hist.sum,
            },
            # the engine phase of a tick as the sum of its legs
            # (probes.COPROC_ENGINE_PHASES; the worker's two calls and their
            # self times are stats' t_submit / t_harvest / t_*_self)
            "tick_account": {
                "ticks": coproc_tick_hist["engine_run"].hist.count,
                "engine_us": coproc_tick_hist["engine"].hist.sum,
                **{
                    phase + "_us": coproc_tick_hist[phase].hist.sum
                    for phase in COPROC_ENGINE_PHASES
                },
            },
            # the log's offset-assigning appends (every partition's: produce,
            # materialized write): batches framed over the framing calls
            # that framed them, one native crossing a list where the native
            # library serves, a call a batch on the per-batch loop
            "append": {
                "framings": storage_append_crossing_batches_hist.hist.count,
                "batches": storage_append_crossing_batches_hist.hist.sum,
            },
            "breaker": stats.pop("breaker", None),
            # multi-chip meshrunner block surfaced explicitly (devices,
            # mesh-vs-single decision + probe, per-device rows, demotions)
            # so `rpk debug coproc` renders it without digging in stats;
            # popped like breaker so the stats dump doesn't repeat it
            "mesh": stats.pop("mesh", None),
            "stats": stats,
        })

    async def _governor(self, req: web.Request) -> web.Response:
        """The coproc decision plane (coproc/governor.py): every adaptive
        decision this process made — columnar backend,
        mesh-vs-single, device_lz4, breaker transitions, harvest path,
        adaptive deadlines — as a journal (newest-first, with
        measured inputs + verdict + reason + active-config snapshot) plus
        the live per-domain posture. ``?limit=N`` caps the journal slice,
        ``?domain=NAME`` filters it. `rpk debug governor` renders this."""
        from redpanda_tpu.coproc import governor as gov_mod

        try:
            limit = max(1, int(req.query.get("limit", "64")))
        except ValueError:
            return web.json_response(
                {"error": "limit must be an int"}, status=400
            )
        domain = req.query.get("domain")
        if domain is not None and domain not in gov_mod.DOMAINS:
            return web.json_response(
                {"error": f"unknown domain {domain!r}",
                 "domains": list(gov_mod.DOMAINS)},
                status=404,
            )
        body = {
            "domains": list(gov_mod.DOMAINS),
            "journal": gov_mod.journal.entries(limit=limit, domain=domain),
            "summary": gov_mod.journal.summary(),
        }
        api = getattr(self.broker, "coproc_api", None)
        if api is None:
            # the journal is process-wide (probes may have run without a
            # live engine), but there is no posture without one
            body["enabled"] = False
        else:
            g = api.engine.governor
            body["enabled"] = True
            body["posture"] = g.posture()
            body["breaker"] = g.aggregate_breaker_snapshot()
        return web.json_response(body)

    # ------------------------------------------------------------ slo
    async def _slo(self, req: web.Request) -> web.Response:
        """Judge the active SLO spec (observability/slo.py) over the probe
        histograms. ``?mark=NAME`` narrows the window to observations since
        that named baseline (POST /v1/slo/mark?name=NAME); without it the
        verdicts cover the process lifetime. Breaching objectives carry
        trace exemplars resolvable via /v1/trace/slow."""
        from redpanda_tpu.observability import tracer
        from redpanda_tpu.observability.slo import slo

        mark = req.query.get("mark")
        if req.query.get("federated", "").lower() in ("1", "true", "yes"):
            # judge the active spec over the MERGED multi-node scrape
            # instead of this process's registry — `rpk debug slo
            # --federated`; marks live in the federated engine, so a
            # federated mark brackets a cluster-wide incident window
            fed = self._federation()
            try:
                report = await fed.evaluate(slo.spec, mark=mark)
            except KeyError:
                return web.json_response(
                    {"error": f"unknown federated mark {mark!r}",
                     "marks": fed.marks()},
                    status=404,
                )
            report["marks"] = fed.marks()
            return web.json_response(report)
        try:
            report = slo.evaluate(mark=mark)
        except KeyError:
            return web.json_response(
                {"error": f"unknown mark {mark!r}", "marks": slo.marks()},
                status=404,
            )
        report["exemplars_enabled"] = tracer.enabled
        report["marks"] = slo.marks()
        return web.json_response(report)

    async def _slo_mark(self, req: web.Request) -> web.Response:
        """Snapshot every histogram as a named baseline, so a later
        GET /v1/slo?mark=NAME judges only what happened since — the
        bracket an operator (or the chaos suite) puts around an incident.
        ``?federated=1`` snapshots the merged cluster scrape instead."""
        from redpanda_tpu.observability.slo import slo

        name = req.query.get("name", "default")
        if req.query.get("federated", "").lower() in ("1", "true", "yes"):
            meta = await self._federation().set_mark(name)
            return web.json_response({
                "mark": name, "federated": True,
                "nodes": meta.get("nodes", []),
                "unreachable": meta.get("unreachable", []),
            })
        series = slo.set_mark(name)
        return web.json_response({"mark": name, "series": series})

    async def _slo_exemplars(self, req: web.Request) -> web.Response:
        """THIS node's breach-exemplar rings (probes.exemplars_snapshot),
        per series key — the per-node leg the federated SLO plane fans out
        to so a cluster-level breach entry can carry the CULPRIT node's
        exemplar trace ids (each resolvable via /v1/trace/cluster/{tid})."""
        from redpanda_tpu.observability import probes, tracer

        return web.json_response({
            "node": self.broker.config.node_id,
            "enabled": tracer.enabled,
            "exemplars": probes.exemplars_snapshot(),
        })

    # ------------------------------------------------------------ pulse
    async def _profile(self, req: web.Request) -> web.Response:
        """pandapulse status: flight-recorder summary, per-stage totals,
        wall-profiler folded-stack top, and the event loop's newest stalls
        (loopwatch) — `rpk debug profile` renders this; profile.json in the
        debug bundle."""
        from redpanda_tpu.observability.loopwatch import loopwatch
        from redpanda_tpu.observability.pulse import pulse

        try:
            top = max(1, int(req.query.get("top", "20")))
        except ValueError:
            return web.json_response({"error": "top must be an int"}, status=400)
        body = pulse.snapshot(top=top)
        body["node"] = self.broker.config.node_id
        body["loop_stalls"] = loopwatch.stalls()
        if req.query.get("stacks", "").lower() in ("1", "true", "yes"):
            body["stacks"] = pulse.profiler.stacks()
            body["folded"] = pulse.profiler.folded()
        return web.json_response(body)

    async def _profile_timeline(self, req: web.Request) -> web.Response:
        """Chrome trace-event JSON (Perfetto-loadable) of the newest
        ``?launches=N`` launch lifecycles, governor verdicts + admission
        episodes as instant events on the same clock. ``?federated=1``
        assembles the cluster timeline across every broker's admin (the
        /v1/trace/cluster posture: unreachable nodes reported, not fatal)."""
        from redpanda_tpu.observability.pulse import pulse

        try:
            launches = max(0, int(req.query.get("launches", "0")))
        except ValueError:
            return web.json_response(
                {"error": "launches must be an int"}, status=400
            )
        if req.query.get("federated", "").lower() in ("1", "true", "yes"):
            from redpanda_tpu.observability import federation

            body = await federation.assemble_cluster_timeline(
                self._admin_targets(), launches,
                headers=self._peer_headers(),
            )
            return web.json_response(body)
        return web.json_response(pulse.timeline(launches=launches))

    # ------------------------------------------------------------ history
    async def _history(self, req: web.Request) -> web.Response:
        """The pandatrend metrics-history ring (observability/history.py):
        bounded per-interval delta windows with derived rates/quantiles,
        the EWMA band state, and breach totals — `rpk debug trend` renders
        this. ``?series=SUBSTR`` filters every per-series section,
        ``?limit=N`` caps the window slice (newest last), ``?federated=1``
        fans out to every broker's admin and returns the per-node rings
        side by side (windows never merge across wall clocks)."""
        from redpanda_tpu.observability.history import history

        series = req.query.get("series") or None
        try:
            limit = max(0, int(req.query.get("limit", "0")))
        except ValueError:
            return web.json_response(
                {"error": "limit must be an int"}, status=400
            )
        if req.query.get("federated", "").lower() in ("1", "true", "yes"):
            from redpanda_tpu.observability import federation

            body = await federation.assemble_cluster_history(
                self._admin_targets(), series=series, limit=limit,
                headers=self._peer_headers(),
            )
            return web.json_response(body)
        body = history.snapshot(series=series, limit=limit)
        body["node"] = self.broker.config.node_id
        return web.json_response(body)

    # ------------------------------------------------------------ metrics
    async def _metrics(self, req: web.Request) -> web.Response:
        return web.Response(
            text=registry.render_prometheus(),
            content_type="text/plain",
            charset="utf-8",
        )

    # ------------------------------------------------------------ traces
    async def _trace_recent(self, req: web.Request) -> web.Response:
        from redpanda_tpu.observability import tracer

        try:
            # clamp: recent(0) means "whole ring" programmatically, but an
            # HTTP limit<=0 must never turn a poll into a full-ring dump
            limit = max(1, int(req.query.get("limit", "20")))
        except ValueError:
            return web.json_response({"error": "limit must be an int"}, status=400)
        return web.json_response({
            "enabled": tracer.enabled,
            "spans_recorded": tracer.spans_recorded,
            "traces": tracer.recent(limit),
        })

    async def _trace_slow(self, req: web.Request) -> web.Response:
        from redpanda_tpu.observability import tracer

        try:
            limit = max(1, int(req.query.get("limit", "50")))
        except ValueError:
            return web.json_response({"error": "limit must be an int"}, status=400)
        return web.json_response({
            "enabled": tracer.enabled,
            "threshold_ms": tracer.slow_threshold_us / 1000.0,
            "spans": tracer.slow(limit),
        })

    # ---------------------------------------------------- cluster traces
    def _admin_targets(self) -> list[tuple[int, str | None]]:
        """[(node_id, admin_base_url | None)] for every active broker —
        the fan-out set of the pandascope plane. Self always dials its own
        listener (uniform HTTP path, no special case); a peer that never
        advertised an admin port (pre-pandascope log entry) maps to None
        and is reported unreachable rather than silently skipped."""
        me = self.broker.config.node_id
        self_url = f"http://{self.host}:{self.port}"
        if self.controller is None:
            return [(me, self_url)]
        out: list[tuple[int, str | None]] = []
        for b in self.controller.members.all_brokers():
            if b.node_id == me:
                out.append((b.node_id, self_url))
            elif getattr(b, "admin_port", 0):
                out.append((b.node_id, f"http://{b.host}:{b.admin_port}"))
            else:
                out.append((b.node_id, None))
        return out or [(me, self_url)]

    def _peer_headers(self) -> dict[str, str] | None:
        """Credentials the pandascope fan-out presents to PEER admins.
        Under auth every /v1/trace/* and federated route requires them —
        without this, enabling admin_api_require_auth would silently turn
        every cluster view into a one-node 'partial' (each peer 401s and
        reads as unreachable). The bearer token is cluster-wide by
        operational convention (one token in the deploy config); a
        cluster running per-node tokens degrades to the visible partial
        view rather than anything silent."""
        if self.require_auth and self.auth_token:
            return {"Authorization": f"Bearer {self.auth_token}"}
        return None

    async def _trace_by_id(self, req: web.Request) -> web.Response:
        """THIS node's surviving spans for one trace id — the per-node leg
        the cluster assembler fans out to."""
        from redpanda_tpu.observability import tracer

        try:
            tid = int(req.match_info["trace_id"])
        except ValueError:
            return web.json_response(
                {"error": "trace_id must be an int"}, status=400
            )
        spans = tracer.spans_for(tid)
        me = self.broker.config.node_id
        return web.json_response({
            "trace_id": tid,
            "node": me,
            "epoch": tracer.epoch_wall,
            "spans": spans,
        })

    async def _trace_cluster(self, req: web.Request) -> web.Response:
        """ONE trace assembled cluster-wide: fan out to every node's
        /v1/trace/id/<tid>, merge by trace id — produce → raft replicate →
        follower append → coproc dispatch as a single multi-node trace."""
        from redpanda_tpu.observability import federation

        try:
            tid = int(req.match_info["trace_id"])
        except ValueError:
            return web.json_response(
                {"error": "trace_id must be an int"}, status=400
            )
        trace = await federation.assemble_cluster_trace(
            self._admin_targets(), tid, headers=self._peer_headers()
        )
        return web.json_response(trace)

    async def _trace_cluster_slow(self, req: web.Request) -> web.Response:
        """Assembled cluster traces for the LOCAL slow ring's newest trace
        ids — what the debug bundle captures as cluster_traces.json: the
        requests that breached, stitched across every broker they touched."""
        from redpanda_tpu.observability import federation, tracer

        try:
            limit = max(1, min(16, int(req.query.get("limit", "5"))))
        except ValueError:
            return web.json_response({"error": "limit must be an int"}, status=400)
        tids: list[int] = []
        for s in tracer.slow(limit=200):
            if s["trace_id"] not in tids:
                tids.append(s["trace_id"])
            if len(tids) >= limit:
                break
        targets = self._admin_targets()
        headers = self._peer_headers()
        # concurrent per-trace fan-outs: the assemblies are independent,
        # and awaiting them serially would multiply an unreachable node's
        # timeout by the trace count (a dead peer must cost ONE timeout,
        # not one per bundle entry)
        traces = list(
            await asyncio.gather(
                *(
                    federation.assemble_cluster_trace(
                        targets, tid, headers=headers
                    )
                    for tid in tids
                )
            )
        )
        return web.json_response({
            "enabled": tracer.enabled,
            "targets": [
                {"node": n, "url": u, "reachable": u is not None}
                for n, u in targets
            ],
            "traces": traces,
        })

    # ---------------------------------------------------- federation
    def _federation(self):
        if self._federated_slo is None:
            from redpanda_tpu.observability.federation import FederatedSlo

            self._federated_slo = FederatedSlo(
                self._admin_targets, headers_fn=self._peer_headers
            )
        return self._federated_slo

    async def _federation_metrics(self, req: web.Request) -> web.Response:
        """The merged cluster window in JSON registry form: every series
        scraped off every node's /metrics, HdrHists merged additively with
        the per-node contributions preserved under the node label —
        federated_metrics.json in the debug bundle."""
        from redpanda_tpu.observability import federation

        snap = await federation.federated_snapshot(
            self._admin_targets(), headers=self._peer_headers()
        )
        meta = snap.pop("__meta__", {})
        return web.json_response({
            "nodes": meta.get("nodes", []),
            "unreachable": meta.get("unreachable", []),
            "partial": bool(meta.get("unreachable")),
            "series": snap,
        })
