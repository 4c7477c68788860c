#!/usr/bin/env python3
"""The benchmark's entry point: one cell, one run.

    python3 benchmarks/run.py --workload <config>.<traffic> --seed N --seconds S --trace 0|1

A new process that never initializes a JAX backend: it starts the broker
(the system under test) through ``launcher.py``, a producer or two and a
consumer through ``loadgen.py``, warms the cell's own traffic up inside
``setup_s``, measures one window, checks what the window produced against
the plain reference, and prints one JSON object as its last line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a data file found by the name in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``layer_metrics/<metric>.json``, ``references/<fn>.py``. A configuration
file also names what its producers feed (``input_shape``): the document
generator, ``documents.generator`` (+ ``documents.params``), and the codec
its producers seal batches with, ``producer.compression``.

``--manifest PATH`` (the driver does not pass it): look the cell and its
configuration up in another file than ``BENCHMARK.json``, so that a
configuration which is no cell yet can be run.

``--rehearse 1`` (not a measurement): the same path at the traffic file's
``rehearsal`` size on whatever platform JAX has, no ``metrics`` printed.
Without it a broker whose device is not a TPU ends the run with exit code 1
and no result line.
"""

from __future__ import annotations

T_PROCESS_START = __import__("time").monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]

import broker as broker_mod  # noqa: E402
import loadgen  # noqa: E402
import readers  # noqa: E402
import trace_reduce  # noqa: E402
import wire  # noqa: E402

WORKER = os.path.join(HERE, "loadgen.py")


class RunFailure(Exception):
    """The run cannot give a result (no chip, a worker died, a time-out)."""


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_PROCESS_START:7.2f}s] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def input_shape(config: dict) -> dict:
    """What the configuration's producers feed the broker, checked before
    anything starts: ``wire.InputShapeError`` names the key that asks for a
    generator or a codec the benchmark does not have."""
    documents = config.get("documents") or {}
    shape = {
        "generator": documents.get("generator", loadgen.DEFAULT_GENERATOR),
        "params": documents.get("params") or {},
        "compression": (config.get("producer") or {}).get("compression", "none"),
    }
    loadgen.load_generator(shape["generator"])
    wire.codec_id(shape["compression"])
    return shape


# ------------------------------------------------------------------ workers
class Worker:
    """A ``loadgen.py`` child: JSON lines in, JSON lines out."""

    def __init__(self, role: str, spec: dict, run_dir: str, tag: str):
        self.tag = tag
        spec_path = os.path.join(run_dir, f"{tag}.spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        self.err_path = os.path.join(run_dir, f"{tag}.err")
        with open(self.err_path, "wb") as err:
            self.proc = subprocess.Popen(
                [sys.executable, WORKER, role, spec_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
                start_new_session=True, env={**os.environ, "PYTHONPATH": REPO},
            )

    def send(self, cmd: dict) -> None:
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        self.proc.stdin.flush()

    def recv(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            with open(self.err_path, "rb") as f:
                tail = f.read()[-3000:].decode(errors="replace")
            raise RunFailure(f"worker {self.tag} ended early:\n{tail}")
        return json.loads(line)

    def call(self, cmd: dict) -> dict:
        self.send(cmd)
        return self.recv()

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send({"cmd": "exit"})
                self.proc.wait(timeout=10.0)
            except (OSError, subprocess.TimeoutExpired):
                pass
        broker_mod.stop_group(self.proc)


def split_cores(n_producers: int) -> dict:
    """The cores this process may use, read and not assumed, shared out:
    the last ones to the consumer, the producers and this harness, the
    rest to the broker."""
    cores = sorted(os.sched_getaffinity(0))
    need = n_producers + 2
    if len(cores) >= need + 2:
        clients = cores[-need:]
        return {"broker": cores[:-need], "producers": [[c] for c in clients[:n_producers]],
                "consumer": [clients[-2]], "harness": [clients[-1]], "n": len(cores)}
    # too few cores to keep anyone apart (a rehearsal): everyone everywhere
    return {"broker": cores, "producers": [cores] * n_producers, "consumer": cores,
            "harness": cores, "n": len(cores)}


# ------------------------------------------------------------------ control plane
PRIMER_TOPIC = "bench-primer"


async def control_plane(kafka_port: int, topics: list[tuple[str, int]]) -> None:
    """Create the topics through the program's own client, then send one
    acknowledged batch to a scratch topic (set-up, not timed, not judged).
    The first produce of a broker process runs the front end's one-shot CRC
    backend probe in a worker thread, and produces that arrive meanwhile can
    pass it: pipelined batches of one partition were appended out of wire
    order on a cold broker (PERF.md section 6). No measured stream may be
    the first to produce."""
    import wire
    from redpanda_tpu.hashing.crc32c import crc32c
    from redpanda_tpu.kafka.client import KafkaClient

    client = await KafkaClient([("127.0.0.1", kafka_port)]).connect()
    try:
        for name, partitions in [*topics, (PRIMER_TOPIC, 1)]:
            await client.create_topic(name, partitions=partitions, replication=1)
    finally:
        await client.close()
    conn = await wire.Conn("127.0.0.1", kafka_port).open()
    try:
        frame = wire.produce_frame(PRIMER_TOPIC, 0, wire.build_batch([b"{}"], crc32c), 1)
        err, _base = wire.parse_produce_response(await conn.request(frame))
        if err:
            raise RunFailure(f"the primer batch was refused: error {err}")
    finally:
        await conn.close()


async def deploy(kafka_port: int, script: str, spec: dict | None, topics: list[str]) -> None:
    """Deploy a transform the way an operator does, one record on
    ``coprocessor_internal_topic``; with no ``spec``, remove it."""
    from redpanda_tpu.coproc import wasm_event
    from redpanda_tpu.kafka.client import KafkaClient
    from redpanda_tpu.models.fundamental import COPROC_INTERNAL_TOPIC

    record = (wasm_event.make_remove_record(script) if spec is None
              else wasm_event.make_deploy_record(script, json.dumps(spec), topics))
    batch = wasm_event.deploy_batch([record])
    client = await KafkaClient([("127.0.0.1", kafka_port)]).connect()
    try:
        t_end = time.monotonic() + 60.0
        while True:  # the internal topic appears once the listener made it
            try:
                await client.produce_batches(COPROC_INTERNAL_TOPIC, 0, [batch])
                return
            except Exception:
                if time.monotonic() > t_end:
                    raise
                await asyncio.sleep(0.25)
    finally:
        await client.close()


# ------------------------------------------------------------------ snapshots
def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def snapshot(brk: broker_mod.Broker, cache_dir: str) -> dict:
    """The program's counters at one instant."""
    t = time.monotonic()
    status = brk.status()
    return {
        "t": t,
        "status": status,
        "stats": status.get("stats") or {},
        "metrics": readers.parse_prometheus(brk.admin("/metrics").decode()),
        "gc": brk.control({"cmd": "gc"})["collections"],
        "cache_entries": cache_entries(cache_dir),
    }


def tree_bytes(directory: str) -> int:
    """Bytes of the files under ``directory`` (the broker's data directory:
    what the seeded backlog is on disk, as its codec left it)."""
    total = 0
    for root, _dirs, files in os.walk(directory):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:
                pass  # a segment rolled away under the walk
    return total


def journal_size(brk: broker_mod.Broker) -> int:
    gov = json.loads(brk.admin("/v1/governor?domain=admission&limit=1000"))
    return len(gov.get("journal") or [])


def selections(status: dict) -> dict:
    """This run's one-shot choices, as the program reports them: a spread
    between runs can then be laid at one of them."""
    s = status.get("stats") or {}
    posture = (s.get("governor") or {}).get("posture") or {}
    auto = posture.get("autotune") or {}
    return {
        "columnar_backend": s.get("columnar_backend"),
        "columnar_probe": s.get("columnar_probe"),
        "parse_path": s.get("parse_path"),
        "parse_probe": s.get("parse_probe"),
        "host_pool_probe": s.get("host_pool_probe"),
        "host_workers": s.get("host_workers"),
        "posture": {k: v for k, v in posture.items()
                    if k not in ("autotune", "breakers", "deadlines_ms", "engine")},
        "group_ticks": auto.get("group_ticks"),
        "launch_depth": auto.get("launch_depth"),
        "compiled_programs": s.get("compiled_programs"),
    }


def health(after: dict) -> dict[str, float]:
    """The guarantees a run can see in the program's own counters. Limit 0
    for each."""
    s = after["stats"]
    open_breakers = sum(
        1 for b in (s.get("breakers") or {}).values()
        if b.get("state") != "closed" or b.get("trips")
    )
    return {
        "n_fallback_rows": s.get("n_fallback_rows", 0),
        "n_retries": s.get("n_retries", 0),
        "coproc_failures_total": readers.metric_total(after["metrics"], "coproc_failures_total"),
        "breakers_not_closed": open_breakers,
    }


class Settling:
    """The warm-up's end: the program's state has been still for
    ``quiet_s`` (and ``min_s`` have passed), or ``cap_s`` have."""

    def __init__(self, rule: dict, t_start: float):
        self.rule, self.t_start = rule, t_start
        self.seen, self.last_change, self.hit_cap = None, t_start, False

    def done(self, state: tuple) -> bool:
        now = time.monotonic()
        if state != self.seen:
            self.seen, self.last_change = state, now
        age = now - self.t_start
        if age >= self.rule["min_s"] and now - self.last_change >= self.rule["quiet_s"]:
            return True
        self.hit_cap = age >= self.rule["cap_s"]
        return self.hit_cap


# ------------------------------------------------------------------ the run
class Run:
    def __init__(self, args, cell: dict, config: dict, traffic: dict, inputs: dict):
        self.args = args
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.rehearse = bool(args.rehearse)
        if self.rehearse:
            self.traffic = {**traffic, **traffic.get("rehearsal", {})}
        self.kind = self.traffic["kind"]
        self.seconds = float(args.seconds)
        self.run_dir = tempfile.mkdtemp(prefix="perfbench_")
        self.cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            REPO, ".jax_cache"
        )
        self.brk: broker_mod.Broker | None = None
        self.producers: list[Worker] = []
        self.consumer: Worker | None = None
        self.topic = "bench"
        self.script = config["script"]["name"]
        self.mtopic = f"{self.topic}.${self.script}$"
        self.notes: dict = {"input": inputs}
        self.buckets: set = set()
        # --trace 1: capture name -> [its directory, seconds it ran]
        self.captures: dict[str, list] = {}
        self.capture_until: float | None = None

    # -------------------------------------------------------------- streams
    def streams(self) -> dict:
        t, c = self.traffic, self.config
        partitions = c["topic"]["partitions"]
        rpb = c["records_per_batch"]

        def stream(topic: str, records: int, seed: int) -> dict:
            per_part = -(-records // (partitions * rpb)) * rpb
            return {"topic": topic, "seed": seed, "partitions": partitions,
                    "records_per_partition": per_part}

        seed = int(self.args.seed)
        if self.kind == "catchup":
            return {
                "warm": stream(self.topic + "-warm", t["warmup_records"], seed + 1),
                "main": stream(self.topic, t["records"], seed),
            }
        horizon = t["warmup"]["cap_s"] + self.seconds + t["slack_s"]
        return {"main": stream(self.topic, int(t["records_per_s"] * horizon), seed)}

    # -------------------------------------------------------------- set-up
    def worker_specs(self, cores: dict) -> tuple[list[dict], dict]:
        """What each producer and the consumer is started with: every byte a
        producer will send follows from its spec."""
        t, c = self.traffic, self.config
        n_prod = t["producers"]
        partitions = c["topic"]["partitions"]
        base = {"repo": REPO, "streams": self.streams(),
                "records_per_batch": c["records_per_batch"],
                "documents": c.get("documents") or {}, "check_documents": self.rehearse}
        producers = [{
            **base, "cores": cores["producers"][i],
            "partition_range": [i * partitions // n_prod, (i + 1) * partitions // n_prod],
            "connections": t["producer_connections"],
            "compression": self.notes["input"]["compression"],
        } for i in range(n_prod)]
        consumer = {**base, "cores": cores["consumer"], "reference": c["reference"],
                    **t["consumer"]}
        return producers, consumer

    def start(self) -> None:
        c = self.config
        self.cores = split_cores(self.traffic["producers"])
        os.sched_setaffinity(0, self.cores["harness"])
        say(f"cores: {self.cores}")
        self.brk = broker_mod.Broker(
            REPO, self.run_dir, c["broker_properties"], self.cores["broker"]
        )
        producers, consumer = self.worker_specs(self.cores)
        streams = consumer["streams"]
        for i, spec in enumerate(producers):
            self.producers.append(Worker("producer", spec, self.run_dir, f"producer{i}"))
        self.consumer = Worker("consumer", consumer, self.run_dir, "consumer")

        device = self.brk.wait_ready()
        say(f"broker ready in {self.brk.ready_s:.1f} s: {device}")
        self.device = device
        if device.get("platform") != "tpu" and not self.rehearse:
            raise RunFailure(f"the broker's device is {device!r}: not a TPU, no measurement")
        if not self.rehearse and device.get("count", 0) < self.cell["chips"]:
            raise RunFailure(f"{device.get('count')} chips, the cell asks for {self.cell['chips']}")
        asyncio.run(control_plane(
            self.brk.ports["kafka"],
            [(s["topic"], s["partitions"]) for s in streams.values()],
        ))
        for w in self.producers:
            r = w.recv()
            say(f"{w.tag} built {r['frames']} frames in {r['build_s']:.1f} s "
                f"(documents {r['documents_s']:.1f}, batches sealed {r['seal_s']:.1f})")
            w.call({"cmd": "connect", "host": "127.0.0.1", "port": self.brk.ports["kafka"]})
        r = self.consumer.recv()
        say(f"consumer built the reference in {r['build_s']:.1f} s "
            f"(documents {r['documents_s']:.1f}): {r['expected']} outputs")
        self.consumer.call({"cmd": "connect", "host": "127.0.0.1",
                            "port": self.brk.ports["kafka"]})

    def all_producers(self, cmd: dict) -> list[dict]:
        for w in self.producers:
            w.send(cmd)
        return [w.recv() for w in self.producers]

    def deploy(self, script: str, topic: str) -> None:
        asyncio.run(deploy(self.brk.ports["kafka"], script, self.config["script"]["spec"], [topic]))

    def remove(self, script: str) -> None:
        asyncio.run(deploy(self.brk.ports["kafka"], script, None, []))

    def wait_consumer(self, topic: str, until: float, what: str) -> dict:
        """Poll the consumer until the topic's fixed work is fetched or
        ``until`` passes."""
        while True:
            p = self.consumer.call({"cmd": "progress", "topic": topic})
            if p["errors"]:
                raise RunFailure(f"{what}: {p['errors']}")
            if p["t_complete"] is not None or time.monotonic() >= until:
                return p
            self.tick_capture()
            time.sleep(0.05)

    def program_state(self) -> tuple:
        """What the warm-up waits to settle: the row buckets that have a
        program, the compile cache, the governor's launch knobs (admission
        journal) and the probes."""
        s = self.brk.status().get("stats") or {}
        self.buckets |= {(c["lane"], c["n_pad"]) for c in s.get("compiled_programs") or []}
        return (len(self.buckets), cache_entries(self.cache_dir), journal_size(self.brk), s.get("columnar_backend"),
                s.get("parse_path"), json.dumps(s.get("host_pool_probe"), sort_keys=True))

    # -------------------------------------------------------------- catchup
    def run_catchup(self) -> dict:
        t = self.traffic
        for name in ("warm", "main"):
            rs = self.all_producers({"cmd": "seed", "stream": name, "inflight": t["seed_inflight"]})
            say(f"seeded {name} in {max(r['seconds'] for r in rs):.1f} s")
        self.notes["data_dir_bytes_after_seeding"] = tree_bytes(os.path.join(self.run_dir, "data"))
        # warm-up: the same script under other names over the warm-up topic,
        # each drained to the end and removed (a new script reads its topic
        # from the start), until the program's one-shot choices and its
        # launch knobs have been still for quiet_s
        warm_topic = self.topic + "-warm"
        self.start_capture("first_launches")
        t_w = time.monotonic()
        settling, rounds = Settling(t["warmup"], t_w), 0
        while True:
            warm_script = f"{self.script}_warm{rounds}"
            mtopic = f"{warm_topic}.${warm_script}$"
            self.consumer.call({"cmd": "consume", "stream": "warm", "fixed_work": True,
                                "topic": mtopic})
            t_r = time.monotonic()
            self.deploy(warm_script, warm_topic)
            p = self.wait_consumer(mtopic, t_r + t["warmup_timeout_s"], "warm-up")
            if p["t_complete"] is None:
                raise RunFailure(f"warm-up not drained in {t['warmup_timeout_s']} s")
            self.remove(warm_script)
            self.consumer.call({"cmd": "drop", "topic": mtopic})
            rounds += 1
            if settling.done(self.program_state()):
                break
        self.notes.update(warmup_s=time.monotonic() - t_w, warmup_rounds=rounds,
                          warmup_hit_cap=settling.hit_cap)
        say(f"warm-up: {rounds} drains in {self.notes['warmup_s']:.1f} s, "
            f"cap hit: {settling.hit_cap}")
        time.sleep(t.get("settle_s", 0.5))

        self.consumer.call({"cmd": "consume", "stream": "main", "fixed_work": True,
                            "topic": self.mtopic})
        before = snapshot(self.brk, self.cache_dir)
        t0 = time.monotonic()
        self.setup_s = t0 - T_PROCESS_START
        self.deploy(self.script, self.topic)
        t1 = t0 + self.seconds
        self.start_capture("window", t0)
        p = self.wait_consumer(self.mtopic, t1, "window")
        t_end = min(p["t_complete"] or t1, t1)
        self.end_capture()
        after = snapshot(self.brk, self.cache_dir)
        say(f"window closed after {t_end - t0:.2f} s (drained: {p['t_complete'] is not None})")
        return {"t0": t0, "t1": t1, "before": before, "after": after, "fixed_work": True}

    # -------------------------------------------------------------- paced
    def run_paced(self) -> dict:
        t = self.traffic
        self.consumer.call({"cmd": "consume", "stream": "main", "topic": self.mtopic})
        t_load = time.monotonic() + 0.2
        n_prod = len(self.producers)
        rate = t["records_per_s"] / self.config["records_per_batch"] / n_prod
        for w in self.producers:
            w.call({"cmd": "run", "stream": "main", "batches_per_s": rate, "t_start": t_load})
        # the script is deployed over the first half second of traffic, so
        # its first launch is a representative one (>= 1,024 rows) and the
        # program takes its one-shot probes on it, in every run
        time.sleep(max(t_load + t["deploy_after_s"] - time.monotonic(), 0))
        self.start_capture("first_launches")
        self.deploy(self.script, self.topic)
        # warm-up: the cell's own load until no program has compiled and no
        # admission-journal entry has appeared for quiet_s
        settling = Settling(t["warmup"], t_load)
        while True:
            time.sleep(0.25)
            self.tick_capture()
            if settling.done(self.program_state()):
                break
        self.notes.update(warmup_s=time.monotonic() - t_load, warmup_hit_cap=settling.hit_cap)
        say(f"warm-up {self.notes['warmup_s']:.1f} s, cap hit: {settling.hit_cap}")
        before = snapshot(self.brk, self.cache_dir)
        t0 = time.monotonic()
        self.setup_s = t0 - T_PROCESS_START
        t1 = t0 + self.seconds
        for w in self.producers:  # they stop themselves at t1
            w.send({"cmd": "stop_at", "t": t1})
        self.start_capture("window", t0)
        self.end_capture()
        time.sleep(max(t1 - time.monotonic(), 0))
        after = snapshot(self.brk, self.cache_dir)
        for w in self.producers:
            w.recv()
        return {"t0": t0, "t1": t1, "before": before, "after": after, "fixed_work": False}

    # -------------------------------------------------------------- trace
    def start_capture(self, name: str, t0: float | None = None) -> None:
        """(``--trace 1``) Bracket the seconds the traffic file gives capture
        ``name`` with the profiler. A run has two: ``first_launches``, from
        the first deploy on, where the program takes its one-shot probes (all
        the device work of a lane that then chooses the host), and ``window``,
        inside the measured window, which the per-layer metrics read."""
        if not int(self.args.trace):
            return
        self.end_capture()
        tr = self.traffic["trace"][name]
        if t0 is not None:
            time.sleep(max(t0 + tr["start_after_s"] - time.monotonic(), 0))
        self.captures[name] = [os.path.join(self.run_dir, "trace_" + name), time.monotonic()]
        self.brk.control({"cmd": "trace_start", "dir": self.captures[name][0]})
        self.capture_until = time.monotonic() + min(tr["seconds"], self.seconds)

    def tick_capture(self) -> None:
        """Stop a capture that has run its length (called from the polling
        loops of warm-up and window)."""
        if self.capture_until is not None and time.monotonic() >= self.capture_until:
            self.capture_until = None
            newest = list(self.captures.values())[-1]
            newest[1] = time.monotonic() - newest[1]
            self.brk.control({"cmd": "trace_stop"}, timeout_s=120.0)

    def end_capture(self) -> None:
        if self.capture_until is not None:
            time.sleep(max(self.capture_until - time.monotonic(), 0))
            self.tick_capture()

    # -------------------------------------------------------------- results
    def finish(self, win: dict) -> dict:
        logs = []
        acked: dict[str, int] = {}
        for w in self.producers:
            path = os.path.join(self.run_dir, f"{w.tag}.log.json")
            d = w.call({"cmd": "dump", "path": path})
            self.notes[w.tag] = d
            logs.append(path)
            with open(path) as f:
                for name, p, _k, _due, _sent, _ack, err, _bytes in json.load(f):
                    if name == "main" and err == 0:
                        acked[str(p)] = acked.get(str(p), 0) + 1
        result_path = os.path.join(self.run_dir, "consumer.result.json")
        self.consumer.call({
            "cmd": "finish", "stream": "main", "topic": self.mtopic, "acked_batches": acked,
            "drain_timeout_s": self.traffic["drain_timeout_s"], "producer_logs": logs,
            "t0": win["t0"], "t1": win["t1"], "fixed_work": win["fixed_work"],
            "control": bool(self.args.control), "path": result_path,
        })
        final = snapshot(self.brk, self.cache_dir)
        memory = self.brk.control({"cmd": "memory"})
        return {"client": load_json(result_path), "final": final, "memory": memory}

    def keep_evidence(self) -> None:
        """A run that ends in a failure: the broker's log and the newest
        event-loop stalls it will still tell of, copied beside the file
        ``--keep-trace`` names or, without one, the log's end on stderr,
        before ``stop`` removes the run directory."""
        if self.brk is None:
            return
        try:
            stalls = json.loads(self.brk.admin("/v1/profile", 5.0)).get("loop_stalls")
        except (OSError, ValueError):
            stalls = None  # the broker answers no more
        if self.args.keep_trace:
            os.makedirs(os.path.dirname(self.args.keep_trace) or ".", exist_ok=True)
            if os.path.isfile(self.brk.log_path):
                shutil.copy(self.brk.log_path, self.args.keep_trace + ".broker.log")
            with open(self.args.keep_trace + ".loop_stalls.json", "w") as f:
                json.dump(stalls, f)
            return
        tail = self.brk.tail(200_000).splitlines()[-60:]
        print("broker.log, its last lines:\n" + "\n".join(tail), file=sys.stderr)
        print(f"loop_stalls: {json.dumps(stalls)}", file=sys.stderr)

    def stop(self) -> None:
        for w in self.producers + ([self.consumer] if self.consumer else []):
            w.stop()
        if self.brk is not None:
            self.brk.stop()
        if self.args.keep_trace and "window" in self.captures:
            os.makedirs(os.path.dirname(self.args.keep_trace) or ".", exist_ok=True)
            shutil.copy(trace_reduce.find_xplane(self.captures["window"][0]), self.args.keep_trace)
        shutil.rmtree(self.run_dir, ignore_errors=True)


def end_to_end(traffic: dict, client: dict, setup_s: float) -> dict:
    """The mix's end-to-end metrics, each a number of the clients'
    reduction named by the traffic file, and ``setup_s``."""
    out = {"setup_s": {"value": setup_s, "unit": "s"}}
    for name, (unit, path) in traffic["end_to_end"].items():
        out[name] = {"value": readers.dotted(client["reduce"], path), "unit": unit}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--control", type=int, default=0,
                    help="also judge the fetched output with one guarantee broken")
    ap.add_argument("--keep-trace", default="",
                    help="copy the window's .xplane.pb here (a failed run: its broker.log beside it)")
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="the file the cell and its configuration are looked up in")
    args = ap.parse_args()

    manifest = load_json(REPO, args.manifest)
    cell = next((w for w in manifest["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"no workload {args.workload!r} in {args.manifest}", file=sys.stderr)
        return 2
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(REPO, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if not os.path.isdir(os.path.join(REPO, "redpanda_tpu")):
        print("the program (redpanda_tpu/) is not in this checkout", file=sys.stderr)
        return 1

    try:
        inputs = input_shape(config)
    except wire.InputShapeError as exc:
        print(f"RUN FAILED: {exc}", file=sys.stderr)
        return 1
    run = Run(args, cell, config, traffic, inputs)
    try:
        run.start()
        win = {"catchup": run.run_catchup, "paced": run.run_paced}[run.kind]()
        res = run.finish(win)
        traces = {
            name: trace_reduce.reduce_profile(
                trace_reduce.load(trace_reduce.find_xplane(d)), span_s=span_s)
            for name, (d, span_s) in run.captures.items()
        }
    except (RunFailure, broker_mod.BrokerFailure) as exc:
        run.keep_evidence()
        print(f"RUN FAILED: {exc}", file=sys.stderr)
        return 1
    finally:
        run.stop()

    client = res["client"]
    red = client["reduce"]
    say(f"selections: {json.dumps(selections(res['final']['status']))}")
    say(f"notes: {json.dumps(run.notes)}")
    say(f"client: {json.dumps({k: v for k, v in red.items()})}")

    # ---- correct: every number compared, beside its limit
    checks_out = {
        "records_different": client["records_different"],
        "records_missing": client["records_missing"],
        "records_extra": client["records_extra"],
        "fetch_errors": len(client["fetch_errors"]),
        "produce_errors": red.get("produce_errors", 0)
        + sum(run.notes[w.tag]["errors"] + run.notes[w.tag]["unacked"] for w in run.producers),
        "producer_ran_out_of_frames": sum(
            1 for w in run.producers if run.notes[w.tag]["exhausted"]
        ),
        **health(res["final"]),
    }
    for name, value in checks_out.items():
        print(f"check {name} = {value} (limit 0)")
    print(f"check records_fetched = {client['records_fetched']} "
          f"(must equal records_expected = {client['records_expected']}, and be > 0)")
    correct = all(v == 0 for v in checks_out.values()) and client["records_expected"] > 0
    if client.get("first_mismatch"):
        print(f"first mismatch: {client['first_mismatch']}")
    if "control" in client:
        print(f"control (each broken guarantee caught): {json.dumps(client['control'])}")

    layer = readers.read_all(
        os.path.join(HERE, "layer_metrics"), kind=run.kind,
        before=win["before"], after=win["after"], client=red, trace=traces.get("window"),
        window_s=win["after"]["t"] - win["before"]["t"],
    )
    say("layers: " + json.dumps({k: round(v["value"], 4) for k, v in layer.items()}))
    b, a = win["before"]["stats"], win["after"]["stats"]
    say("engine counters over the window: " + json.dumps({
        k: round(a[k] - b.get(k, 0), 4) for k in sorted(a)
        if isinstance(a[k], (int, float)) and not isinstance(a[k], bool)
        and k[:2] in ("t_", "n_", "by") and a[k] != b.get(k, 0)}))
    m0, m1 = win["before"]["metrics"], win["after"]["metrics"]
    say("storage caches over the window: " + json.dumps({
        k: round(m1[k] - m0.get(k, 0.0), 4) for k in sorted(m1)
        if k.startswith(("batch_cache_", "readers_cache_")) and m1[k] != m0.get(k, 0.0)}))
    say("broker interpreter collections over the window (gen 0, 1, 2): "
        + json.dumps([y - x for x, y in zip(win["before"]["gc"], win["after"]["gc"])]))
    first_runs = win["after"]["stats"].get("n_compiles", 0) - win["before"]["stats"].get("n_compiles", 0)
    cold = win["after"]["cache_entries"] - win["before"]["cache_entries"]
    print(f"observed program_first_runs_in_window = {first_runs}; "
          f"compile cache entries written in window = {cold}")

    attempted = red.get("kept_in_window") or red["transform_rate"]["records"]
    failed = red.get("kept_unseen", 0) + (0 if correct else 1)
    mem = res["memory"]
    device = {
        "platform": mem["platform"], "kind": mem["kind"], "count": mem["count"],
        "memory_peak_bytes": max(b or 0 for b in mem["peak_bytes_in_use"]),
    }
    if run.rehearse:
        print(json.dumps({"rehearsal": True, "correct": correct, "device": device,
                          "not_metrics": {"end_to_end": end_to_end(run.traffic, client, run.setup_s),
                                          "per_layer": layer}}))
        return 0 if correct else 1
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if int(args.trace):
        out["metrics"] = layer
        # the device line and the operations are of both captures (the
        # traced seconds of this run); the idle gaps are the window's
        device.update(trace_reduce.totals(list(traces.values())))
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(list(traces.values())),
            "idle_gaps": traces["window"]["breakdown"]["idle_gaps"],
        }
        if device["busy_s"] <= 0:
            print("RUN FAILED: no operation ran on the device in the traced seconds",
                  file=sys.stderr)
            return 1
    else:
        out["metrics"] = end_to_end(run.traffic, client, run.setup_s)
    out["device"] = device
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
