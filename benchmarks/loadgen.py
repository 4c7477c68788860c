"""Load generator and consumer workers: JAX-free child processes of
``run.py``, each pinned to cores of its own, talking to the broker through
``wire.py`` only.

    python3 loadgen.py producer|consumer <spec.json>

The parent writes one JSON command per line to the worker's stdin and reads
one JSON reply per line from its stdout. Every wire byte a producer sends is
built before it answers ``ready``. Times are ``time.monotonic()``
(CLOCK_MONOTONIC), which all processes of one machine share.

Producer commands: ``seed`` (a stream's whole backlog, as fast as acks
allow), ``run`` (open loop at a fixed rate, until ``stop_at``), ``dump``,
``exit``.
Consumer commands: ``consume`` (tail a topic), ``progress``, ``drop``, ``finish``
(drain, decode, compare, reduce), ``exit``.

What a deployment feeds the broker is its configuration file's to say, and
both kinds of worker take it from their spec:

- ``producer.compression``: the codec producers seal their batches with
  (``wire.CODECS``; ``"none"`` when the key is absent).
- ``documents.generator``: ``"<module>.<function>"``, ``<module>`` a file of
  this directory loaded by path (``load_generator``); ``documents.params``,
  an optional flat object, is passed as keyword arguments after
  ``(seed, partitions, records_per_partition, only)``.

The generator's contract (``check_documents`` and ``Consumer.build`` hold
it in every rehearsal, ``test_inputs.py`` over every generator a
configuration names):

1. it returns ``values[p][i]``, non-empty ``bytes``, for every partition
   in ``only`` (all of them when ``only`` is None), ``records_per_partition``
   to a partition;
2. the values are a function of the seed (with the two sizes and the
   params) alone;
3. a partition's stream does not depend on ``only``: producers ask for
   their own partitions, the consumer for all, and both must hold the
   same bytes;
4. at the rehearsal's size every value is inside the configuration's
   ``documents.bytes_min`` / ``bytes_max`` (``code`` grows a digit or two
   at a cell's own size: ``docs.py`` reads 924-1,062 B there);
5. the configuration's reference recovers ``p * records_per_partition + i``
   from each output it keeps (``sequence(output)``): ``transform_rate``
   counts the inputs a consumer has seen the outcome of by it.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import checks  # noqa: E402
import wire  # noqa: E402

DEFAULT_GENERATOR = "docs.make_documents"
_GENERATOR_NAME = re.compile(r"^([a-z_][a-z0-9_]*)\.([a-z_][a-z0-9_]*)$")


def _load_by_path(module_name: str, path: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str):
    """``references/<name>.py``: ``reference(value, **params)`` and
    ``sequence(output)``."""
    return _load_by_path("perfbench_reference_" + name,
                         os.path.join(HERE, "references", name + ".py"))


def load_generator(name: str):
    """The function ``documents.generator`` names; an unknown one is an
    error that names the key."""
    m = _GENERATOR_NAME.match(name) if isinstance(name, str) else None
    path = os.path.join(HERE, m.group(1) + ".py") if m else ""
    if not m or not os.path.isfile(path):
        raise wire.InputShapeError(f"documents.generator {name!r}: not \"<module>.<function>\" "
                         "of a file of benchmarks/")
    fn = getattr(_load_by_path("perfbench_documents_" + m.group(1), path), m.group(2), None)
    if not callable(fn):
        raise wire.InputShapeError(f"documents.generator {name!r}: benchmarks/{m.group(1)}.py "
                         f"has no function {m.group(2)}")
    return fn


def document_source(documents: dict | None):
    """A configuration's ``documents`` -> ``source(stream, only=None)``,
    the generator resolved once."""
    documents = documents or {}
    make = load_generator(documents.get("generator", DEFAULT_GENERATOR))
    params = documents.get("params") or {}

    def source(stream: dict, only=None) -> dict[int, list[bytes]]:
        return make(stream["seed"], stream["partitions"],
                    stream["records_per_partition"], only, **params)

    return source


def check_documents(values, documents: dict, stream: dict, only) -> None:
    """Points 1 and 4 of the generator's contract, on what one call
    returned."""
    name = documents.get("generator", DEFAULT_GENERATOR)
    lo, hi = documents.get("bytes_min", 1), documents.get("bytes_max", float("inf"))
    want = list(only if only is not None else range(stream["partitions"]))
    if not isinstance(values, dict) or any(p not in values for p in want):
        raise wire.InputShapeError(f"documents.generator {name!r}: no values for some of partitions {want}")
    for p in want:
        part = values[p]
        if len(part) != stream["records_per_partition"]:
            raise wire.InputShapeError(f"documents.generator {name!r}: {len(part)} values in partition "
                             f"{p}, not {stream['records_per_partition']}")
        for i, v in enumerate(part):
            if type(v) is not bytes or not max(lo, 1) <= len(v) <= hi:
                what = f"{len(v)} B" if type(v) is bytes else type(v).__name__
                raise wire.InputShapeError(f"documents.generator {name!r}: values[{p}][{i}] is {what}; "
                                 f"the configuration states bytes of {lo}-{hi} B")


def reply(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


async def commands():
    loop = asyncio.get_running_loop()
    while True:
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line:
            return
        yield json.loads(line)


# ------------------------------------------------------------------ producer
class Producer:
    def __init__(self, spec: dict):
        self.spec = spec
        self.partitions = range(*spec["partition_range"])
        self.conns: list[wire.Conn] = []
        self.frames: dict[str, dict[int, list[bytes]]] = {}
        self.batch_bytes: dict[str, dict[int, list[int]]] = {}
        self.next_batch: dict[str, dict[int, int]] = {}
        # per sent batch: stream, partition, batch index, due, sent, acked,
        # error, bytes of the record batch on the wire
        self.log: list[tuple] = []
        self.stop_at = float("inf")
        self.exhausted = False  # a stream ran out of built frames
        self.run_task: asyncio.Task | None = None

    def build(self) -> None:
        from redpanda_tpu.hashing.crc32c import crc32c  # see wire.py docstring

        rpb = self.spec["records_per_batch"]
        documents = self.spec.get("documents") or {}
        source = document_source(documents)
        codec = wire.codec_id(self.spec.get("compression", "none"))
        compressor = wire.zstd_compressor() if codec == wire.ZSTD else None
        corr = 0
        self.documents_s = self.seal_s = 0.0  # of build_s: the generator; batch + CRC + codec
        for name, stream in self.spec["streams"].items():
            t_d = time.monotonic()
            values = source(stream, self.partitions)
            self.documents_s += time.monotonic() - t_d
            if self.spec.get("check_documents"):
                check_documents(values, documents, stream, self.partitions)
            per_part, sizes = {}, {}
            for p in self.partitions:
                part = values[p]
                frames, sizes[p] = [], []
                for s in range(0, len(part), rpb):
                    corr += 1
                    t_b = time.monotonic()
                    batch = wire.build_batch(part[s : s + rpb], crc32c, codec=codec,
                                             compressor=compressor)
                    self.seal_s += time.monotonic() - t_b
                    frames.append(wire.produce_frame(stream["topic"], p, batch, corr))
                    sizes[p].append(len(batch))
                per_part[p] = frames
            self.frames[name], self.batch_bytes[name] = per_part, sizes
            self.next_batch[name] = {p: 0 for p in self.partitions}

    async def connect(self, host: str, port: int) -> None:
        n = self.spec["connections"]
        self.conns = [await wire.Conn(host, port).open() for _ in range(n)]

    def conn_for(self, p: int) -> wire.Conn:
        return self.conns[p % len(self.conns)]

    def _send(self, name: str, p: int, due: float) -> asyncio.Future | None:
        """Send partition ``p``'s next batch of stream ``name``."""
        k = self.next_batch[name][p]
        frames = self.frames[name][p]
        if k >= len(frames):
            return None
        self.next_batch[name][p] = k + 1
        entry = [name, p, k, due, time.monotonic(), None, None, self.batch_bytes[name][p][k]]
        self.log.append(entry)
        fut = self.conn_for(p).request(frames[k])

        def acked(f: asyncio.Future, entry=entry) -> None:
            entry[5] = time.monotonic()
            if f.cancelled() or f.exception() is not None:
                entry[6] = -1
            else:
                entry[6] = wire.parse_produce_response(f.result())[0]

        fut.add_done_callback(acked)
        return fut

    async def seed(self, name: str, inflight: int) -> dict:
        t0 = time.monotonic()

        async def one(p: int) -> None:
            pending: list[asyncio.Future] = []
            while True:
                fut = self._send(name, p, time.monotonic())
                if fut is None:
                    break
                pending.append(fut)
                if len(pending) >= inflight:
                    await pending.pop(0)
            for f in pending:
                await f

        await asyncio.gather(*(one(p) for p in self.partitions))
        return {"seconds": time.monotonic() - t0}

    async def run_open(self, name: str, batches_per_s: float, t_start: float) -> None:
        """Batch k is due at t_start + k / rate, round robin over this
        worker's partitions: the schedule never looks at the acks."""
        parts = list(self.partitions)
        k = 0
        while True:
            due = t_start + k / batches_per_s
            if due >= self.stop_at:
                return
            now = time.monotonic()
            if due > now:
                await asyncio.sleep(due - now)
            if self._send(name, parts[k % len(parts)], due) is None:
                self.exhausted = True
                return
            k += 1

    def dump(self, path: str) -> dict:
        with open(path, "w") as f:
            json.dump(self.log, f)
        errors = sum(1 for e in self.log if e[6] not in (0, None))
        unacked = sum(1 for e in self.log if e[6] is None)
        return {"batches": len(self.log), "errors": errors, "unacked": unacked,
                "exhausted": self.exhausted}


async def producer_main(spec: dict) -> None:
    prod = Producer(spec)
    t0 = time.monotonic()
    prod.build()
    reply({"ready": True, "build_s": time.monotonic() - t0,
           "documents_s": prod.documents_s, "seal_s": prod.seal_s,
           "frames": sum(len(f) for s in prod.frames.values() for f in s.values())})
    async for cmd in commands():
        op = cmd["cmd"]
        if op == "connect":
            await prod.connect(cmd["host"], cmd["port"])
            reply({"ok": True})
        elif op == "seed":
            reply(await prod.seed(cmd["stream"], cmd.get("inflight", 4)))
        elif op == "run":
            prod.stop_at = float("inf")
            prod.run_task = asyncio.create_task(
                prod.run_open(cmd["stream"], cmd["batches_per_s"], cmd["t_start"]))
            reply({"ok": True})
        elif op == "stop_at":
            prod.stop_at = cmd["t"]
            await prod.run_task
            # the last acks
            deadline = time.monotonic() + 30.0
            while any(e[6] is None for e in prod.log) and time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            reply({"ok": True})
        elif op == "dump":
            reply(prod.dump(cmd["path"]))
        elif op == "exit":
            break
    for c in prod.conns:
        await c.close()


# ------------------------------------------------------------------ consumer
class Consumer:
    def __init__(self, spec: dict):
        self.spec = spec
        self.documents_s = 0.0
        self.ref = load_reference(spec["reference"]["name"])
        self.params = spec["reference"].get("params", {})
        # per stream: kept[p] = input indices the reference keeps, expected[p]
        # = their outputs, in order
        self.kept: dict[str, dict[int, list[int]]] = {}
        self.expected: dict[str, dict[int, list[bytes]]] = {}
        self.tails: dict[str, "Tail"] = {}
        self.conns: list[wire.Conn] = []

    def build(self) -> None:
        fn, params = self.ref.reference, self.params
        documents = self.spec.get("documents") or {}
        source = document_source(documents)
        check = self.spec.get("check_documents")
        for name, stream in self.spec["streams"].items():
            t_d = time.monotonic()
            values = source(stream)
            self.documents_s += time.monotonic() - t_d
            if check:
                check_documents(values, documents, stream, None)
            kept, expected = {}, {}
            for p, part in values.items():
                outs = [fn(v, **params) for v in part]
                kept[p] = [i for i, o in enumerate(outs) if o is not None]
                expected[p] = [o for o in outs if o is not None]
                if check and [self.ref.sequence(o) for o in expected[p]] != [
                        p * len(part) + i for i in kept[p]]:
                    raise wire.InputShapeError(  # point 5 of the generator's contract
                        f"documents.generator {documents.get('generator', DEFAULT_GENERATOR)!r}: "
                        f"reference {self.spec['reference']['name']!r} does not recover "
                        f"p * records_per_partition + i from what it keeps of partition {p}")
            self.kept[name], self.expected[name] = kept, expected


class Tail:
    """Long-polls every partition of one materialized topic and keeps what
    it fetched, raw, with the time each batch arrived."""

    def __init__(self, topic: str, partitions: int, conns: list[wire.Conn], spec: dict,
                 want: dict[int, int] | None):
        self.topic = topic
        self.partitions = partitions
        self.conns = conns
        self.spec = spec
        self.want = want  # records per partition that end a fixed amount of work
        self.offsets = {p: 0 for p in range(partitions)}
        self.counts = {p: 0 for p in range(partitions)}
        # per partition: (arrival time, n_records, raw batch)
        self.batches: dict[int, list[tuple[float, int, bytes]]] = {
            p: [] for p in range(partitions)
        }
        self.empty_polls = {p: 0 for p in range(partitions)}
        self.t_complete: float | None = None
        self.errors: list[str] = []
        self.stopping = False
        self.tasks: list[asyncio.Task] = []

    def start(self) -> None:
        n = len(self.conns)
        for c, conn in enumerate(self.conns):
            parts = [p for p in range(self.partitions) if p % n == c]
            if parts:
                self.tasks.append(asyncio.create_task(self._loop(conn, parts)))

    def complete(self) -> bool:
        return self.want is not None and all(
            self.counts[p] >= n for p, n in self.want.items()
        )

    async def _loop(self, conn: wire.Conn, parts: list[int]) -> None:
        s = self.spec
        corr = 0
        while not self.stopping:
            corr += 1
            frame = wire.fetch_frame(
                self.topic, {p: self.offsets[p] for p in parts}, corr,
                s["max_wait_ms"], s["min_bytes"], s["partition_max_bytes"],
            )
            try:
                resp = await conn.request(frame)
            except ConnectionError as exc:
                self.errors.append(repr(exc))
                return
            now = time.monotonic()
            missing = False
            for p, err, _hwm, blob in wire.parse_fetch_response(resp):
                if err in (wire.ERR_UNKNOWN_TOPIC, wire.ERR_NOT_LEADER):
                    missing = True  # the topic is made by its first write
                    continue
                if err:
                    self.errors.append(f"fetch {self.topic}/{p}: error {err}")
                    self.stopping = True
                    continue
                walked = wire.walk_batches(blob)
                if not walked:
                    self.empty_polls[p] += 1
                    continue
                self.empty_polls[p] = 0
                for start, end, last_offset, count in walked:
                    self.batches[p].append((now, count, bytes(blob[start:end])))
                    self.counts[p] += count
                    self.offsets[p] = last_offset + 1
            if self.t_complete is None and self.complete():
                self.t_complete = now
            if missing:
                await asyncio.sleep(0.02)

    async def stop(self) -> None:
        self.stopping = True
        for t in self.tasks:
            t.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)


async def consumer_main(spec: dict) -> None:
    from redpanda_tpu.hashing.crc32c import crc32c  # fetched batches' CRCs are checked

    cons = Consumer(spec)
    t0 = time.monotonic()
    cons.build()
    reply({"ready": True, "build_s": time.monotonic() - t0, "documents_s": cons.documents_s,
           "expected": {n: sum(len(v) for v in e.values()) for n, e in cons.expected.items()}})
    async for cmd in commands():
        op = cmd["cmd"]
        if op == "connect":
            cons.host, cons.port = cmd["host"], cmd["port"]
            reply({"ok": True})
        elif op == "consume":
            name = cmd["stream"]
            stream = spec["streams"][name]
            conns = [
                await wire.Conn(cons.host, cons.port).open()
                for _ in range(spec["connections"])
            ]
            cons.conns += conns
            want = None
            if cmd.get("fixed_work"):
                want = {p: len(v) for p, v in cons.expected[name].items()}
            tail = Tail(cmd["topic"], stream["partitions"], conns, spec, want)
            cons.tails[cmd["topic"]] = tail
            tail.start()
            reply({"ok": True})
        elif op == "progress":
            tail = cons.tails[cmd["topic"]]
            reply({"records": sum(tail.counts.values()), "t_complete": tail.t_complete,
                   "errors": tail.errors[:3]})
        elif op == "drop":
            tail = cons.tails.pop(cmd["topic"])
            await tail.stop()
            for c in tail.conns:
                await c.close()
                cons.conns.remove(c)
            reply({"ok": True})
        elif op == "finish":
            name = cmd["stream"]
            tail = cons.tails[cmd["topic"]]
            rpb = spec["records_per_batch"]
            # inputs acknowledged per partition, in records
            acked = {int(p): n * rpb for p, n in cmd["acked_batches"].items()}
            want = {
                p: sum(1 for i in cons.kept[name][p] if i < acked.get(p, 0))
                for p in range(tail.partitions)
            }
            deadline = time.monotonic() + cmd["drain_timeout_s"]
            # all that is due, then two more empty polls of every partition:
            # nothing may be materialized twice
            while time.monotonic() < deadline and not tail.errors:
                done = all(tail.counts[p] >= want[p] for p in want)
                if done and all(n >= 2 for n in tail.empty_polls.values()):
                    break
                await asyncio.sleep(0.05)
            await tail.stop()
            t_dec = time.monotonic()
            got, arrivals = checks.decode_tail(tail.batches, crc32c)
            expected = {p: cons.expected[name][p][: want[p]] for p in want}
            result = checks.compare(expected, got)
            result["fetch_errors"] = tail.errors[:5]
            result["reduce"] = checks.reduce_window(
                kept=cons.kept[name], acked=acked, arrivals=arrivals,
                producer_logs=cmd.get("producer_logs", []), stream=name,
                records_per_batch=rpb,
                t0=cmd["t0"], t1=cmd["t1"], fixed_work=bool(cmd.get("fixed_work")),
                t_complete=tail.t_complete,
            )
            if cmd.get("control"):
                result["control"] = checks.control_verdicts(expected, got)
            result["decode_compare_s"] = time.monotonic() - t_dec
            with open(cmd["path"], "w") as f:
                json.dump(result, f)
            reply({"ok": True})
        elif op == "exit":
            break
    for tail in cons.tails.values():
        await tail.stop()
    for c in cons.conns:
        await c.close()


def main() -> None:
    role = sys.argv[1]
    with open(sys.argv[2]) as f:
        spec = json.load(f)
    if spec.get("cores"):
        os.sched_setaffinity(0, spec["cores"])
    sys.path.insert(0, spec["repo"])
    try:
        asyncio.run(producer_main(spec) if role == "producer" else consumer_main(spec))
    except wire.InputShapeError as exc:
        sys.exit(f"{role}: {exc}")


if __name__ == "__main__":
    main()
