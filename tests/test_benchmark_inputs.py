"""What a configuration file says its producers feed the broker, as tier-1.

The broker-less cases of ``benchmarks/test_inputs.py`` (PR 32; run by hand
there, beside the whole-run rehearsals): the per-codec round trip of a
sealed batch through the program's decoder and ``batch_codec.explode_ptrs``,
the generator contract over every generator a configuration names, the
pinned digests of the four uncompressed cells' produce frames, the Zstd
fixture's frames, ``docs_text.py``'s pins and the refusals by name. They
are that file's functions, collected here, so the two cannot drift; the
benchmark's modules are loaded by path, as ``test_serving_timers.py`` loads
``readers``.

PR 37 added a generator that is not ``docs.py``'s document
(``docs_nexmark.make_events``, configuration ``nexmark64p-q1``): two
properties of that file's ``test_generator_contract`` are written for the
``{"level", "code", "msg", "pad"}`` document of ~1 KB, so the case here
calls that function for every generator it fits and holds the same two
properties in NEXmark's own terms; the stream's shapes (proportions, sizes,
price law, timestamps) follow. PR 40 added a third generator
(``docs_nobench.make_objects``, configuration ``nobench64p-q2``) the same
way: the five points of the contract, then NoBench's shapes in their own
terms.
"""

import collections
import json
import os
import statistics
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import test_inputs as bench  # noqa: E402

from test_inputs import (  # noqa: E402,F401
    test_a_fixtures_reference_recovers_the_sequence_from_its_generator,
    test_a_sealed_batch_is_what_the_engine_would_stage,
    test_codec_names,
    test_docs_text_compresses_like_text_where_docs_compresses_like_filler,
    test_docs_text_is_docs_but_for_what_pad_holds,
    test_every_produce_frame_of_a_cell_is_the_parents_byte_for_byte,
    test_the_compressed_fixtures_frames_are_zstd_sealed_and_a_third_the_size,
    test_unknown_generators_are_refused_by_name,
)
from test_tail import (  # noqa: E402,F401  (PR 49: docs_tail.make_documents, json64p-v1-tail)
    test_the_generator_holds_the_contract,
    test_the_reference_recovers_each_kept_inputs_sequence_and_drops_none_for_its_size,
    test_the_size_law_gives_its_shares_within_a_percent,
)


NEXMARK = "docs_nexmark.make_events"
NEXMARK_CONFIG = os.path.join("benchmarks", "configs", "nexmark64p-q1.json")
NEXMARK_FIELDS = {
    0: ["event_type", "id", "name", "emailAddress", "creditCard", "city", "state",
        "dateTime", "extra"],
    1: ["event_type", "id", "itemName", "description", "initialBid", "reserve", "dateTime",
        "expires", "seller", "category", "extra"],
    2: ["event_type", "auction", "bidder", "price", "dateTime", "extra"],
}


NOBENCH = "docs_nobench.make_objects"
NOBENCH_CONFIG = os.path.join("benchmarks", "configs", "nobench64p-q2.json")
NOBENCH_FIELDS = ["str1", "str2", "num", "bool", "dyn1", "dyn2", "nested_arr", "nested_obj"]
OWN_SHAPES = {NEXMARK: (NEXMARK_CONFIG, 400), NOBENCH: (NOBENCH_CONFIG, 600)}


def _nobench_object(v: bytes, n: int) -> dict:
    """One object of ``docs_nobench.py`` held to NoBench's shape; returns it."""
    assert b"\\" not in v and json.dumps(json.loads(v)).encode() == v  # ", " and ": ", ASCII
    doc = json.loads(v)
    first = n % 100 * 10
    assert list(doc) == NOBENCH_FIELDS + [f"sparse_{first + k:03d}" for k in range(10)] + ["thousandth"]
    assert doc["num"] == n and doc["thousandth"] == n % 1000 and type(doc["bool"]) is bool
    assert list(doc["nested_obj"]) == ["str", "num"] and doc["nested_obj"]["num"] == n ^ 1
    assert type(doc["dyn1"]) in (int, str) and type(doc["dyn2"]) in (int, str, bool)
    assert 0 <= len(doc["nested_arr"]) <= 7 and all(type(w) is str for w in doc["nested_arr"])
    strings = [doc["str1"], doc["str2"], doc["nested_obj"]["str"]] + [
        doc[k] for k in doc if k.startswith("sparse_")]
    assert all(len(s) in (8, 16, 24, 32) and set(s) <= set("ABCDEFGHIJKLMNOPQRSTUVWXYZ234567")
               for s in strings)
    # the collision the structural read exists for: the top-level num comes first
    assert v.index(b'"num": ') < v.index(b'"nested_obj": ') < v.rindex(b'"num": ')
    return doc


@pytest.mark.parametrize("generator", bench.GENERATORS)
@pytest.mark.parametrize("prop", ["bytes", "seed_alone", "independent_of_only",
                                  "inside_the_stated_widths", "fields_in_order"])
def test_generator_contract(generator, prop):
    if generator not in OWN_SHAPES or prop in ("bytes", "seed_alone", "independent_of_only"):
        return bench.test_generator_contract(generator, prop)
    a = bench._make(generator, 2**31 + 11, 8, 64)
    if prop == "inside_the_stated_widths":
        config, too_narrow = OWN_SHAPES[generator]
        d = bench.load(config)["documents"]
        stream = {"seed": 2**31 + 11, "partitions": 8, "records_per_partition": 64}
        bench.loadgen.check_documents(a, d, stream, None)
        with pytest.raises(bench.wire.InputShapeError, match="documents.generator"):
            bench.loadgen.check_documents(a, {**d, "bytes_max": too_narrow}, stream, None)
        with pytest.raises(bench.wire.InputShapeError, match="documents.generator"):
            bench.loadgen.check_documents({**a, 2: a[2][:-1]}, d, stream, None)
    elif generator == NOBENCH:
        for p, part in a.items():
            for i, v in enumerate(part):
                _nobench_object(v, p * 64 + i)
    else:
        for p, part in a.items():
            for i, v in enumerate(part):
                n = p * 64 + i
                kind = 0 if n % 50 == 0 else 1 if n % 50 < 4 else 2
                assert b"\\" not in v and b" \"" not in v and b": " not in v
                doc = json.loads(v)
                assert list(doc) == NEXMARK_FIELDS[kind] and doc["event_type"] == kind
                assert doc["dateTime"] == 1_700_000_000_000 + n


def test_the_nexmark_configuration_feeds_the_sources_shapes():
    """Configuration ``nexmark64p-q1``: its generator under its own params
    gives the proportions, average sizes, price law and timestamps the
    source states, and its reference keeps the Bids and recovers the event
    number from each (point 5 of the contract, which ``transform_rate``
    counts by)."""
    c = bench.load(NEXMARK_CONFIG)
    assert c["documents"]["generator"] == NEXMARK and c["records_per_batch"] == 256
    assert c["producer"] == {"compression": "none"} and c["broker_properties"] == {"coproc_enable": "true"}
    per = 2000
    stream = {"seed": 2**31 + 5, "partitions": 4, "records_per_partition": per}
    values = bench.loadgen.document_source(c["documents"])(stream)
    ref, params = bench.loadgen.load_reference(c["reference"]["name"]), c["reference"]["params"]
    assert ref.BASE_MS == c["documents"]["params"]["base_ms"]
    sizes, prices, kept = collections.defaultdict(list), [], 0
    for p, part in values.items():
        outs = [(i, ref.reference(v, **params)) for i, v in enumerate(part)]
        assert [ref.sequence(o) for i, o in outs if o is not None] == [
            p * per + i for i, o in outs if o is not None]
        assert [i for i, o in outs if o is not None] == [
            i for i in range(per) if (p * per + i) % 50 >= 4]  # the Bids, all of them
        kept += sum(o is not None for _, o in outs)
        for v in part:
            doc = json.loads(v)
            sizes[doc["event_type"]].append(len(v))
            assert len(str(doc["dateTime"])) == 13
            if doc["event_type"] == 2:
                prices.append(doc["price"])
                assert doc["auction"] >= 1000 and doc["bidder"] >= 1000
    assert kept / (4 * per) == 0.92
    assert [len(sizes[k]) for k in (0, 1, 2)] == [160, 480, 7360]  # 1 : 3 : 46
    assert 195 <= statistics.mean(sizes[0]) <= 205
    assert 480 <= statistics.mean(sizes[1]) <= 520
    assert 95 <= statistics.mean(sizes[2]) <= 105
    assert max(map(max, sizes.values())) <= c["documents"]["bytes_max"] < 1024
    # round(10 ** (6u) * 100): 100 .. 10 ** 8 cents, a sixth to a decade
    assert 100 <= min(prices) and max(prices) <= 10**8
    decades = collections.Counter(len(str(x)) for x in prices)
    assert all(0.12 < decades[k] / len(prices) < 0.21 for k in range(3, 9))
    # hot auctions and bidders: half the bids on a hot auction's id (a multiple
    # of 100 over 1,000), three quarters by a hot bidder (1 over one)
    bids = [json.loads(v) for part in values.values() for v in part if v[14:15] == b"2"]
    assert 0.45 < sum(b["auction"] % 100 == 0 for b in bids) / len(bids) < 0.56
    assert 0.70 < sum(b["bidder"] % 100 == 1 for b in bids) / len(bids) < 0.80


def test_the_nobench_configuration_feeds_the_sources_shapes():
    """Configuration ``nobench64p-q2``: its generator gives NoBench's
    object (the ten sparse keys of one cluster, ``thousandth == num % 1000``,
    the ``nested_obj`` of object ``n XOR 1``, a stock library's separators,
    every value one that ``json.loads`` reads), at the sizes the
    configuration states, and its reference keeps every object and recovers
    its number (point 5 of the contract, which ``transform_rate`` counts
    by)."""
    c = bench.load(NOBENCH_CONFIG)
    assert c["documents"]["generator"] == NOBENCH and c["records_per_batch"] == 32
    assert c["producer"] == {"compression": "none"} and c["broker_properties"] == {"coproc_enable": "true"}
    assert c["reduced"] == ["brokers", "replication"] and len(c["source"]) <= 200
    per = 1000
    stream = {"seed": 2**31 + 5, "partitions": 4, "records_per_partition": per}
    values = bench.loadgen.document_source(c["documents"])(stream)
    ref, params = bench.loadgen.load_reference(c["reference"]["name"]), c["reference"]["params"]
    docs, sizes = [], []
    for p, part in values.items():
        outs = [ref.reference(v, **params) for v in part]
        assert all(len(o) == 70 for o in outs)  # every object kept
        assert [ref.sequence(o) for o in outs] == [p * per + i for i in range(per)]
        for i, v in enumerate(part):
            doc = _nobench_object(v, p * per + i)
            partner = json.loads(part[i ^ 1])
            assert doc["nested_obj"] == {"str": partner["str1"], "num": partner["num"]}
            assert outs[i][2 : 2 + len(partner["str1"])] == partner["str1"].encode()
            docs.append(doc)
            sizes.append(len(v))
    d = c["documents"]
    assert d["bytes_min"] <= min(sizes) and max(sizes) <= d["bytes_max"] < 1024
    assert 600 <= statistics.mean(sizes) <= 670
    # any one sparse attribute is in 1% of the objects; the dynamic types' shares
    assert sum("sparse_110" in doc for doc in docs) == len(docs) // 100
    assert 0.93 < sum(type(doc["dyn1"]) is int for doc in docs) / len(docs) < 0.97
    kinds = collections.Counter(type(doc["dyn2"]) for doc in docs)
    assert all(0.29 < kinds[k] / len(docs) < 0.38 for k in (int, str, bool))
    lengths = collections.Counter(len(doc["nested_arr"]) for doc in docs)
    assert sorted(lengths) == list(range(8)) and all(0.09 < n / len(docs) < 0.16 for n in lengths.values())
    words = collections.Counter(w for doc in docs for w in doc["nested_arr"])
    assert len(words) == 50 and max(words.values()) < 3 * min(words.values())
    # an odd partition size has no partner for its last object: refused, not wrapped
    with pytest.raises(ValueError, match="even"):
        bench.loadgen.document_source(c["documents"])({**stream, "records_per_partition": 7})


def test_the_pinned_cells_are_the_manifests_uncompressed_cells():
    """``bench.test_the_pinned_cells_are_the_manifests_cells`` as it has to
    read since PR 33 added a cell whose producers compress and PR 37 one
    that feeds another generator: the frames pinned at 29ef861 are those of
    every cell whose configuration feeds ``docs.make_documents``
    uncompressed, and every other cell names another generator or another
    codec (its frames are its own generator's, which the contract cases
    above hold by shape)."""
    man = bench.manifest()
    inputs = {}
    for w in man["workloads"]:
        config = bench.config_of(man, w["name"])
        inputs[w["name"]] = (config["documents"]["generator"],
                             config.get("producer", {}).get("compression", "none"))
    plain = {cell for cell, fed in inputs.items() if fed == ("docs.make_documents", "none")}
    assert plain == set(bench.PARENT_FRAMES)
    for cell in set(inputs) - set(bench.PARENT_FRAMES):
        generator, codec = inputs[cell]
        assert generator != "docs.make_documents" or codec != "none", cell
        assert callable(bench.loadgen.load_generator(generator)) and bench.wire.codec_id(codec) >= 0


# ------------------------------------------------------------------ json64p-v1-zstd (PR 45)
V1_ZSTD_CELL = "json64p-v1-zstd.paced"


def test_the_live_zstd_configuration_feeds_the_zstd_catchup_cells_frames_byte_for_byte():
    """``json64p-v1-zstd`` is ``json64p-v1``'s script and reference over
    ``json64p-v1map-zstd``'s input: given one stream, its producers build
    the frames that configuration's producers build, byte for byte, and
    they are Zstd-sealed batches of 32 of ``docs_text.py``'s documents."""
    man = bench.manifest()
    new = bench.config_of(man, V1_ZSTD_CELL)
    old = bench.config_of(man, "json64p-v1map-zstd.catchup")
    plain = bench.config_of(man, "json64p-v1.catchup")
    assert new["documents"] == old["documents"] and new["producer"] == old["producer"]
    assert (new["script"], new["reference"], new["topic"], new["guarantees"]) == (
        plain["script"], plain["reference"], plain["topic"], plain["guarantees"])
    assert new["reduced"] == ["brokers", "replication"] == list(new["reduced_from"])
    ours = bench.built_producers(man, V1_ZSTD_CELL)
    theirs = []
    for prod in ours:
        spec = {**prod.spec, "documents": old["documents"],
                "compression": old["producer"]["compression"]}
        twin = bench.loadgen.Producer(spec)
        twin.build()
        theirs.append(twin)
    n, digest = bench.frames_digest(ours)
    assert (n, digest) == bench.frames_digest(theirs) and n == 64 * 19  # 608 records a partition
    from redpanda_tpu.hashing.crc32c import crc32c

    sizes = [s for prod in ours for per_part in prod.batch_bytes["main"].values() for s in per_part]
    assert 250 < sum(sizes) / (32 * len(sizes)) < 420  # wire bytes a record
    frame = ours[1].frames["main"][40][3]
    batch = frame[-ours[1].batch_bytes["main"][40][3]:]
    assert batch[21 + 1] & 0x07 == 4  # the attribute bits name Zstd
    want = bench.docs_text.make_documents(bench.SEED, 64, 608, range(40, 41))[40][96:128]
    assert bench.wire.decode_batch(batch, crc32c) == (0, want)


def test_the_live_zstd_configurations_reference_keeps_a_third_and_its_sequence():
    """Point 5 of the generator contract for the new pair of generator and
    reference (``docs_text.make_documents`` under ``filter_contains``), and
    the traffic file is ``paced.json`` key for key but the warm-up."""
    c = bench.config_of(bench.manifest(), V1_ZSTD_CELL)
    ref = bench.loadgen.load_reference(c["reference"]["name"])
    params = c["reference"]["params"]
    stream = {"seed": 2**31 + 5, "partitions": 4, "records_per_partition": 256}
    values = bench.loadgen.document_source(c["documents"])(stream)
    kept = 0
    for p, part in values.items():
        outs = [(i, ref.reference(v, **params)) for i, v in enumerate(part)]
        assert [ref.sequence(o) for i, o in outs if o is not None] == [
            p * 256 + i for i, o in outs if o is not None]
        assert all(o == part[i] for i, o in outs if o is not None)  # the value itself
        kept += sum(o is not None for _, o in outs)
    assert 0.2 < kept / (4 * 256) < 0.4
    paced = bench.load(os.path.join("benchmarks", "traffic", "paced.json"))
    device = bench.load(os.path.join("benchmarks", "traffic", "paced-device.json"))
    assert {k: v for k, v in device.items() if k not in ("warmup", "what")} == {
        k: v for k, v in paced.items() if k not in ("warmup", "what")}
    assert {k: device["warmup"][k] for k in ("min_s", "quiet_s", "cap_s")} == {
        "min_s": 15, "quiet_s": 6, "cap_s": 50}


@pytest.mark.parametrize("stats, want", [
    ({"n_launches": 64.0, "n_split_launches": 64.0}, 1.0),   # NEXmark: every launch in two parts
    ({"n_launches": 57.0, "n_split_launches": 0.0}, 0.0),
    ({"n_launches": 57.0}, 0.0),                              # a program without the counter
    ({"n_launches": 10.0, "n_split_launches": 4.0}, 0.4),
    ({}, None),                                               # no launch: nothing to read
])
def test_split_launch_share_is_read_by_the_stats_ratio_reader_and_listed(stats, want):
    """PR 47's per-layer metric is data alone: ``layer_metrics/split_launch_share.json``
    is read by the reader the benchmark already had, over ``stats()``'s
    ``n_split_launches`` and ``n_launches``, and ``BENCHMARK.json`` lists it
    for the cells ``staged_value_share`` is listed for."""
    import readers

    directory = os.path.join(BENCH, "layer_metrics")
    (d,) = [d for d in readers.load_definitions(directory) if d["name"] == "split_launch_share"]
    (twin,) = [d for d in readers.load_definitions(directory) if d["name"] == "staged_value_share"]
    assert d["read"]["kind"] == "stats_ratio" and d["read"]["kind"] in readers.KINDS
    assert (d["read"]["num"], d["read"]["den"]) == (["n_split_launches"], ["n_launches"])
    assert {k: d[k] for k in ("layer", "moves", "traffic", "source")} == {
        "layer": "link", "moves": "transform_rate", "traffic": twin["traffic"],
        "source": "program_counter"}
    listed = {e["name"]: e for e in bench.manifest()["per_layer"]}
    entry = listed["split_launch_share"]
    assert entry == {**{k: d[k] for k in ("name", "unit", "better", "source", "layer", "moves")},
                     "workloads": listed["staged_value_share"]["workloads"]}
    assert bench.manifest()["per_layer"][87] == entry  # appended as the 88th, nothing before it moved
    before = {"stats": {k: 3.0 for k in stats}, "metrics": {}}
    after = {"stats": {k: 3.0 + v for k, v in stats.items()}, "metrics": {}}
    got = readers.read_all(directory, kind="catchup", before=before, after=after,
                           client={}, trace=None, window_s=40.0).get("split_launch_share")
    assert got == (None if want is None else {"value": want, "unit": "ratio"})
    assert "split_launch_share" not in readers.read_all(
        directory, kind="paced", before=before, after=after, client={}, trace=None, window_s=40.0)


@pytest.mark.parametrize("metrics, want", [
    ({"_sum": 576.0, "_count": 64.0}, 9.0),    # one crossing a partition read
    ({"_sum": 576.0, "_count": 96.0}, 6.0),    # every other read's window ran out: two crossings
    ({"_sum": 576.0, "_count": 576.0}, 1.0),   # the per-frame loop: a sample a batch
    ({"_sum": 0.0, "_count": 0.0}, None),      # no scan in the window: nothing to read
    ({}, None),                                # a program without the histogram
])
def test_read_batches_per_crossing_is_read_by_the_histogram_reader_and_listed(metrics, want):
    """PR 48's per-layer metric is data alone:
    ``layer_metrics/read_batches_per_crossing.json`` is read by the reader the
    benchmark already had, over the histogram ``storage_read_crossing_batches``,
    and ``BENCHMARK.json`` lists it for the cells its append-side twin is
    listed for: the catch-up cells (six then, seven with PR 49's
    ``json64p-v1-tail.catchup``), and no ``paced.`` twin (a live tick's
    reads are served by the batch cache and make no scan)."""
    import readers

    directory = os.path.join(BENCH, "layer_metrics")
    defs = {d["name"]: d for d in readers.load_definitions(directory)}
    d, twin = defs["read_batches_per_crossing"], defs["append_batches_per_crossing"]
    assert "paced.read_batches_per_crossing" not in defs
    assert d["read"]["kind"] == "histogram_delta" and d["read"]["kind"] in readers.KINDS
    assert d["read"]["metric"] == "storage_read_crossing_batches"
    assert {k: d[k] for k in ("layer", "unit", "better", "moves", "traffic", "source")} == {
        k: twin[k] for k in ("layer", "unit", "better", "moves", "traffic", "source")}
    listed = {e["name"]: e for e in bench.manifest()["per_layer"]}
    entry = listed["read_batches_per_crossing"]
    assert entry == {**{k: d[k] for k in ("name", "unit", "better", "source", "layer", "moves")},
                     "workloads": listed["append_batches_per_crossing"]["workloads"]}
    assert len(entry["workloads"]) == 7 and all(w.endswith(".catchup") for w in entry["workloads"])
    assert bench.manifest()["per_layer"][88] == entry  # appended as the 89th, nothing before it moved
    series = "storage_read_crossing_batches"
    before = {"stats": {}, "metrics": {series + k: 5.0 for k in metrics}}
    after = {"stats": {}, "metrics": {series + k: 5.0 + v for k, v in metrics.items()}}
    got = readers.read_all(directory, kind="catchup", before=before, after=after,
                           client={}, trace=None, window_s=40.0).get("read_batches_per_crossing")
    assert got == (None if want is None else {"value": want, "unit": "batches"})
    assert "read_batches_per_crossing" not in readers.read_all(
        directory, kind="paced", before=before, after=after, client={}, trace=None, window_s=40.0)



# ------------------------------------------------------------------ PR 52: the tick's account
# name -> what its reader must name: (series, labels) of a histogram_delta
# file, (numerator, denominator) of a stats_ratio one
PR52_FILES = {
    "tick_read_ahead_wait_ms": ("coproc_tick_latency_us", {"phase": "read_ahead_wait"}),
    "tick_engine_prepare_ms": ("coproc_tick_latency_us", {"phase": "engine_prepare"}),
    "paced.tick_engine_prepare_ms": ("coproc_tick_latency_us", {"phase": "engine_prepare"}),
    "worker_submit_ms_per_launch": ("t_submit", "n_launches"),
    "worker_harvest_ms_per_launch": ("t_harvest", "n_launches"),
    "worker_submit_self_ms_per_launch": ("t_submit_self", "n_launches"),
    "worker_harvest_self_ms_per_launch": ("t_harvest_self", "n_launches"),
    "paced.worker_submit_ms_per_launch": ("t_submit", "n_launches"),
    "paced.worker_harvest_ms_per_launch": ("t_harvest", "n_launches"),
    "paced.worker_submit_self_ms_per_launch": ("t_submit_self", "n_launches"),
    "paced.worker_harvest_self_ms_per_launch": ("t_harvest_self", "n_launches"),
    "dispatch_ms_per_launch": ("t_dispatch", "n_device_launches"),
    "paced.dispatch_ms_per_launch": ("t_dispatch", "n_device_launches"),
    # the two stages step 0 found (PERF.md section 5)
    "plan_ms_per_launch": ("t_plan", "n_device_launches"),
    "paced.plan_ms_per_launch": ("t_plan", "n_device_launches"),
    "unpack_ms_per_launch": ("t_unpack", "n_device_launches"),
    "paced.produce_queue_ms": ("kafka_produce_stage_latency_us", {"stage": "queue"}),
    "paced.produce_decode_ms": ("kafka_produce_stage_latency_us", {"stage": "decode"}),
    "paced.produce_crc_ms": ("kafka_produce_stage_latency_us", {"stage": "crc"}),
    "paced.produce_replicate_ms": ("kafka_produce_stage_latency_us", {"stage": "replicate"}),
}


@pytest.fixture(scope="module")
def live_broker(tmp_path_factory):
    """What a CPU broker really exports once it has served a produce and a
    launch on each of the payload lane's roads (the keep mask; the result
    matrix) through its pacemaker: the parsed ``/metrics`` text
    and the engine's ``stats()``, as the benchmark's snapshot holds them."""
    import asyncio

    import readers
    import test_pacemaker_read_ahead as ra
    from redpanda_tpu.cluster.topic_table import TopicConfig
    from redpanda_tpu.kafka.client.client import KafkaClient
    from redpanda_tpu.metrics import registry
    from redpanda_tpu.models.fundamental import NTP
    from redpanda_tpu.ops.transforms import Int, Str, filter_contains, map_project

    out = {}

    async def main():
        tmp = tmp_path_factory.mktemp("live")
        storage, broker, server, api = await ra._start(tmp)
        client = await KafkaClient([("127.0.0.1", server.port)]).connect()
        try:
            await broker.create_topic(TopicConfig("src", 1))
            ctx = await ra._deployed(api, "live", "payload")
            matrix_road = (filter_contains(b'"level":"error"')
                           | map_project(Int("code"), Str("msg", 16))).to_json()
            await api.deploy("live_map", matrix_road, ["src"])
            await ra.wait_until(lambda: "live_map" in api.pacemaker.scripts(), msg="deployed")
            ctxs = [ctx, api.pacemaker.scripts()["live_map"]]
            for k in range(3):
                await client.produce("src", 0, ra._docs(64, base=64 * k))
            await ra.wait_until(
                lambda: all(c.offsets.get(NTP.kafka("src", 0)) == 3 * 64 - 1 for c in ctxs),
                msg="transformed")
            out["metrics"] = readers.parse_prometheus(registry.render_prometheus())
            out["stats"] = api.pacemaker.engine.stats()
        finally:
            await client.close()
            await ra._stop(storage, server, api)

    ra.run(main())
    return out


@pytest.mark.parametrize("name", sorted(PR52_FILES))
def test_a_pr52_metric_file_names_what_the_program_exports_and_is_listed(name, live_broker):
    """Each of PR 52's files is data alone: it loads, its reader is one the
    benchmark had, what it names is a series or a ``stats()`` key a live CPU
    broker really exports (a renamed key fails here, not as a null on the
    ledger), and ``BENCHMARK.json`` lists it over cells that exist and run
    its traffic kind."""
    import readers

    directory = os.path.join(BENCH, "layer_metrics")
    defs = {d["name"]: d for d in readers.load_definitions(directory)}
    d, want = defs[name], PR52_FILES[name]
    paced = name.startswith("paced.")
    assert d["traffic"] == (["paced"] if paced else ["catchup"])
    assert d["moves"] == ("e2e_p95_ms" if paced else "transform_rate")
    assert (d["source"], d["unit"], d["better"]) == ("program_span", "ms", "lower")
    read = d["read"]
    assert read["kind"] in readers.KINDS
    assert "the parent" in read["why"] or "nothing to read" in read["why"]  # says what a parent reads
    stats, metrics = live_broker["stats"], live_broker["metrics"]
    if read["kind"] == "histogram_delta":
        assert (read["metric"], read["labels"]) == want and read["scale"] == 0.001
        for suffix in ("_sum", "_count"):
            assert any(
                series.partition("{")[0] == read["metric"] + suffix
                and all(f'{k}="{v}"' in series for k, v in read["labels"].items())
                for series in metrics), (name, suffix)
        assert readers.metric_total(metrics, read["metric"] + "_count", read["labels"]) > 0
    else:
        assert read["kind"] == "stats_ratio" and read["scale"] == 1000.0
        assert (read["num"], read["den"]) == ([want[0]], [want[1]])
        assert stats[want[0]] > 0 and stats[want[1]] > 0
    # the reader over a window of that broker: a number; over the parent's
    # (no series, no key): nothing for a histogram, 0 for a ratio
    empty = {"metrics": {}, "stats": {}}
    kind = d["traffic"][0]
    got = readers.read_all(directory, kind=kind, before=empty, after=live_broker,
                           client={}, trace=None, window_s=40.0)
    assert got[name]["value"] >= 0 and got[name]["unit"] == "ms"
    parent = {"metrics": {}, "stats": {want[1]: 5.0} if read["kind"] == "stats_ratio" else {}}
    gone = readers.read_all(directory, kind=kind, before=empty, after=parent,
                            client={}, trace=None, window_s=40.0).get(name)
    assert gone == (None if read["kind"] == "histogram_delta" else {"value": 0.0, "unit": "ms"})
    man = bench.manifest()
    (entry,) = [e for e in man["per_layer"] if e["name"] == name]
    assert entry == {**{k: d[k] for k in ("name", "unit", "better", "source", "layer", "moves")},
                     "workloads": entry["workloads"]}
    cells = {w["name"]: w for w in man["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    for cell in entry["workloads"]:
        traffic = bench.load(os.path.join("benchmarks", "traffic", cells[cell]["traffic"] + ".json"))
        assert traffic["kind"] == kind, (name, cell)
    assert man["per_layer"].index(entry) >= 92  # appended: nothing that was there moved
