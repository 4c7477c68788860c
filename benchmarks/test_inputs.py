"""Tests of what a configuration file says its producers feed the broker
(PR 32): the producer codec (``producer.compression``) and the document
generator (``documents.generator``). Not collected by the repo's tier-1
command; run by hand beside ``test_benchmark.py``:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_inputs.py -q

The whole-run cases start a broker on JAX's CPU backend at the traffic
file's ``rehearsal`` size (about 25 s each).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]

import docs  # noqa: E402
import docs_text  # noqa: E402
import loadgen  # noqa: E402
import run as run_mod  # noqa: E402
import wire  # noqa: E402
from test_benchmark import BROKEN_LAUNCHER, manifest  # noqa: E402
from test_configs import _rehearse as rehearse_cell  # noqa: E402

ZSTD_MANIFEST = os.path.join("benchmarks", "testdata", "manifest_zstd.json")
ZSTD_CELL = "json64p-v1map-zstd.catchup"
SEED = 2**31 + 5


def load(path: str) -> dict:
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def config_of(man: dict, cell: str) -> dict:
    name = next(w["config"] for w in man["workloads"] if w["name"] == cell)
    return load(next(c["file"] for c in man["configs"] if c["name"] == name))


# every configuration a manifest of the benchmark names, the fixture's too
CONFIG_FILES = sorted({c["file"] for m in (manifest(), load(ZSTD_MANIFEST)) for c in m["configs"]})
GENERATORS = sorted({load(f)["documents"]["generator"] for f in CONFIG_FILES})


# ------------------------------------------------------------------ the four cells' frames
# sha256 over every produce frame both producers build for the cell, in the
# order (producer, stream, partition, batch), at the rehearsal's size, seed
# 2**31 + 5, --seconds 20: taken from the parent's tree (29ef861) before
# PR 32 touched wire.py, loadgen.py or run.py. The three catch-up cells
# share one traffic file and one input shape, so one digest.
PARENT_FRAMES = {
    "json64p-where.paced": (1216, "b49f0ef5e1836669ef2595c85d9685302b5cb6bbe53c592b05ea1f5e29439eb5"),
    "json64p-where.catchup": (2304, "85f13137bd61a626447b78b325fe38edbf51220ca775cbc29a06d8c2a577ea89"),
    "json64p-v1.catchup": (2304, "85f13137bd61a626447b78b325fe38edbf51220ca775cbc29a06d8c2a577ea89"),
    "json64p-v1map.catchup": (2304, "85f13137bd61a626447b78b325fe38edbf51220ca775cbc29a06d8c2a577ea89"),
}


def built_producers(man: dict, cell_name: str) -> list[loadgen.Producer]:
    """The cell's producers as a rehearsal starts them, built, not connected."""
    cell = next(w for w in man["workloads"] if w["name"] == cell_name)
    config = config_of(man, cell_name)
    traffic = load(os.path.join("benchmarks", "traffic", cell["traffic"] + ".json"))
    args = argparse.Namespace(seed=SEED, seconds=20, rehearse=1, trace=0, control=0, keep_trace="")
    run = run_mod.Run(args, cell, config, traffic, run_mod.input_shape(config))
    shutil.rmtree(run.run_dir)
    cores = {"producers": [None] * traffic["producers"], "consumer": None}
    out = []
    for spec in run.worker_specs(cores)[0]:
        prod = loadgen.Producer(spec)
        prod.build()
        out.append(prod)
    return out


def frames_digest(producers: list[loadgen.Producer]) -> tuple[int, str]:
    h, n = hashlib.sha256(), 0
    for prod in producers:
        for name, per_part in prod.frames.items():
            for p in sorted(per_part):
                for frame in per_part[p]:
                    h.update(frame)
                    n += 1
    return n, h.hexdigest()


def test_the_pinned_cells_are_the_manifests_cells():
    assert set(PARENT_FRAMES) == {w["name"] for w in manifest()["workloads"]}


@pytest.mark.parametrize("cell", sorted(PARENT_FRAMES))
def test_every_produce_frame_of_a_cell_is_the_parents_byte_for_byte(cell):
    assert frames_digest(built_producers(manifest(), cell)) == PARENT_FRAMES[cell]


def test_the_compressed_fixtures_frames_are_zstd_sealed_and_a_third_the_size():
    plain = built_producers(manifest(), "json64p-v1map.catchup")
    sealed = built_producers(load(ZSTD_MANIFEST), ZSTD_CELL)
    assert frames_digest(sealed)[0] == frames_digest(plain)[0] == 2304
    from redpanda_tpu.hashing.crc32c import crc32c

    sizes = [n for prod in sealed for per_part in prod.batch_bytes["main"].values() for n in per_part]
    assert 250 < sum(sizes) / (32 * len(sizes)) < 420  # wire bytes a record
    frame = sealed[1].frames["main"][40][3]
    batch = frame[-sealed[1].batch_bytes["main"][40][3]:]
    assert batch[21 + 1] & 0x07 == 4  # the attribute bits name Zstd
    want = docs_text.make_documents(SEED, 64, 1024, range(40, 41))[40][96:128]
    assert wire.decode_batch(batch, crc32c) == (0, want)


# ------------------------------------------------------------------ the codec
@pytest.mark.parametrize("codec", sorted(wire.CODECS))
def test_a_sealed_batch_is_what_the_engine_would_stage(codec):
    """The program's decoder and ``batch_codec.explode_ptrs`` (the payload
    lane's explode: decompress, then one (offset, length) a record) give the
    values the harness produced, compressed or not."""
    import numpy as np

    from redpanda_tpu.coproc import batch_codec
    from redpanda_tpu.hashing.crc32c import crc32c
    from redpanda_tpu.kafka.protocol.batch import decode_wire_batches

    parts = docs_text.make_documents(11, 2, 64)
    raws = [wire.build_batch(parts[p][s : s + 32], crc32c, codec=wire.codec_id(codec))
            for p in (0, 1) for s in (0, 32)]
    values = parts[0] + parts[1]
    decoded = decode_wire_batches(b"".join(raws))
    assert all(r.valid_crc for r in decoded) and len(decoded) == 4
    batches = [r.batch for r in decoded]
    assert [r.value for b in batches for r in b.records()] == values
    pe = batch_codec.explode_ptrs(batches)
    if pe is None:
        pytest.skip("the native library has no pointer-table explode here")
    got = [bytes(memoryview(payload)[o : o + n])
           for payload, off, ln in zip(pe.payloads, pe.rel_off, pe.rel_len)
           for o, n in zip(off.tolist(), ln.tolist())]
    assert got == values and pe.ranges == [(0, 32), (32, 64), (64, 96), (96, 128)]
    assert np.array_equal(pe.sizes, [len(v) for v in values])


def test_codec_names():
    assert wire.codec_id("none") == 0 and wire.codec_id("zstd") == 4
    for name in ("lz4", "snappy", "gzip", "", None):
        with pytest.raises(wire.InputShapeError, match="producer.compression"):
            wire.codec_id(name)
    with pytest.raises(ValueError):
        wire.build_batch([b"x"], lambda b: 0, codec=3)


# ------------------------------------------------------------------ the generator's contract
def _make(generator: str, seed: int, partitions: int, per_part: int, only=None) -> dict:
    stream = {"seed": seed, "partitions": partitions, "records_per_partition": per_part}
    return loadgen.document_source({"generator": generator})(stream, only)


@pytest.mark.parametrize("generator", GENERATORS)
@pytest.mark.parametrize("prop", ["bytes", "seed_alone", "independent_of_only",
                                  "inside_the_stated_widths", "fields_in_order"])
def test_generator_contract(generator, prop):
    seed = 2**31 + 11
    a = _make(generator, seed, 8, 64)
    if prop == "bytes":
        assert sorted(a) == list(range(8))
        assert all(len(part) == 64 and all(type(v) is bytes and v for v in part)
                   for part in a.values())
    elif prop == "seed_alone":
        assert a == _make(generator, seed, 8, 64)
        assert a != _make(generator, seed + 1, 8, 64)
    elif prop == "independent_of_only":
        some = _make(generator, seed, 8, 64, range(3, 5))
        assert sorted(some) == [3, 4] and some[3] == a[3] and some[4] == a[4]
    elif prop == "inside_the_stated_widths":
        for f in CONFIG_FILES:
            d = load(f)["documents"]
            if d["generator"] == generator:
                stream = {"seed": seed, "partitions": 8, "records_per_partition": 64}
                loadgen.check_documents(a, d, stream, None)
                with pytest.raises(wire.InputShapeError, match="documents.generator"):
                    loadgen.check_documents(a, {**d, "bytes_max": 1000}, stream, None)
                with pytest.raises(wire.InputShapeError, match="documents.generator"):
                    loadgen.check_documents({**a, 2: a[2][:-1]}, d, stream, None)
    else:
        for p, part in a.items():
            for i, v in enumerate(part):
                assert b"\\" not in v and v.count(b'"') == 14
                doc = json.loads(v)
                assert list(doc) == ["level", "code", "msg", "pad"]
                assert doc["code"] == p * 64 + i and doc["level"] in ("error", "info", "warn")


@pytest.mark.parametrize("config_file", sorted(
    set(CONFIG_FILES) - {c["file"] for c in manifest()["configs"]}))
def test_a_fixtures_reference_recovers_the_sequence_from_its_generator(config_file):
    """Point 5 of the contract, which ``transform_rate`` counts by, over the
    generator the configuration names with its params (``test_configs.py``
    holds the same over every configuration of ``BENCHMARK.json``)."""
    c = load(config_file)
    ref, params = loadgen.load_reference(c["reference"]["name"]), c["reference"]["params"]
    stream = {"seed": 2**31 + 5, "partitions": 4, "records_per_partition": 256}
    values = loadgen.document_source(c["documents"])(stream)
    kept = 0
    for p, part in values.items():
        outs = [(i, ref.reference(v, **params)) for i, v in enumerate(part)]
        assert [ref.sequence(o) for i, o in outs if o is not None] == [
            p * 256 + i for i, o in outs if o is not None]
        kept += sum(o is not None for _, o in outs)
    assert 0.2 < kept / (4 * 256) < 0.4


def test_unknown_generators_are_refused_by_name():
    for name in ("nope.make", "docs.nope", "docs", "../docs.make_documents", "Docs.make", None):
        with pytest.raises(wire.InputShapeError, match="documents.generator"):
            loadgen.load_generator(name)
    assert loadgen.load_generator("docs.make_documents")(3, 2, 4) == docs.make_documents(3, 2, 4)


# ------------------------------------------------------------------ docs_text's pins
def _zstd3_ratio(values: list[bytes]) -> float:
    records = wire.encode_records(values)
    return len(records) / len(wire.zstd_compressor().compress(records))


def test_docs_text_is_docs_but_for_what_pad_holds():
    a, b = docs.make_documents(7, 4, 4096), docs_text.make_documents(7, 4, 4096)
    lens = sorted(len(v) for part in b.values() for v in part)
    assert (lens[0], lens[-1]) == (924, 1059)  # inside 923-1,060: codes of up to 5 digits here
    assert 0.13 < sum(n > 1024 for n in lens) / len(lens) < 0.16  # over the staging row
    levels = {"error": 0, "info": 0, "warn": 0}
    for p in a:
        for x, y in zip(a[p], b[p]):
            dx, dy = json.loads(x), json.loads(y)
            assert {k: dx[k] for k in ("level", "code", "msg")} == {
                k: dy[k] for k in ("level", "code", "msg")} and len(x) == len(y)
            levels[dy["level"]] += 1
            pad = dy["pad"]
            assert 870 <= len(pad) <= 940 and pad[0] != " " and "  " not in pad
            assert all(3 <= len(w) <= 10 and w.isalpha() and w.islower()
                       for w in pad.split(" ")[:-1])
    assert all(0.31 < n / (4 * 4096) < 0.36 for n in levels.values())
    long_msgs = sum(len(json.loads(v)["msg"]) > 64 for v in b[0]) / 4096
    assert 0.09 < long_msgs < 0.16  # about one in eight
    # the params move the text, the defaults are the configuration's
    assert b[1] == docs_text.make_documents(7, 4, 4096, range(1, 2), vocabulary=4096, zipf_s=1.1)[1]
    assert b[1] != docs_text.make_documents(7, 4, 4096, range(1, 2), vocabulary=512)[1]


def test_docs_text_compresses_like_text_where_docs_compresses_like_filler():
    """A 32-record batch under Zstd level 3, seed 7: the second generator
    exists because the first one's reads ~20x."""
    text = _zstd3_ratio(docs_text.make_documents(7, 4, 32)[2])
    filler = _zstd3_ratio(docs.make_documents(7, 4, 32)[2])
    assert 2.5 < text < 4.0 and 15.0 < filler < 30.0
    assert text == pytest.approx(3.04, abs=0.1)


# ------------------------------------------------------------------ whole runs
def _rehearse(*extra: str, manifest_path: str = ZSTD_MANIFEST,
              launcher: str | None = None) -> tuple[int, dict, str]:
    return rehearse_cell(ZSTD_CELL, "--manifest", manifest_path, *extra, launcher=launcher)


def test_the_compressed_configuration_rehearses_correct_and_broken_is_caught(tmp_path):
    rc, last, out = _rehearse("--rehearse", "1", "--control", "1")
    assert rc == 0 and last["correct"] is True and "metrics" not in last, out[-3000:]
    control = json.loads(out.split("control (each broken guarantee caught): ")[1].splitlines()[0])
    assert control == {"one_missing": True, "one_duplicated": True,
                       "one_reordered": True, "one_flipped_byte": True}
    notes = json.loads(out.split("notes: ")[1].splitlines()[0])
    assert notes["input"] == {"generator": "docs_text.make_documents",
                              "params": {"vocabulary": 4096, "zipf_s": 1.1},
                              "compression": "zstd"}
    client = json.loads(out.split("client: ")[1].splitlines()[0])
    assert 250 < client["input_wire_bytes_per_rec"] < 420
    layer = {k: v["value"] for k, v in last["not_metrics"]["per_layer"].items()}
    assert layer["device_launch_share"] == 100.0 and 0.2 < layer["kept_share"] < 0.3
    # the served path itself broken: one acknowledged batch never written
    broken = tmp_path / "broken_launcher.py"
    broken.write_text(BROKEN_LAUNCHER)
    rc, last, out = _rehearse("--rehearse", "1", launcher=str(broken))
    assert rc == 1 and last["correct"] is False, out[-3000:]
    assert "check records_missing = 0" not in out


@pytest.mark.parametrize("key, value, named", [
    ("producer", {"compression": "lz4"}, "producer.compression 'lz4'"),
    ("documents", {"generator": "nope.make"}, "documents.generator 'nope.make'"),
])
def test_an_unknown_codec_or_generator_ends_the_run_before_the_broker_starts(
        key, value, named, tmp_path):
    man = load(ZSTD_MANIFEST)
    config = {**config_of(man, ZSTD_CELL)}
    config[key] = {**config.get(key, {}), **value}
    (tmp_path / "config.json").write_text(json.dumps(config))
    man["configs"][0]["file"] = str(tmp_path / "config.json")
    (tmp_path / "manifest.json").write_text(json.dumps(man))
    rc, last, out = _rehearse("--rehearse", "1", manifest_path=str(tmp_path / "manifest.json"))
    assert rc == 1 and last == {} and named in out, out[-2000:]
    assert "RUN FAILED" in out and "cores:" not in out and "broker ready" not in out


def test_a_generator_that_breaks_its_contract_fails_the_rehearsal(tmp_path):
    """The fixture with widths its generator does not keep to: the workers
    refuse it and the run ends with exit code 1 and no result line."""
    man = load(ZSTD_MANIFEST)
    config = config_of(man, ZSTD_CELL)
    config["documents"] = {**config["documents"], "bytes_max": 1000}
    (tmp_path / "config.json").write_text(json.dumps(config))
    man["configs"][0]["file"] = str(tmp_path / "config.json")
    (tmp_path / "manifest.json").write_text(json.dumps(man))
    rc, last, out = _rehearse("--rehearse", "1", manifest_path=str(tmp_path / "manifest.json"))
    assert rc == 1 and last == {}, out[-2000:]
    assert "RUN FAILED" in out and "documents.generator 'docs_text.make_documents'" in out
    assert "broker.log, its last lines" in out  # a failed run keeps its evidence
