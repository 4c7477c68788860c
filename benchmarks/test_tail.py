"""Configuration ``json64p-v1-tail`` and its cell (PR 49): the generator
``docs_tail.make_documents`` held to the contract of ``loadgen.py``'s
docstring and to its size law, and the whole-run rehearsal of
``json64p-v1-tail.catchup``. The broker-less cases are collected by
``tests/test_benchmark_inputs.py`` as tier-1; the rehearsal is run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_tail.py -q
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import loadgen  # noqa: E402
import test_inputs  # noqa: E402

GENERATOR = "docs_tail.make_documents"
CELL = "json64p-v1-tail.catchup"
CONFIG = test_inputs.load(os.path.join("benchmarks", "configs", "json64p-v1-tail.json"))
# the law's shares over each edge: (384 / edge) ** 1.2
SHARES = {1024: 0.3082, 2048: 0.1342, 4096: 0.0584, 8192: 0.0254, 16383: 0.0111}


@pytest.mark.parametrize("prop", ["bytes", "seed_alone", "independent_of_only",
                                  "inside_the_stated_widths", "fields_in_order"])
def test_the_generator_holds_the_contract(prop):
    """Points 1-4 as every generator's, the document config 4's own."""
    test_inputs.test_generator_contract(GENERATOR, prop)


def test_the_reference_recovers_each_kept_inputs_sequence_and_drops_none_for_its_size():
    """Point 5, at the configuration's own limit: every warn is kept whatever
    its size, and carries ``p * records_per_partition + i``."""
    ref = loadgen.load_reference(CONFIG["reference"]["name"])
    params = CONFIG["reference"]["params"]
    assert params["row_stride"] == CONFIG["documents"]["params"]["cap_bytes"] == 16384
    assert CONFIG["broker_properties"]["coproc_max_value_bytes"] == "16384"
    stream = {"seed": 2**31 + 5, "partitions": 4, "records_per_partition": 512}
    values = loadgen.document_source(CONFIG["documents"])(stream)
    kept = wide_kept = 0
    for p, part in values.items():
        outs = [(i, v, ref.reference(v, **params)) for i, v in enumerate(part)]
        assert all(o == v for _, v, o in outs if o is not None)
        assert [ref.sequence(o) for _, _, o in outs if o is not None] == [
            p * 512 + i for i, _, o in outs if o is not None]
        assert all((o is not None) == (b'"level":"warn"' in v) for _, v, o in outs)
        kept += sum(o is not None for _, _, o in outs)
        wide_kept += sum(o is not None and len(v) > 1024 for _, v, o in outs)
    assert 0.3 < kept / 2048 < 0.37 and 0.08 < wide_kept / 2048 < 0.13
    # the four config-4 payload configurations' limit would lose those
    assert sum(ref.reference(v, params["needle"], 1024) is not None
               for part in values.values() for v in part) == kept - wide_kept


def test_the_size_law_gives_its_shares_within_a_percent():
    p = CONFIG["documents"]["params"]
    stream = {"seed": 2**31 + 49, "partitions": 64, "records_per_partition": 1024}
    values = loadgen.document_source(CONFIG["documents"])(stream)
    lengths = np.array([len(v) for part in values.values() for v in part])
    assert len(lengths) == 65536
    assert lengths.min() == p["floor_bytes"] == CONFIG["documents"]["bytes_min"] == 384
    assert lengths.max() == p["cap_bytes"] == CONFIG["documents"]["bytes_max"] == 16384
    for edge, share in SHARES.items():
        assert abs((lengths > edge).mean() - share) < 0.01, edge
        assert abs((384 / (edge + (edge == 16383))) ** 1.2 - share) < 0.0005
    assert 650 < np.median(lengths) < 720 and 1330 < lengths.mean() < 1470
    assert 0.67 < lengths[lengths > 1024].sum() / lengths.sum() < 0.75
    # the law itself, recomputed from the seed: U is the third draw
    rng = np.random.default_rng(stream["seed"])
    shape = (64, 1024)
    rng.integers(0, 3, size=shape), rng.integers(8, 73, size=shape)
    u = 1.0 - rng.random(size=shape)
    want = np.minimum(np.floor(384 * u ** (-1 / 1.2)), 16384).astype(np.int64)
    assert np.array_equal(lengths.reshape(shape), want)


def test_rehearsal_is_correct_drops_nothing_and_each_broken_guarantee_is_caught(tmp_path):
    from test_benchmark import BROKEN_LAUNCHER
    from test_configs import _rehearse

    rc, last, out = _rehearse(CELL, "--rehearse", "1", "--control", "1")
    assert rc == 0 and last["correct"] is True and "metrics" not in last, out[-3000:]
    control = json.loads(out.split("control (each broken guarantee caught): ")[1].splitlines()[0])
    assert control == {"one_missing": True, "one_duplicated": True,
                       "one_reordered": True, "one_flipped_byte": True}
    layer = {k: v["value"] for k, v in last["not_metrics"]["per_layer"].items()}
    assert layer["device_launch_share"] == 100.0 and layer["compiles_in_window"] == 0
    assert layer["oversize_rows_per_launch"] == 0 and layer["mask_harvest_share"] == 1.0
    assert 0.3 < layer["kept_share"] < 0.37 and 400 < layer["out_bytes_per_rec"] < 520
    # a rehearsal's small launches, mostly while the classes' programs are
    # being built: more than one part a launch, the wide rows at least the law's
    assert layer["parts_per_launch"] > 1.5 and layer["wide_rows_share"] >= 0.29
    assert 0.1 < layer["wide_staged_value_share"] < 1.0
    broken = tmp_path / "broken_launcher.py"
    broken.write_text(BROKEN_LAUNCHER)
    rc, last, out = _rehearse(CELL, "--rehearse", "1", launcher=str(broken))
    assert rc == 1 and last["correct"] is False, out[-3000:]
    assert "check records_missing = 0" not in out
