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
"""

import os
import sys

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
    test_generator_contract,
    test_the_compressed_fixtures_frames_are_zstd_sealed_and_a_third_the_size,
    test_unknown_generators_are_refused_by_name,
)


def test_the_pinned_cells_are_the_manifests_uncompressed_cells():
    """``bench.test_the_pinned_cells_are_the_manifests_cells`` as it has to
    read since PR 33 added a cell whose producers compress: the frames
    pinned at 29ef861 are those of every cell whose configuration feeds
    ``docs.make_documents`` uncompressed, and the new cell is none of them
    (its frames are the fixture's, which the case above pins by shape)."""
    man = bench.manifest()
    plain = set()
    for w in man["workloads"]:
        config = bench.config_of(man, w["name"])
        if (config["documents"]["generator"] == "docs.make_documents"
                and config.get("producer", {}).get("compression", "none") == "none"):
            plain.add(w["name"])
    assert plain == set(bench.PARENT_FRAMES)
    assert {w["name"] for w in man["workloads"]} - plain <= {"json64p-v1map-zstd.catchup"}
