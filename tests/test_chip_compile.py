"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a chip that is described, not
attached (`jax.experimental.topologies`). These cases compile the launches
`chip_smoke.py` produces at the r03 shape (100k documents, one force-merged segment:
524,288 block rows, doc_pad 131,072; read from the rehearsal's compile manifest) for a
`v5e:2x2` topology: the sparse launch at its largest bucket in both variants, the dense
launch an overflow query takes (64 head rows, three searches, both variants; its
operands one packed plane, and a filtered launch's mask four rows stacked in the
program), and the
mesh program of `chip_smoke.py --chips 4` on a
four-device `Mesh`. A compile that passes is not a chip run; it says the chip's
compiler accepts the program and how much device memory it plans.

The topology is described in a module-scoped fixture, never at import: only one
process may hold the TPU library, every xdist worker imports every test file, and this
file runs in one worker (`--dist loadfile`). The persistent compilation cache is off
around these compiles — an executable compiled for a described chip is written to the
cache but cannot be read back without one.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

BLOCK = 128
ROWS = 524_288  # block rows of the force-merged 100k-document segment
DOC_PAD = 131_072


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    import jax
    import jax.numpy as jnp

    return [jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=sharding)
            for shape, dt in specs]


def _dense_plane(M: int, Q: int, coord_w: int, scalars: int = 0):
    """The dense launch's one operand plane (TermBatch.plane: tri | qplane |
    head, then a tail's scalars) as a shape."""
    from elasticsearch_tpu.ops.scoring import HEAD_SLOTS

    return ((6 * M + Q * (2 + coord_w) + 5 * Q * HEAD_SLOTS + scalars,), "int32")


def _sparse_args(sharding, Qb, TB, coord_w):
    return _shapes(
        sharding,
        ((ROWS, BLOCK), "int32"), ((ROWS, BLOCK), "uint8"), ((ROWS, BLOCK), "uint8"),
        ((1, 256), "float32"), ((1,), "int32"),  # SimTables caches, modes
        # the launch's two operand planes (SparseBatch.slots / .qplane)
        ((5, Qb, TB), "int32"), ((Qb, 2 + coord_w), "int32"))


@pytest.mark.parametrize("TB,k,passes,simple,coord_w", [
    (512, 128, 2, True, 5),    # four-term should, size 100, tb_max blocks
    (256, 16, 1, False, 3),    # two-term `operator: and`, size 10
], ids=["should4-k128-TB512", "and2-k16-TB256"])
def test_sparse_launch_compiles_for_v5e(one_chip, TB, k, passes, simple, coord_w):
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import _get_sparse_compiled

    # the launch site's own program, operand planes unpacked inside it
    fn = _get_sparse_compiled(8, TB, k, DOC_PAD, passes, simple, False, coord_w)
    with compile_tag("sparse"):
        compiled = fn.lower(*_sparse_args(one_chip, 8, TB, coord_w)).compile()
    mem = compiled.memory_analysis()
    # the [Qb, TB*128] candidate matrix and its sort temporaries: megabytes, not HBM
    assert 0 < mem.temp_size_in_bytes < 256 << 20
    assert "tpu_custom_call" not in compiled.as_text()  # the composed launch, no kernel


@pytest.mark.parametrize("simple", [True, False], ids=["simple", "bool"])
def test_dense_overflow_launch_compiles_for_v5e(one_chip, simple):
    """Queries whose terms span more than tb_max blocks take the dense launch: a
    [Q, doc_pad] f32 accumulator that the head terms' rows are added to, then the
    blocks that are left scattered from the lazily faulted f32 freqs plane."""
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import _get_compiled

    Q, E, H = 3, 256, 64  # searches, (query, block) entries of the tails, head rows
    args = _shapes(
        one_chip,
        ((ROWS, BLOCK), "int32"), ((ROWS, BLOCK), "float32"),  # docs, f32 freqs
        ((H, DOC_PAD), "uint8"),  # head rows, the tf plane's dtype
        ((DOC_PAD,), "bool"), ((1, DOC_PAD), "float32"),  # live, per-document table
        # the launch's one operand plane (TermBatch.plane), then M, a literal
        _dense_plane(E, Q, 8)) + [E]
    fn = _get_compiled(Q, 16, DOC_PAD, simple)  # the launch site's own program
    with compile_tag("dense"):
        compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    # docs i32 + freqs f32 planes dominate the arguments: 2 * 524288 * 128 * 4 bytes
    assert mem.argument_size_in_bytes >= 2 * ROWS * BLOCK * 4 + H * DOC_PAD
    assert mem.temp_size_in_bytes < 1 << 30
    # the rows are added in a loop whose trip count is data: one program whatever
    # the number of head clauses
    assert "while" in compiled.as_text()


def test_filtered_launch_of_a_packed_plane_and_four_mask_rows_compiles_for_v5e(
        one_chip):
    """`wiki.filtered`'s launch as the drainer hands it over: the operands one
    flat int32 plane the program takes apart by static slices (tri | qplane |
    head), and the mask a tuple of four resident [doc_pad] rows the tail
    stacks inside the program. The chip's compiler must take the slices of a
    plane whose sections are not tile-aligned and the stack of bool rows."""
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import _get_agg_compiled

    Q, E, H = 4, 256, 64
    args = _shapes(
        one_chip,
        ((ROWS, BLOCK), "int32"), ((ROWS, BLOCK), "float32"),
        ((H, DOC_PAD), "uint8"), ((DOC_PAD,), "bool"), ((1, DOC_PAD), "float32"),
        _dense_plane(E, Q, 4)) + [E] + _shapes(
        one_chip,
        ((0, 5, DOC_PAD), "float32"), ((0, 0, DOC_PAD), "int32")) + [
        (),  # no bucket aggregation: the filtered family's empty stack
        tuple(_shapes(one_chip, *[((DOC_PAD,), "bool")] * Q))]  # the mask rows
    fn = _get_agg_compiled(Q, 10, DOC_PAD, 0, True)
    with compile_tag("filtered"):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    assert lowered.as_text().split("@", 1)[1].split(" ", 1)[0] == \
        "jit_estpu_scoring_aggs_filtered"
    mem = compiled.memory_analysis()
    # arguments: the two postings planes, the head rows, and Q mask rows of a
    # byte a document beside a plane of a few kilobytes
    assert mem.argument_size_in_bytes >= 2 * ROWS * BLOCK * 4 + Q * DOC_PAD
    assert mem.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("n_plans,rung,slots", [
    (1, 0, 4), (4, 0, 4), (1, 1, 4), (4, 0, 2), (1, 1, 2), (1, 0, 2)],
    ids=["alone-first", "four-first", "alone-second", "four-pairs-first",
         "pair-second", "pair-first"])
def test_phrase_launch_compiles_for_v5e(one_chip, n_plans, rung, slots):
    """The exact-phrase program over the cell `wiki.phrase`'s positions plane
    (262,144 rows of keys at 50,000 documents, doc_pad 65,536: 15 bits of
    position) at every shape a served launch has: both group widths on the
    ladder's first rung and one plan on its second, where a tile of a long
    phrase rides too (the last rung is no program since the tiles: a list
    that long is cut by document ranges into launches of the second), each on
    the line of four slots and, for pairs, on the line of two. A gather of
    the block rows the launch lists and merges (the only sort is top_k's own,
    over 1,280 candidates); a line of two slots in under half the
    temporaries the line of four may take."""
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import (
        _P_COLS, PHRASE_RUNGS, PHRASE_SLOTS, _get_phrase_compiled)

    rows = PHRASE_RUNGS[rung]
    args = _shapes(
        one_chip, ((262_144, BLOCK), "int32"),  # the plane's keys
        ((1, 256), "float32"), ((1,), "int32"),  # SimTables caches, modes
        # the launch's operand plane and the block rows each slot gathers
        ((n_plans, _P_COLS), "int32"),
        ((n_plans, slots, rows), "int32"))
    fn = _get_phrase_compiled(n_plans, rows, 10, 31 - 16, slots)
    with compile_tag("phrase"):
        compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # composed, no kernel
    mem = compiled.memory_analysis()
    line = n_plans * slots * rows * BLOCK * 4  # one copy of the keys
    bound = 3 << 29 if slots == PHRASE_SLOTS else 3 << 28
    assert line <= mem.temp_size_in_bytes < bound
    assert mem.argument_size_in_bytes >= 262_144 * BLOCK * 4


@pytest.mark.parametrize("n_plans,disjuncts", [(1, 2), (4, 2), (4, 4)],
                         ids=["alone-two", "four-two", "four-four"])
def test_dismax_launch_compiles_for_v5e(one_chip, n_plans, disjuncts):
    """The dis_max program at the cell `beir.bestfields`' size (100,000
    passages of two analyzed fields, doc_pad 131,072): the dense core over an
    accumulator a (plan, disjunct), the combine, top-k. The operands are the
    dense launch's own, one packed plane with the plans' tie-breakers as its
    last words, at both group widths and the widest disjunct count."""
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import _get_dismax_compiled

    # the triples' rung is fixed by the width: TAIL_FLOOR (256) blocks a plan
    rows, E, H = n_plans * disjuncts, 256 * n_plans, 64
    args = _shapes(
        one_chip,
        ((ROWS, BLOCK), "int32"), ((ROWS, BLOCK), "float32"),
        ((H, DOC_PAD), "uint8"), ((DOC_PAD,), "bool"), ((2, DOC_PAD), "float32"),
        _dense_plane(E, rows, 4, scalars=n_plans)) + [E]
    fn = _get_dismax_compiled(n_plans, disjuncts, 10, DOC_PAD)
    with compile_tag("dis_max"):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    assert lowered.as_text().split("@", 1)[1].split(" ", 1)[0] == \
        "jit_estpu_scoring_dismax"
    assert "tpu_custom_call" not in compiled.as_text()  # composed, no kernel
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 2 * ROWS * BLOCK * 4 + H * DOC_PAD
    assert mem.temp_size_in_bytes < 1 << 30
    # the head rows are added in a loop whose trip count is data, as in the
    # dense programs: one program whatever the number of head clauses
    assert "while" in compiled.as_text()


@pytest.mark.parametrize("n_queries,rung", [(1, 0), (8, 0), (1, 1), (1, -1)],
                         ids=["alone-first", "eight-first", "alone-second",
                              "alone-last"])
def test_multiterm_launch_compiles_for_v5e(one_chip, n_queries, rung):
    """The multi-term mask program over the cell `wiki.multiterm`'s postings
    plane (147,532 block rows at 50,000 documents: 147,584 padded, doc_pad
    65,536) at both ends of the first rung's query counts and alone on the two
    longer rungs: a gather of block rows and one scatter into the mask
    matrix, a bool [doc_pad] row a search."""
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import (MULTITERM_RUNGS,
                                               _get_multiterm_compiled)

    rows, doc_pad = MULTITERM_RUNGS[rung], 65_536
    args = _shapes(one_chip, ((147_584, BLOCK), "int32"),  # the plane's doc ids
                   ((n_queries, rows), "int32"))  # the launch's one operand
    fn = _get_multiterm_compiled(n_queries, rows, doc_pad)
    with compile_tag("filtered"):
        compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # composed, no kernel
    assert " scatter(" in text and " sort(" not in text  # one scatter, no sort
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 256 << 20
    assert mem.argument_size_in_bytes >= 147_584 * BLOCK * 4
    assert mem.output_size_in_bytes >= n_queries * doc_pad  # the rows, a byte a doc


DOC_PAD_LOGS = 1 << 20  # a million log events in one force-merged segment


@pytest.mark.parametrize("tail", ["hits", "sorted_desc", "sorted_asc", "hourly"])
def test_unscored_launches_compile_for_v5e(one_chip, tail):
    """A plan with no scoring clause (match_all, a range query, filtered over
    them) at the log index's doc_pad: the live mask, one constant a query, the
    filter's mask row and the tail's own operands; no postings plane at all."""
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import (
        _get_agg_compiled, _get_sorted_compiled, _unscored_abi)

    D = DOC_PAD_LOGS
    head = (((D,), "bool"), ((1,), "int32"))  # live_parent, the plane: score bits [Q]
    mask = ((1, D), "bool")
    no_aggs = (((0, 5, D), "float32"), ((0, 0, D), "int32"))  # folds, limbs
    if tail.startswith("sorted"):
        fn = _get_sorted_compiled(1, 10, D, tail == "sorted_desc",
                                  _unscored_abi, "unscored")
        args = _shapes(one_chip, *head, mask, ((D,), "float32"))  # the key row
        family = "sorted"
    elif tail == "hits":
        fn = _get_agg_compiled(1, 10, D, 0, True, _unscored_abi, "unscored")
        args = _shapes(one_chip, *head, *no_aggs) + [()] + _shapes(one_chip, mask)
        family = "filtered"
    else:  # size 0 and one date_histogram: a (doc, hour) pair a document
        fn = _get_agg_compiled(1, 1, D, 1, False, _unscored_abi, "unscored")
        pairs = _shapes(one_chip, ((D,), "int32"), ((D,), "int32"),
                        ((256,), "int32"))
        args = _shapes(one_chip, *head, *no_aggs) + [((*pairs, None),)] \
            + _shapes(one_chip, mask)
        family = "aggs"
    with compile_tag(family):
        compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    # the operands are the mask and key rows, megabytes; no [ROWS, 128] plane
    assert mem.argument_size_in_bytes < 64 << 20
    assert mem.temp_size_in_bytes < 256 << 20


DOC_PAD_GEO = 1 << 17  # 80,000 places in one force-merged segment


@pytest.mark.parametrize("Q", [1, 4])
@pytest.mark.parametrize("tail", ["fs_rows", "fs_script", "country_facet"])
def test_function_score_and_exact_sum_launches_compile_for_v5e(one_chip, tail, Q):
    """`geonames.scoring`'s launches at its doc_pad and both rungs of the group's
    ladder: function_score over match_all behind the unscored ABI (one function
    row, or three column rows and the script traced in), and the keyword facet
    whose sums are integer limbs scattered and added as int32."""
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import (
        _get_agg_compiled, _get_fs_compiled, _unscored_abi)
    from elasticsearch_tpu.script import compile_script, script_vector_info

    D = DOC_PAD_GEO
    live = ((D,), "bool")

    def head(scalars=0):  # live_parent, the plane: score bits [Q], the tail's scalars
        return live, ((Q + scalars,), "int32")

    mask = ((1, 1), "bool") if Q == 1 else ((Q, D), "bool")
    row, gate = ((D,), "float32"), ((D,), "bool")
    if tail == "fs_rows":
        fn = _get_fs_compiled("rows", Q, 10, D, _unscored_abi, "unscored",
                              bmode="multiply", use_min_score=False,
                              no_functions=False)
        args = _shapes(one_chip, *head(3), mask, row, gate)
        family = "function_score"
    elif tail == "fs_script":
        script = compile_script(
            "abs(log(abs(doc['population'].value) + 1) + doc['location.lon'].value"
            " + doc['location.lat'].value) * _score", {})
        used = script_vector_info(script)[1]
        assert len(used) == 3
        fn = _get_fs_compiled("script", Q, 10, D, _unscored_abi, "unscored",
                              script=script, used_fields=used, bmode="multiply",
                              use_min_score=False, has_filter=False,
                              has_weight=False)
        args = _shapes(one_chip, *head(4), mask) + [tuple(_shapes(one_chip, row, row, row))] \
            + _shapes(one_chip, gate, gate, gate)
        family = "function_score"
    else:  # size 0, terms on a keyword, a sum of a long under it
        fn = _get_agg_compiled(Q, 1, D, 1, False, _unscored_abi, "unscored")
        pairs = _shapes(one_chip, ((80_000,), "int32"), ((80_000,), "int32"),
                        ((256,), "int32"))
        sub = tuple(_shapes(one_chip, ((1, 5, D), "float32"), ((1, 3, D), "int32")))
        args = _shapes(one_chip, *head(), ((0, 5, D), "float32"), ((0, 0, D), "int32")) \
            + [((*pairs, sub),)] + _shapes(one_chip, mask)
        family = "aggs"
    with compile_tag(family):
        compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 64 << 20  # rows and masks: no postings plane
    # the facet's scatters hold their [Q, Fs, pairs] operands tiled: 0.25 GB at Q = 4
    assert mem.temp_size_in_bytes < (512 << 20 if tail == "country_facet" else 256 << 20)
    name = fn.lower(*args).as_text().split("@", 1)[1].split(" ", 1)[0]
    assert name == {"fs_rows": "jit_estpu_scoring_fs_rows_unscored",
                    "fs_script": "jit_estpu_scoring_fs_script_unscored",
                    "country_facet": "jit_estpu_scoring_aggs_unscored"}[tail]


@pytest.mark.parametrize("descending", [True, False], ids=["desc", "asc"])
def test_scored_sort_launch_compiles_for_v5e(one_chip, descending):
    """A one-term match sorted on a date: the dense launch with the sort tail
    over a resident key row (values, or exact ranks: float32 either way)."""
    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.ops.scoring import _get_sorted_compiled

    args = _shapes(
        one_chip,
        ((ROWS, BLOCK), "int32"), ((ROWS, BLOCK), "float32"),
        ((64, DOC_PAD), "uint8"), ((DOC_PAD,), "bool"), ((1, DOC_PAD), "float32"),
        _dense_plane(256, 1, 4)) + [256] + _shapes(
        one_chip,
        ((1, 1), "bool"), ((DOC_PAD,), "float32"))  # the no-op mask, the key row
    fn = _get_sorted_compiled(1, 10, DOC_PAD, descending)
    with compile_tag("sorted"):
        compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_mesh_program_compiles_for_four_v5e_chips(topo):
    """`chip_smoke.py --chips 4`: one index of 4 x 25,000 documents, one shard a
    device, statistics resolved on the host (per-shard weight and norm-cache
    rows), the global top-k by all_gather."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from elasticsearch_tpu.common.jaxenv import compile_tag
    from elasticsearch_tpu.parallel.mesh_search import (
        _mesh_score_program,
        _on_plane,
    )

    S, rows, doc_pad, E, C, Qp, k, W = 4, 131_072, 32_768, 512, 4, 1, 128, 5
    mesh = Mesh(np.array(topo.devices[:S]), ("shards",))
    sh, rep = P("shards"), P()
    layout = [  # (shape, dtype, spec) as MeshSearchExecutor.search places them
        ((S, rows, BLOCK), "int32", sh), ((S, rows, BLOCK), "uint8", sh),  # docs, tf
        ((S, 1, doc_pad), "uint8", sh), ((S, doc_pad), "bool", sh),  # norms, live
        ((S, 1, 256), "float32", sh),  # the resident norm cache
        # the launch's one operand plane: six entry arrays, the clause
        # weights, n_must, msm and the coord rows, a row a shard
        ((S, 6 * E + C + Qp * (2 + W)), "int32", sh)]
    program = _on_plane(_mesh_score_program(k, Qp, doc_pad, 0), E, Qp, W)
    fn = jax.jit(shard_map(
        program, mesh=mesh, in_specs=tuple(spec for _s, _d, spec in layout),
        out_specs=(rep,), check_vma=False))
    args = [jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                 sharding=NamedSharding(mesh, spec))
            for shape, dt, spec in layout]
    with compile_tag("mesh"):
        compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "all-gather" in text  # the top-k merge crosses the chips
    # per device: its own shard's planes, not all four
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 2 * rows * BLOCK * 5
