"""The main-path kernels compile for a TPU v5e at their real widths.

Ahead-of-time compiles against a described ``v5e:2x2`` topology — no chip
attached (on-chip-measurement guide §2): what the chip's compiler refuses
shows up here at no chip time.  Compiling says nothing about results or
speed.  The topology is described inside a fixture, never at import: only
one process at a time may load the TPU library, and every xdist worker
imports this file.
"""

import os
import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

import bench
from fluidframework_tpu.ops.mergetree_kernel import (
    _export_cold_fn,
    _export_flags,
    _export_warm_fn,
    narrow_ops_for_upload,
    narrow_state_for_upload,
    pack_mergetree_batch,
    replay_vmapped,
)
from tools import bench_configs as cfg

#: the bench chunk: documents per fold dispatch x ops per document
CHUNK_DOCS, CHUNK_OPS = 1024, 96


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mt_chunk():
    """One packed bench chunk: (state, ops, meta), host arrays."""
    docs = [bench.synth_doc(i, CHUNK_OPS) for i in range(CHUNK_DOCS)]
    return pack_mergetree_batch(docs)


def _specs(tree, sharding):
    """Shapes (no arrays) placed on ``sharding`` — a described device
    cannot hold an array."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding), tree)


def _export_args(chunk):
    """The export builders' facts and the narrowed upload the pipeline
    dispatches (``replay_export``)."""
    state, ops, meta = chunk
    i16, ob_rows, ov_slots, i8, has_props = _export_flags(meta)
    facts = dict(i16=i16, ob_rows=ob_rows, ov_slots=ov_slots, i8=i8,
                 sequential=bool(meta.get("sequential")),
                 has_props=has_props)
    return (facts, narrow_state_for_upload(state, meta),
            narrow_ops_for_upload(ops, meta),
            np.asarray(meta["doc_base"], np.int32), int(meta["_S"]))


def test_topology_is_v5e(topo):
    assert len(topo.devices) == 4
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert topo.devices[0].device_kind.replace(" ", "_") in bench.HBM_GBPS


def test_scan_fold_compiles(one_chip, mt_chunk):
    state, ops, _meta = mt_chunk
    compiled = jax.jit(replay_vmapped).lower(
        _specs(state, one_chip), _specs(ops, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.temp_size_in_bytes < 16 * 2**30  # fits the chip's HBM


@pytest.fixture(scope="module")
def export_compiled(one_chip, mt_chunk):
    """The single-chip fold+export of the bench chunk, compiled once per
    start (cold or warm) and overlap slot count for the tests that read
    it.  ``ov_slots`` None is the chunk's own count (0: its views are
    sequential); a count of 1 or more compiles the chunk's shapes as a
    concurrent chunk with that many overlap slots, the warm state
    carrying a (seq, client) plane pair per slot past the first, narrowed
    like rem2."""
    done = {}

    def compiled(warm, ov_slots=None):
        key = (warm, ov_slots)
        if key in done:
            return done[key]
        facts, state_n, ops_n, doc_base, S = _export_args(mt_chunk)
        sequential = facts["sequential"]
        if ov_slots is None:
            ov_slots = facts["ov_slots"]
        else:
            sequential = False
            extra = max(ov_slots - 1, 0)
            state_n = state_n._replace(
                remx_seq=(np.asarray(state_n.rem2_seq),) * extra,
                remx_client=(np.asarray(state_n.rem2_client),) * extra)
        args = [_specs(ops_n, one_chip), _specs(doc_base, one_chip)]
        flags = (facts["i16"], facts["ob_rows"], ov_slots,
                 facts["i8"], sequential, facts["has_props"])
        if warm:
            fn = _export_warm_fn(*flags, out_sharding=one_chip, digest=True)
            args.insert(0, _specs(state_n, one_chip))
        else:
            fn = _export_cold_fn(S, *flags, out_sharding=one_chip,
                                 digest=True)
        done[key] = fn.lower(*args).compile()
        return done[key]

    return compiled


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
def test_export_compiles_with_forced_format_and_digest(export_compiled,
                                                       warm):
    """The single-chip fold+export with the forced row-major Format (it
    decides from the described device, not the CPU backend) and the
    digest plane."""
    formats = jax.tree.leaves(export_compiled(warm).output_formats)
    assert len(formats) >= 2  # the buffer(s) + the digest plane
    assert formats[0].layout.major_to_minor == (0, 1, 2)


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")


def _while_body_lines(hlo: str) -> list:
    """The instruction lines of every computation a ``while`` body of the
    optimized HLO module reaches (fusions, reductions, nested loops)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    todo = [name for lines in comps.values() for line in lines
            for name in re.findall(r"\bbody=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += [ref for line in comps[name]
                 for ref in re.findall(r"%([\w.\-]+)", line)]
    return [line for name in seen for line in comps[name]]


@pytest.mark.parametrize("warm,ov_slots", [(False, None), (True, None),
                                           (False, 2), (True, 2)],
                         ids=["cold", "warm", "cold-ov2", "warm-ov2"])
def test_scan_body_has_no_gather(export_compiled, warm, ov_slots):
    """The merge-tree scan moves its segment pool by a one-slot roll and a
    select.  A per-document ``take`` there compiles on the v5e to a
    general batched gather of the whole [docs, slots] plane, about ten
    times an elementwise pass, once per plane and scan step.  That holds
    for the overlap slots past the first too."""
    body = _while_body_lines(export_compiled(warm, ov_slots).as_text())
    assert body, "no while loop in the compiled fold"
    gathers = [line.strip()[:160] for line in body
               if re.search(r"\sgather\(", line)]
    assert gathers == []


@pytest.mark.parametrize("ov_slots", [None, 1, 2])
def test_warm_program_takes_a_plane_per_extra_overlap_slot(
        export_compiled, ov_slots):
    """A chunk with at most one overlap slot uploads the same twelve slot
    planes, props, n and overflow as before slots were counted (no
    ``remx_*`` leaf in the compiled program's arguments); each slot past
    the first adds a (seq, client) plane pair."""
    state_args = export_compiled(True, ov_slots).args_info[0][0]
    extra = max((ov_slots or 0) - 1, 0)
    assert len(jax.tree.leaves(state_args)) == 15 + 2 * extra
    assert len(state_args.remx_seq) == len(state_args.remx_client) == extra


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("ov_slots", [0, 1])
def test_program_with_at_most_one_overlap_slot_is_the_flag_program(
        one_chip, mt_chunk, warm, ov_slots):
    """A chunk with at most one overlap slot lowers to the program a fold
    with only the on/off overlap flag lowers to: a state with no
    ``remx_*`` planes, the fold given ``has_ov = ov_slots > 0``, and the
    export with rem2's rows or without.  The slot count adds no operand
    and no operation there."""
    from fluidframework_tpu.ops.mergetree_kernel import (
        _cold_start,
        _export_out,
        _export_with_digest,
        _fold_fn,
        _widen_ops,
        _widen_state,
        program_name,
    )

    facts, state_n, ops_n, doc_base, S = _export_args(mt_chunk)
    assert state_n.remx_seq == () and state_n.remx_client == ()
    i16, ob, i8, props = (facts["i16"], facts["ob_rows"], facts["i8"],
                          facts["has_props"])
    sequential = facts["sequential"] and ov_slots == 0
    flags = (i16, ob, ov_slots, i8, sequential, props)
    fold = _fold_fn(sequential, ob, props, ov_slots > 0)

    def flag_program(*args):
        *state, ops, base = args
        with jax.named_scope("fold"):
            if state:
                start = _widen_state(state[0], base)
            ops = _widen_ops(ops, base)
            if not state:
                start = _cold_start(ops, S, 0)
            final = fold(start, ops)
        return _export_with_digest(final, base, i16, ob, ov_slots, i8,
                                   props, True)

    start = "warm" if warm else "cold"
    flag_program.__name__ = flag_program.__qualname__ = program_name(
        "mergetree", True, start)
    args = [_specs(ops_n, one_chip), _specs(doc_base, one_chip)]
    if warm:
        args.insert(0, _specs(state_n, one_chip))
        program = _export_warm_fn(*flags, digest=True)
    else:
        program = _export_cold_fn(S, *flags, digest=True)
    fmt = _export_out(i8, None, True)
    flag_jit = jax.jit(flag_program) if fmt is None else \
        jax.jit(flag_program, out_shardings=fmt)
    assert program.lower(*args).as_text() == \
        flag_jit.lower(*args).as_text()


def _map_args(n_docs):
    from fluidframework_tpu.ops.map_kernel import pack_map_batch

    b = pack_map_batch([cfg.gen_map_doc(i, 96) for i in range(n_docs)])
    return ((b.key_gid, b.op_seq, b.is_set, b.val_idx, b.key_doc,
             b.clear_doc, b.clear_seq),
            dict(num_keys=b.num_keys, num_docs=b.num_docs))


def _matrix_args(n_docs):
    from fluidframework_tpu.ops.matrix_kernel import (
        known_matrix_fallback,
        pack_matrix_batch,
    )

    docs = [cfg.gen_matrix_doc(i, 64) for i in range(n_docs)]
    state, ops, _meta = pack_matrix_batch(
        [d for d in docs if not known_matrix_fallback(d)])
    return (state, ops), {}


def _tree_args(n_docs):
    from fluidframework_tpu.ops.tree_kernel import pack_tree_batch

    state, edits, _meta = pack_tree_batch(
        [cfg.gen_tree_doc(i, 48) for i in range(n_docs)])
    return (state, edits), {}


@pytest.mark.parametrize("kernel", ["map", "matrix", "tree"])
def test_batch_fold_compiles(one_chip, kernel):
    """The other device kernels at tools/bench_configs.py's per-doc sizes
    and its fold batch (1,024 docs; 256 for the tree config)."""
    from fluidframework_tpu.ops import map_kernel, matrix_kernel, \
        tree_kernel

    fn, build, n_docs = {
        "map": (map_kernel._map_lww_kernel, _map_args, 1024),
        "matrix": (matrix_kernel._replay_matrix_batch, _matrix_args, 1024),
        "tree": (tree_kernel._replay_batch, _tree_args, 256),
    }[kernel]
    args, static = build(n_docs)
    compiled = fn.lower(*_specs(args, one_chip), **static).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_sharded_export_step_compiles_on_four_chips(topo, one_chip,
                                                    mt_chunk):
    """The mesh catch-up step on a 4-chip doc mesh: each device holds a
    quarter of the arguments the same step holds on one chip."""
    from fluidframework_tpu.parallel.shard import sharded_export_step

    facts, _state_n, ops_n, doc_base, S = _export_args(mt_chunk)
    flags = (S, facts["i16"], facts["ob_rows"], facts["ov_slots"],
             facts["i8"], facts["sequential"], facts["has_props"])
    per_device = {}
    for n_chips in (1, 4):
        mesh = Mesh(np.asarray(topo.devices[:n_chips]), ("docs",))
        docs = NamedSharding(mesh, PartitionSpec("docs"))
        step = sharded_export_step(mesh, *flags, warm=False, digest=True)
        compiled = step.lower(_specs(ops_n, docs), _specs(doc_base, docs)
                              ).compile()
        per_device[n_chips] = \
            compiled.memory_analysis().argument_size_in_bytes
    assert per_device[4] * 4 == per_device[1], per_device

