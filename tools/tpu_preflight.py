"""One-command chip preflight gate (run it BEFORE a chip call).

Chip time is budgeted; the classes of failure that historically wasted
it are all detectable on CPU first:

  * the round-5 Mosaic compile error — a BlockSpec/grid shape violating
    the (8, 128) rule that interpret mode silently accepts;
  * the round-13 int16 overflow — a narrow plane built without its bound
    guard;
  * kernel/oracle divergence — a fold change that was never re-run
    against the reference before the window;
  * artifact-schema drift — bench.py's roofline block renamed or dropped
    a key its consumers read.

Four gates, all CPU-runnable, each reported in one JSON summary line on
stdout; exit 0 iff every gate passed.

  1. kernel-lint  — the fluidshape family (FL-KERN-*) over the package
     must be clean with ZERO suppressions (static Mosaic compliance,
     narrow-dtype bounds, bucket routing, pad masking, registry drift).
  2. mergetree-parity — the jitted scan fold, exported and extracted as
     the catch-up path serves it, vs the CPU oracle on a small synth
     batch, digest-exact.
  3. tree-parity  — device tree fold vs the CPU oracle on a minimal
     sequenced log, digest-exact.
  4. bench-schema — the roofline dict carries the keys the artifacts
     commit, and ``steady_fold_pct_of_bound`` is still derivable from
     it (and still spelled that way inside bench.py).

NOTE (SEMANTICS.md): gate 1 is a static approximation and gates 2-3 fold
on the CPU backend — passing preflight does NOT prove the kernels compile
for a real chip.  ``tests/test_v5e_compile.py`` compiles the kernels for
a described v5e instead.  Preflight exists so chip time is never spent
discovering what CPU could have told us.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gate(fn):
    """Run one gate; never raise — a preflight that crashes is a FAILED
    preflight with the traceback as detail."""
    import traceback

    try:
        detail = fn()
        return {"ok": True, "detail": detail}
    except Exception:
        return {"ok": False, "detail": traceback.format_exc(limit=4)}


def gate_kernel_lint():
    """fluidshape (FL-KERN-*) over the whole package, zero suppressions."""
    from tools.fluidlint.cli import rule_family
    from tools.fluidlint.core import all_rules, analyze

    rules = {name: rule for name, rule in all_rules().items()
             if rule_family(rule) == "kernel"}
    assert len(rules) >= 5, sorted(rules)
    findings = analyze(ROOT, rules=rules)
    assert not findings, [f.render() for f in findings]
    return f"{len(rules)} FL-KERN rules, 0 findings, 0 suppressions"


def gate_mergetree_parity():
    """Device merge-tree fold (jitted scan, export, extraction) == CPU
    oracle on a small synth batch, digest-exact."""
    import bench
    from fluidframework_tpu.ops.mergetree_kernel import (
        export_to_numpy,
        pack_mergetree_batch,
        replay_export,
        summaries_from_export,
    )

    docs = [bench.synth_doc(i, 16) for i in range(5)]
    state, ops, meta = pack_mergetree_batch(docs)
    export = replay_export(None, ops, meta, S=state.tstart.shape[1])
    device = summaries_from_export(meta, export_to_numpy(export))
    assert [s.digest() for s in device] == \
        [bench.oracle_replay(d).summarize().digest() for d in docs]
    return f"{len(docs)} docs, device digests == oracle digests"


def gate_tree_parity():
    """Device tree fold == CPU oracle on a minimal sequenced log."""
    from fluidframework_tpu.ops.tree_kernel import (
        TreeDocInput,
        oracle_fallback_summary,
        replay_tree_batch,
    )
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )

    def op(seq, edits):
        return SequencedMessage(
            seq=seq, client_id="c0", client_seq=seq, ref_seq=seq - 1,
            min_seq=0, type=MessageType.OP, contents={"edits": edits},
        )

    log = [
        op(1, [{"kind": "insert", "parent": "", "field": "a",
                "anchor": None,
                "content": [{"id": "A", "type": "n", "value": 1}]}]),
        op(2, [{"kind": "insert", "parent": "", "field": "a",
                "anchor": None,
                "content": [{"id": "B", "type": "n", "value": 2}]}]),
        op(3, [{"kind": "move", "ids": ["B"], "parent": "A",
                "field": "kids", "anchor": None,
                "prev": [["B", "", "a", None]]}]),
        op(4, [{"kind": "remove", "ids": ["A"]}]),
    ]
    doc = TreeDocInput(doc_id="preflight", ops=log, final_seq=4,
                       final_msn=0)
    (device,) = replay_tree_batch([doc])
    assert device.digest() == oracle_fallback_summary(doc).digest()
    return "1 doc, device digest == oracle digest"


def gate_bench_schema():
    """The roofline block bench.py reports still has
    the schema the consumers read, and the derived key is still spelled
    ``steady_fold_pct_of_bound`` at the producer."""
    import bench

    roof = bench.roofline(96, 4, "TPU_v4")
    required = {"S", "props_plane_K", "bytes_per_op_optimistic",
                "hbm_GBps", "device_kind", "bound_ops_per_sec"}
    missing = required - set(roof)
    assert not missing, f"roofline schema lost keys: {sorted(missing)}"
    assert roof["bound_ops_per_sec"] > 0, roof
    # The dry-run derivation the bench performs on the chip:
    roof["steady_fold_pct_of_bound"] = round(
        100.0 * 1.0 / roof["bound_ops_per_sec"], 2)
    assert roof["steady_fold_pct_of_bound"] >= 0
    src = open(os.path.join(ROOT, "bench.py"), encoding="utf-8").read()
    assert "steady_fold_pct_of_bound" in src, \
        "bench.py no longer produces steady_fold_pct_of_bound"
    json.dumps(roof)  # artifact-serializable, schema-stable
    return "roofline schema ok, steady_fold_pct_of_bound derivable"


def main() -> int:
    gates = {
        "kernel_lint": _gate(gate_kernel_lint),
        "mergetree_parity": _gate(gate_mergetree_parity),
        "tree_parity": _gate(gate_tree_parity),
        "bench_schema": _gate(gate_bench_schema),
    }
    ok = all(g["ok"] for g in gates.values())
    print(json.dumps({"metric": "tpu_preflight", "preflight_ok": ok,
                      "gates": gates}))
    for name, g in gates.items():
        if not g["ok"]:
            print(f"preflight gate {name} FAILED:\n{g['detail']}",
                  file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
