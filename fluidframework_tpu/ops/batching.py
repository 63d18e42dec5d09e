"""Shared batch-partitioning: known-fallback docs to the oracle, the rest
through a device batch function, results scattered back in input order.

One implementation of the split/scatter bookkeeping for every kernel's
``replay_*_batch`` / ``replay_*_sharded`` entry point (the pattern was
previously hand-rolled per kernel; review-found)."""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, TypeVar, Union

from ..utils.telemetry import span

Doc = TypeVar("Doc")
Result = TypeVar("Result")


def count_fallback(stats: Optional[dict], reason: Union[bool, str]) -> None:
    """Bump the shared fallback counters: the total ``fallback_docs``
    plus — when the predicate names WHY (a reason string instead of a
    bare True) — a per-reason ``fallback_<reason>`` counter, so a bench
    can report revive vs multi-id-move vs MAX_DEPTH instead of one
    opaque number.  THE one counting point for pre-pack routing and the
    extractors' post-fold fallbacks alike (the split must sum to the
    total by construction, not by discipline)."""
    if stats is None:
        return
    stats["fallback_docs"] = stats.get("fallback_docs", 0) + 1
    if isinstance(reason, str) and reason:
        key = f"fallback_{reason}"
        stats[key] = stats.get(key, 0) + 1


def partition_replay(
    docs: Sequence[Doc],
    known_fallback: Callable[[Doc], Union[bool, str, None]],
    fallback_fn: Callable[[Doc], Result],
    batch_fn: Callable[[List[Doc]], List[Result]],
    stats: Optional[dict] = None,
    stage: Optional[dict] = None,
) -> List[Result]:
    """Route docs matching ``known_fallback`` through ``fallback_fn`` (the
    oracle), fold the rest as one device batch, and return results in the
    original order.  Filtering first keeps fallback docs from inflating the
    shared power-of-two pack buckets and wasting their shard of the fold.
    ``known_fallback`` may return a plain truthy value or a REASON string;
    ``stats`` (optional dict) then accumulates ``fallback_docs`` plus a
    per-reason ``fallback_<reason>`` counter for the pre-pack routing
    (post-fold fallbacks are the extractors' to count, through the same
    :func:`count_fallback`).  ``stage`` (optional dict) accumulates the
    seconds the fallback folds took under ``fallback``."""
    if not docs:
        return []
    out: List[Optional[Result]] = [None] * len(docs)
    device_idx: List[int] = []
    fallback: List[tuple] = []
    for i, doc in enumerate(docs):
        reason = known_fallback(doc)
        if reason:
            fallback.append((i, reason))
        else:
            device_idx.append(i)
    if fallback:
        with span("pipeline.fallback", stage, "fallback",
                  docs=len(fallback)):
            for i, reason in fallback:
                out[i] = fallback_fn(docs[i])
                count_fallback(stats, reason)
    if device_idx:
        results = batch_fn([docs[i] for i in device_idx])
        assert len(results) == len(device_idx)
        for d, i in enumerate(device_idx):
            out[i] = results[d]
    return out
