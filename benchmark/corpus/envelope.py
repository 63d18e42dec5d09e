"""Seed the system under test with a corpus, as the service stores it.

The shape of ``bench.build_catchup_corpus``: each document gets an empty
summary at seq 0 holding one datastore ``ds`` with one channel, and its
tail goes straight into the op log, each op wrapped in the
``groupedBatch`` envelope the container runtime emits, with each
client's own ``client_seq`` count.
"""

from __future__ import annotations


def seed_store(service, doc_ids: list, tails: list, channel: dict) -> None:
    """Upload the empty summary for every document and append its tail.
    ``channel`` is the configuration's ``{"ds", "id", "type"}``."""
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedMessage,
    )
    from fluidframework_tpu.runtime.container import ContainerRuntime

    seeded = ContainerRuntime()
    seeded.create_datastore(channel["ds"]).create_channel(
        channel["type"], channel["id"])
    seed_tree = seeded.summarize()
    upload, append = service.storage.upload, service.oplog.append
    op_type, ds, cid = MessageType.OP, channel["ds"], channel["id"]
    for doc_id, tail in zip(doc_ids, tails):
        upload(doc_id, seed_tree, 0)
        client_seqs: dict = {}
        for seq, client, ref_seq, min_seq, contents in tail:
            cseq = client_seqs[client] = client_seqs.get(client, 0) + 1
            append(doc_id, SequencedMessage(
                seq=seq, client_id=client, client_seq=cseq, ref_seq=ref_seq,
                min_seq=min_seq, type=op_type,
                contents={"type": "groupedBatch", "ops": [
                    {"ds": ds, "channel": cid, "clientSeq": cseq,
                     "contents": contents}]},
            ))
