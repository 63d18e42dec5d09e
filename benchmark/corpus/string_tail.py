"""SharedString op tails: BASELINE.json config #1's op mix, keyed by seed,
from three clients that edit concurrently.

The op mix is a copy of ``bench.synth_doc`` (the pinned merge-tree
beastTest-style mix: three clients round-robin; 62% inserts of 1-8
letters, 28% removes of up to 8 characters, and on 30% of documents 10%
annotates of one key ``f`` with a value in 0..3), kept here so that a
later change to the program cannot move the yardstick.  The generator is
keyed by ``(seed, document index)``, so ``--seed`` makes the data; every
document of every seed has the same tail length, and the same 30% of
document indices annotate.

Unlike the pinned mix, whose every op saw the one before it, the ops come
in rounds, as collaborating clients make them: in a round of 1-6 ops
every client writes against the state at the round's start plus its own
ops of the round (``ref_seq`` is the seq the round started from), so a
round's ops are concurrent with each other, and the server's ``min_seq``
(the least ``ref_seq`` the three clients last sent) follows the slowest
client up the tail.  A client picks positions within a lower bound of the
length it sees (its concurrent removes may overlap another client's, which
this count takes twice), so every op is valid without simulating the
document; the reference resolves what the ops do.

Each op is plain data, ``(seq, client, ref_seq, min_seq, contents)``:
the reference reads these tuples, and ``envelope.seed_store`` wraps them
for the program.
"""

from __future__ import annotations

import random

ALPHABET = "abcdefghijklmnopqrstuvwxyz "
CLIENTS = ("client0", "client1", "client2")
#: the most ops in one round of concurrent edits
MAX_ROUND = 6


def doc_rng(seed: int, doc_idx: int) -> random.Random:
    return random.Random(seed * 1_000_003 + doc_idx * 7919 + 13)


def string_tail(seed: int, doc_idx: int, n_ops: int) -> list:
    """One document's sequenced op tail after an empty summary at seq 0:
    ``[(seq, client, ref_seq, min_seq, contents), ...]`` with seq
    1..n_ops."""
    rng = doc_rng(seed, doc_idx)
    rand, randint, choice = rng.random, rng.randint, rng.choice
    annotating_doc = doc_idx % 10 >= 7
    last_ref = {c: 0 for c in CLIENTS}
    ops, base = [], 0     # base: lower bound of the length at the round start
    while len(ops) < n_ops:
        ref = len(ops)
        own = {c: 0 for c in CLIENTS}    # each client's net change this round
        for _ in range(min(randint(1, MAX_ROUND), n_ops - len(ops))):
            i = len(ops)
            client = CLIENTS[i % 3]
            length = base + own[client]
            r = rand()
            if not annotating_doc:
                r = min(r, 0.89)  # no annotates in pure-text docs
            if r < 0.62 or length < 4:
                pos = randint(0, length)
                text = "".join(choice(ALPHABET)
                               for _ in range(randint(1, 8)))
                contents = {"kind": "insert", "pos": pos, "text": text}
                change = len(text)
            elif r < 0.9:
                start = randint(0, length - 2)
                end = min(length, start + randint(1, 8))
                contents = {"kind": "remove", "start": start, "end": end}
                change = start - end
            else:
                start = randint(0, length - 2)
                end = min(length, start + randint(1, 8))
                contents = {"kind": "annotate", "start": start, "end": end,
                            "props": {"f": randint(0, 3)}}
                change = 0
            own[client] += change
            last_ref[client] = ref
            ops.append((i + 1, client, ref, min(last_ref.values()),
                        contents))
        base = max(0, base + sum(own.values()))
    return ops
