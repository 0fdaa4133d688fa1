"""The least HBM bytes one expiry sweep must move, from the geometry
alone.

A sweep decides for every record and every mailbox entry whether it has
come due, and what it touches may not depend on which have: so it reads
every bucket of both trees once, and since it cannot write back only
the buckets it changed, it writes every one once. A bucket is its index
row (``z`` words), its value row (``z * value_words`` words) and, where
the tree is encrypted at rest, its 2-word nonce, which a re-keyed
bucket needs anew. The top ``cached_levels`` of a tree live in planes of
their own and not in their tree rows, which is the same number of
buckets either way: ``2^path_len - 1`` a tree. The stash of each tree
(``stash_rows`` blocks of one index word and ``value_words`` words) is
swept with it. On a mesh every chip sweeps the heap range it owns, and
the stashes are every chip's.

This is what any implementation of the pass must move, whether it
decrypts in chunks under a scan (``engine/expiry.py`` today) or in a
kernel; the free list it rebuilds, the liveness plane and the position
maps are left out, so a roofline share read from it can only be too
low.
"""

from __future__ import annotations

from .round_bytes import WORD_BYTES


def tree_geometry(oram_cfg) -> dict:
    """The numbers of one tree that the floor depends on, read off the
    program's resolved ``OramConfig``."""
    return {"buckets": (1 << int(oram_cfg.path_len)) - 1,
            "bucket_slots": int(oram_cfg.bucket_slots),
            "value_words": int(oram_cfg.value_words),
            "encrypted": bool(oram_cfg.encrypted),
            "stash_rows": int(oram_cfg.stash_size)}


def sweep_geometry(ecfg, shards: int) -> dict:
    return {"shards": int(shards),
            "trees": {"records": tree_geometry(ecfg.rec),
                      "mailbox": tree_geometry(ecfg.mb)}}


def tree_sweep_bytes(t: dict) -> tuple[int, int]:
    """(bytes of the tree's buckets, bytes of its stash), one way."""
    row_words = (t["bucket_slots"] * (1 + t["value_words"])
                 + (2 if t["encrypted"] else 0))
    return (t["buckets"] * row_words * WORD_BYTES,
            t["stash_rows"] * (1 + t["value_words"]) * WORD_BYTES)


def least_sweep_bytes_per_chip(geometry: dict) -> float:
    """Read once and written once: each chip its share of the buckets
    and the whole of the stashes."""
    total = 0.0
    for t in geometry["trees"].values():
        buckets, stash = tree_sweep_bytes(t)
        total += 2 * (buckets / geometry["shards"] + stash)
    return total
