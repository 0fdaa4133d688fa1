"""The least HBM bytes one round must move, from the geometry alone.

A Path-ORAM access reads its whole path and writes it back: every
bucket below the on-chip tree-top cache, as its index row (``z`` words),
its value row (``z * value_words`` words) and, where the tree is
encrypted at rest, its 2-word nonce. One round makes ``batch`` accesses
to the records tree and two passes of ``batch * choices`` accesses to
the mailbox tree. On a mesh every chip reads the whole masked working
set from its own heap range (the oblivious schedule: each chip's access
count is uniform) and writes only the buckets it owns.

This is a floor that ignores the stash, the position map and the
response planes; a roofline share read from it can only be too low.
"""

from __future__ import annotations

WORD_BYTES = 4


def tree_geometry(oram_cfg, accesses: int) -> dict:
    """The numbers of one tree that the floor depends on, read off the
    program's resolved ``OramConfig``."""
    return {"accesses": int(accesses),
            "path_len": int(oram_cfg.path_len),
            "cached_levels": int(oram_cfg.top_cache_levels),
            "bucket_slots": int(oram_cfg.bucket_slots),
            "value_words": int(oram_cfg.value_words),
            "encrypted": bool(oram_cfg.encrypted)}


def round_geometry(ecfg, shards: int) -> dict:
    b, d = ecfg.batch_size, ecfg.mb_choices
    return {"batch": b, "shards": int(shards),
            "trees": {"records": tree_geometry(ecfg.rec, b),
                      "mailbox": tree_geometry(ecfg.mb, 2 * b * d)}}


def tree_round_bytes(t: dict) -> tuple[int, int]:
    """(bytes read, bytes written) by one round in one tree."""
    rows = t["accesses"] * (t["path_len"] - t["cached_levels"])
    row_words = (t["bucket_slots"] * (1 + t["value_words"])
                 + (2 if t["encrypted"] else 0))
    nbytes = rows * row_words * WORD_BYTES
    return nbytes, nbytes


def least_round_bytes_per_chip(geometry: dict) -> float:
    read = written = 0
    for t in geometry["trees"].values():
        r, w = tree_round_bytes(t)
        read += r
        written += w
    return read + written / geometry["shards"]
