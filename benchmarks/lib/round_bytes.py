"""The least HBM bytes one oblivious round must move, from the geometry
alone.

A Path-ORAM tree of ``path_len`` levels keeps its top ``cached_levels``
on chip; level ``L`` below them holds ``2^L`` buckets in HBM. One pass
over a tree makes ``a`` accesses together, each to a path the data
chooses, and what moves may not depend on which. So a level whose
``2^L`` buckets are no more than ``a`` is moved whole, once (every
bucket can lie on some path; fewer rows would name the ones that do),
and a deeper level costs one bucket row per access:

    rows(pass) = sum over L in [cached_levels, path_len) of min(2^L, a)

each row read once and written back once, as its index row (``z``
words), its value row (``z * value_words`` words) and, where the tree
is encrypted at rest, its 2-word nonce. One round makes one pass of
``batch`` accesses over the records tree and two passes of ``batch *
choices`` accesses over the mailbox tree. On a mesh every chip reads
the whole masked working set from its own heap range (each chip's
access count is uniform) and writes only the buckets it owns.

This is derived from the semantics, not read off the program: a round
that moved ``a`` rows at every level (as the program did before PR 26)
moves more, and one that moves less leaks. The stash, the position map
and the response planes are left out, so a roofline share read from it
can only be too low; ``tests/test_round_bytes.py`` holds it at or under
the program's own cost ledger at every configuration.
"""

from __future__ import annotations

WORD_BYTES = 4


def pass_rows(t: dict) -> int:
    """Bucket rows one pass over the tree must read (and write)."""
    return sum(min(1 << level, t["accesses"])
               for level in range(t["cached_levels"], t["path_len"]))


def tree_geometry(oram_cfg, accesses: int, passes: int) -> dict:
    """The numbers of one tree that the floor depends on, read off the
    program's resolved ``OramConfig``; ``accesses`` are those of one
    pass. ``rows_per_pass`` is the floor's own count, said beside them."""
    t = {"accesses": int(accesses), "passes": int(passes),
         "path_len": int(oram_cfg.path_len),
         "cached_levels": int(oram_cfg.top_cache_levels),
         "bucket_slots": int(oram_cfg.bucket_slots),
         "value_words": int(oram_cfg.value_words),
         "encrypted": bool(oram_cfg.encrypted)}
    t["rows_per_pass"] = pass_rows(t)
    return t


def round_geometry(ecfg, shards: int) -> dict:
    b, d = ecfg.batch_size, ecfg.mb_choices
    return {"batch": b, "shards": int(shards),
            "trees": {"records": tree_geometry(ecfg.rec, b, 1),
                      "mailbox": tree_geometry(ecfg.mb, b * d, 2)}}


def tree_round_bytes(t: dict) -> tuple[int, int]:
    """(bytes read, bytes written) by one round in one tree."""
    row_words = (t["bucket_slots"] * (1 + t["value_words"])
                 + (2 if t["encrypted"] else 0))
    nbytes = t["passes"] * pass_rows(t) * row_words * WORD_BYTES
    return nbytes, nbytes


def least_round_bytes_per_chip(geometry: dict) -> float:
    read = written = 0
    for t in geometry["trees"].values():
        r, w = tree_round_bytes(t)
        read += r
        written += w
    return read + written / geometry["shards"]
