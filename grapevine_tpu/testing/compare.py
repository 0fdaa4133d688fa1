"""State-comparison helpers shared by tests, bench.py and chip_smoke.py."""

from __future__ import annotations

import jax
import numpy as np

__all__ = [
    "states_equal",
    "logical_tree_planes",
    "assert_logical_state_equal",
    "logical_block_map",
]


def states_equal(sa, sb):
    """Engine-state bit-equality, every leaf, at-rest ciphertext and the
    padded bucket no heap index addresses included.

    Returns (equal, first_differing_keypath_or_None).
    """
    if jax.tree_util.tree_structure(sa) != jax.tree_util.tree_structure(sb):
        return False, "<tree structure>"
    la = {
        jax.tree_util.keystr(p): np.asarray(x)
        for p, x in jax.tree_util.tree_leaves_with_path(sa)
    }
    lb = dict(zip(la.keys(), map(np.asarray, jax.tree_util.tree_leaves(sb))))
    for key, x in la.items():
        if not np.array_equal(x, lb[key]):
            return False, key
    return True, None


def logical_tree_planes(cfg, oram):
    """Decrypted logical content of one ORAM's bucket tree, with the
    tree-top cache overlaid (host-side; never on the round path).

    Returns ``(idx [n, Z], val [n, stored_row_words], leaf [n, Z] |
    None)`` plaintext planes (the value rows as stored: the blocks' Z*V
    words and the zero pad after them).
    Under ``cfg.top_cache_levels = k > 0`` the top 2^k−1
    buckets' HBM rows are stale (empty-at-init ciphertext, re-keyed but
    never read) and the authoritative plaintext lives in the cache
    planes — so rows [0, 2^k−1) are taken from the cache. This is the
    canonical form the cached↔uncached bit-identity contract compares:
    two states are equal iff their logical planes, stashes, maps, and
    scalars are equal (ciphertext at cached levels legitimately
    diverges — the cached run never re-encrypts them).
    """
    from ..oblivious.bucket_cipher import row_keystream, row_plane_keystreams
    import jax.numpy as jnp

    z, v = cfg.bucket_slots, cfg.value_words
    n = cfg.n_buckets_padded
    idx = np.asarray(oram.tree_idx).reshape(n, z).copy()
    val = np.asarray(oram.tree_val).reshape(n, -1).copy()
    leaf = (
        np.asarray(oram.tree_leaf).reshape(n, z).copy()
        if np.asarray(oram.tree_leaf).size
        else None
    )
    if cfg.encrypted:
        buckets = jnp.arange(n, dtype=jnp.uint32)
        ks_idx, ks_val = row_plane_keystreams(
            oram.cipher_key, buckets, oram.nonces, z, cfg.row_words,
            cfg.cipher_rounds,
        )
        idx ^= np.asarray(ks_idx)
        val ^= np.asarray(ks_val)
        if leaf is not None:
            ksl = np.asarray(
                row_keystream(
                    oram.cipher_key, buckets + jnp.uint32(n), oram.nonces,
                    z, cfg.cipher_rounds,
                )
            )
            leaf ^= ksl
    cb = cfg.cache_buckets
    if cb:
        idx[:cb] = np.asarray(oram.cache_idx).reshape(cb, z)
        val[:cb] = np.asarray(oram.cache_val)
        if leaf is not None:
            leaf[:cb] = np.asarray(oram.cache_leaf).reshape(cb, z)
    return idx, val, leaf


def logical_block_map(cfg, oram) -> dict:
    """{block index: value bytes} of every live block in one ORAM —
    tree planes (cache overlaid) ∪ stash. Placement-free (host-side;
    never on the round path)."""
    from ..oblivious.primitives import SENTINEL

    z, v = cfg.bucket_slots, cfg.value_words
    idx, val, _leaf = logical_tree_planes(cfg, oram)
    out: dict = {}
    rows = val[:, : cfg.val_row_words].reshape(-1, v)
    flat = idx.reshape(-1)
    for slot in np.nonzero(flat != int(SENTINEL))[0]:
        out[int(flat[slot])] = rows[slot].tobytes()
    sidx = np.asarray(oram.stash_idx)
    sval = np.asarray(oram.stash_val)
    for j in np.nonzero(sidx != int(SENTINEL))[0]:
        blk = int(sidx[j])
        assert blk not in out, (
            f"block {blk} lives in two places — the "
            "tree/stash partition invariant broke"
        )
        out[blk] = sval[j].tobytes()
    return out


def assert_logical_state_equal(ecfg_a, sa, ecfg_b, sb, ctx=""):
    """Cached↔uncached final-state contract: every logical plane, stash,
    position map, and scalar equal — the tree-cache analog of PR 7's
    payload-state bit-equality (which cache-level ciphertext divergence
    makes too strict to apply raw). Works across differing
    ``top_cache_levels`` and across flat/recursive posmaps (inner trees
    compared logically too, via their own planes)."""
    from ..oram.posmap import inner_oram_config

    for tree in ("rec", "mb"):
        ca, cb_ = getattr(ecfg_a, tree), getattr(ecfg_b, tree)
        oa, ob = getattr(sa, tree), getattr(sb, tree)
        pa = logical_tree_planes(ca, oa)
        pb = logical_tree_planes(cb_, ob)
        for name, x, y in zip(("idx", "val", "leaf"), pa, pb):
            if x is None and y is None:
                continue
            # but for the padded last bucket, which no heap index
            # addresses
            assert np.array_equal(x[:-1], y[:-1]), (
                f"{ctx}: {tree} logical {name} plane diverges"
            )
        for f in ("stash_idx", "stash_val", "stash_leaf", "overflow",
                  "epoch", "cipher_key"):
            assert np.array_equal(
                np.asarray(getattr(oa, f)), np.asarray(getattr(ob, f))
            ), f"{ctx}: {tree}.{f} diverges"
        if ca.posmap is None:
            assert np.array_equal(
                np.asarray(oa.posmap), np.asarray(ob.posmap)
            ), f"{ctx}: {tree} flat posmap diverges"
        else:
            ia, ib = inner_oram_config(ca.posmap), inner_oram_config(cb_.posmap)
            qa = logical_tree_planes(ia, oa.posmap.inner)
            qb = logical_tree_planes(ib, ob.posmap.inner)
            for name, x, y in zip(("idx", "val"), qa[:2], qb[:2]):
                assert np.array_equal(x[:-1], y[:-1]), (
                    f"{ctx}: {tree} inner posmap logical {name} diverges"
                )
            for f in ("stash_idx", "stash_val", "posmap", "overflow"):
                assert np.array_equal(
                    np.asarray(getattr(oa.posmap.inner, f)),
                    np.asarray(getattr(ob.posmap.inner, f)),
                ), f"{ctx}: {tree} inner posmap {f} diverges"
            assert np.array_equal(
                np.asarray(oa.posmap.dummy_entry),
                np.asarray(ob.posmap.dummy_entry),
            ), f"{ctx}: {tree} posmap dummy_entry diverges"
    for f in ("freelist", "free_top", "recipients", "seq", "hash_key",
              "id_key", "rng"):
        assert np.array_equal(
            np.asarray(getattr(sa, f)), np.asarray(getattr(sb, f))
        ), f"{ctx}: {f} diverges"
