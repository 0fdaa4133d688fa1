"""The least bytes one chip must receive over the inter-chip links in
one round on a mesh, from the geometry alone, and the chip's published
inter-chip bandwidth.

Both trees are held in equal parts, one per chip, and every chip runs
the whole round: it needs every bucket row the round fetches. A pass
fetches ``lib/round_bytes.py`` ``pass_rows`` rows (whole levels where
the accesses cover them, one row per access below), and which chip owns
a per-path row is the data's choice, uniform over the chips. So of the
rows of a pass a chip owns one in ``n`` and must be sent the other
``(n - 1) / n``, each as it is stored: its index row (``z`` words), its
value row at the stored width (``stored_row_words``: 6,080 block words
are kept as 6,144, whole lane tiles) and, where the tree is encrypted
at rest, its 2-word nonce. Write-back is local (every row has one
owner) and costs the links nothing.

This is what the rows cost to deliver, whatever assembles them: an
all-reduce of masked full-size buffers (the program today) moves more,
an all-gather of owned rows this much. A share of the roofline read
from it can only be too low.
"""

from __future__ import annotations

from .round_bytes import WORD_BYTES, pass_rows

#: inter-chip interconnect bandwidth a chip, GB/s. Source: Google Cloud
#: documentation, "TPU v5e": 1,600 Gbit/s of chip-to-chip interconnect
#: (ICI) bandwidth per chip = 200 GB/s.
PEAK_ICI_GBPS = {
    "TPU v5 lite": 200.0,
}


def peak_ici_gbps(device_kind: str) -> float:
    if device_kind not in PEAK_ICI_GBPS:
        raise KeyError(
            f"no published inter-chip bandwidth for device_kind "
            f"{device_kind!r}: add it to benchmarks/lib/ici_bytes.py with "
            "its source")
    return PEAK_ICI_GBPS[device_kind]


def stored_row_bytes(t: dict, stored_row_words: int | None = None) -> int:
    """Bytes of one bucket row as the tree stores it: index row, value
    row (``stored_row_words`` where the program pads it, else the block
    words), nonce."""
    value = (stored_row_words if stored_row_words is not None
             else t["bucket_slots"] * t["value_words"])
    return WORD_BYTES * (t["bucket_slots"] + value
                         + (2 if t["encrypted"] else 0))


def least_received_bytes_per_chip(geometry: dict,
                                  stored: dict | None = None) -> float:
    """Bytes a chip must receive in one round: over both trees, passes
    x rows a pass x stored row bytes, times ``(n - 1) / n``. ``stored``
    optionally gives each tree's stored value-row words. 0 on one chip."""
    n = geometry["shards"]
    if n <= 1:
        return 0.0
    stored = stored or {}
    total = sum(t["passes"] * pass_rows(t)
                * stored_row_bytes(t, stored.get(name))
                for name, t in geometry["trees"].items())
    return total * (n - 1) / n
