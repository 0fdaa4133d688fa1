"""``lib/gcwatch.py``: the collections of a window are counted by
generation with their pauses, and ``settle()`` takes what is kept out of
the collector's sight while young garbage still goes."""

import gc

from benchmarks.lib import gcwatch


class _Node:
    def __init__(self):
        self.me = self  # a cycle: only the collector frees it


def test_the_watch_counts_collections_by_generation():
    watch = gcwatch.GcWatch()
    watch.start()
    try:
        gc.collect(0)
        gc.collect(1)
        gc.collect(2)
        gc.collect(2)
    finally:
        seen = watch.stop()
    assert watch.stop() == seen  # safe to call twice
    counts = seen["gc_collections_in_window"]
    assert counts[2] == seen["gc_gen2_in_window"] == 2
    assert counts[0] >= 1 and counts[1] >= 1
    assert 0 < seen["gc_gen2_pause_ms"] <= seen["gc_pause_ms"]
    gc.collect(2)  # after stop(): not counted
    assert watch.count[2] == 2


def test_settle_collects_young_garbage_and_freezes_what_is_kept():
    gc.collect()
    before = gc.get_freeze_count()
    try:
        kept = [_Node() for _ in range(1000)]
        for _ in range(1000):
            _Node()  # garbage: cycles nobody holds
        gcwatch.settle()
        assert gc.get_freeze_count() >= before + 1000
        # the garbage went before the freeze: no _Node but the kept ones
        alive = sum(isinstance(o, _Node) for o in gc.get_objects())
        gc.unfreeze()
        alive += sum(isinstance(o, _Node) for o in gc.get_objects()) - alive
        assert alive == len(kept)
    finally:
        gc.unfreeze()
