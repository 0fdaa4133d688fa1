"""Batch-level metrics (engine/metrics.py): counters, occupancy, p99."""


from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.engine.metrics import EngineMetrics
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

NOW = 1_700_000_000


def _req(rt, auth, recipient=C.ZERO_PUBKEY):
    return QueryRequest(
        request_type=rt,
        auth_identity=auth,
        auth_signature=b"\x01" * C.SIGNATURE_SIZE,
        record=RequestRecord(
            msg_id=C.ZERO_MSG_ID,
            recipient=recipient,
            payload=b"\x07" * C.PAYLOAD_SIZE,
        ),
    )


def test_metrics_ring_and_percentiles():
    m = EngineMetrics(ring_size=8)
    for i in range(20):  # wraps the ring
        m.record_round(n_real=3, batch_size=4, seconds=0.001 * (i + 1))
    m.record_sweep(5)
    m.record_auth(failures=2)
    m.observe_stash("rec", 17)
    m.observe_stash("rec", 9)  # high-water keeps the max
    s = m.snapshot()
    assert s["rounds"] == 20
    assert s["real_ops"] == 60
    assert s["batch_occupancy"] == 0.75
    assert s["sweeps"] == 1 and s["evicted"] == 5
    assert s["batch_verifies"] == 1 and s["auth_failures"] == 2
    assert s["stash_high_water"] == 17
    # ring holds the last 8 rounds (13..20 ms)
    assert 12.9 < s["round_ms_p50"] < 17.1
    assert s["round_ms_p99"] <= 20.1


def test_concurrent_recording_is_lossless():
    """Hammer every recording entry point from N threads: counter totals
    must be exact and the ring consistent — record_round runs outside
    the engine lock in production (PendingRound.resolve), so the
    internal locks are the only thing between us and lost samples."""
    import threading

    m = EngineMetrics(ring_size=64)
    n_threads, per = 8, 250
    barrier = threading.Barrier(n_threads)

    def hammer(tid):
        barrier.wait()  # maximize interleaving
        for i in range(per):
            m.record_round(n_real=1, batch_size=2, seconds=0.002)
            m.record_auth(failures=1)
            m.observe_stash("mb", i % 50)
            m.observe_phase("verify", 0.0005)
            m.observe_queue_depth(i % 7)
            m.record_sweep(2)

    threads = [
        threading.Thread(target=hammer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    total = n_threads * per
    s = m.snapshot()
    assert s["rounds"] == total
    assert s["real_ops"] == total
    assert s["batch_occupancy"] == 0.5
    assert s["batch_verifies"] == total and s["auth_failures"] == total
    assert s["sweeps"] == total and s["evicted"] == 2 * total
    assert s["stash_high_water"] == 49
    assert s["queue_depth_high_water"] == 6
    # ring integrity: every committed sample is a real write (all equal
    # here, so any interleaving must yield exactly 2ms at any quantile)
    assert s["round_ms_p50"] == 2.0 and s["round_ms_p99"] == 2.0
    # histogram totals are exact too
    assert s["grapevine_phase_seconds{phase=verify}_count"] == total
    assert s["grapevine_stash_occupancy{tree=mb}_count"] == total
    assert s["grapevine_stash_occupancy{tree=rec}_count"] == 0
    # and the hammered registry still audits clean
    assert m.registry.audit()["ok"]


def test_small_sample_percentiles_do_not_underreport():
    """Satellite fix: linear interpolation under-reported p99 on a
    partially-filled ring (at 20 rounds it blended the 19th and 20th
    samples). method="higher" returns a real order statistic."""
    m = EngineMetrics(ring_size=1024)
    for i in range(20):
        m.record_round(n_real=1, batch_size=1, seconds=0.001 * (i + 1))
    s = m.snapshot()
    # p99 of 20 samples must be the largest sample, not an interpolation
    assert s["round_ms_p99"] == 20.0
    assert s["round_ms_p50"] == 11.0  # ceil order statistic, never below


def test_engine_health_includes_batch_metrics():
    cfg = GrapevineConfig(
        bucket_cipher_rounds=0,
        max_messages=64,
        max_recipients=16,
        mailbox_cap=4,
        batch_size=4,
        stash_size=96,
        expiry_period=10,
    )
    e = GrapevineEngine(cfg, seed=1)
    a, b = bytes([1]) * 32, bytes([2]) * 32
    resps = e.handle_queries(
        [_req(C.REQUEST_TYPE_CREATE, a, recipient=b)] * 2, NOW
    )
    assert all(r.status_code == C.STATUS_CODE_SUCCESS for r in resps)
    e.expire(NOW + 100)
    h = e.health()
    assert h["rounds"] == 1
    assert h["real_ops"] == 2
    assert h["batch_occupancy"] == 0.5  # 2 real ops in a 4-slot round
    assert h["sweeps"] == 1 and h["evicted"] == 2
    assert h["round_ms_p99"] > 0
    # two live records were inserted then expired
    assert h["messages"] == 0
    assert h["stash_high_water"] >= 0
    assert h["stash_overflow"] == 0


def test_round_layout_gauges_say_what_the_shapes_resolve_to():
    """The level-dense layout engages by geometry (ISSUE 26), so its
    "hit share" is static: dense levels and fetched HBM bucket rows of
    one ``oram_round`` per tree, on the registry under the ``tree``
    label and in the start-up log — here at a geometry where the
    mailbox tree is dense throughout and the records tree is not."""
    from grapevine_tpu.obs.exporter import render_prometheus

    cfg = GrapevineConfig(
        bucket_cipher_rounds=0, max_messages=256, max_recipients=16,
        mailbox_cap=4, batch_size=4, stash_size=96,
    )
    e = GrapevineEngine(cfg, seed=1)
    rec, mb = e.ecfg.rec, e.ecfg.mb
    b, bd = 4, 4 * e.ecfg.mb_choices
    layout = e.round_layout()
    # floor(log2 b) + 1 levels, never under the cache nor over the tree
    assert layout["rec"][0] == min(max(3, rec.top_cache_levels), rec.path_len)
    assert layout["mb"][0] == min(max(bd.bit_length(), mb.top_cache_levels),
                                  mb.path_len) == mb.path_len
    assert rec.path_len > layout["rec"][0]  # per-path levels remain
    for tree, c, n in (("rec", rec, b), ("mb", mb, bd)):
        ld, rows, perpath = layout[tree]
        assert perpath == n * (c.path_len - ld)
        assert rows == (1 << ld) - (1 << c.top_cache_levels) + perpath
        assert rows <= n * (c.path_len - c.top_cache_levels)
    # the number that says which kind of round a tree gets: the mailbox
    # tree is moved whole, the records tree keeps a row per path below
    assert layout["mb"][2] == 0 < layout["rec"][2]
    text = render_prometheus(e.metrics.registry)
    for tree, (ld, rows, perpath) in layout.items():
        assert f'grapevine_round_dense_levels{{tree="{tree}"}} {ld}' in text
        assert (f'grapevine_round_fetched_bucket_rows{{tree="{tree}"}} '
                f'{rows}') in text
        assert (f'grapevine_round_perpath_bucket_rows{{tree="{tree}"}} '
                f'{perpath}') in text


def test_round_layout_of_a_mailbox_tree_taller_than_the_batch_covers(caplog):
    """Dense levels above, per-path levels below, in BOTH trees (the
    shape of 2^16 recipients at B=2048, here 512 recipients at B=8):
    ``grapevine_round_perpath_bucket_rows`` is what tells it from a
    geometry whose mailbox round moves its tree whole, on the registry
    and in the start-up log."""
    import logging

    from grapevine_tpu.obs.exporter import render_prometheus

    cfg = GrapevineConfig(
        bucket_cipher_rounds=0, max_messages=1024, max_recipients=512,
        mailbox_cap=4, batch_size=8, stash_size=128,
    )
    with caplog.at_level(logging.INFO, logger="grapevine_tpu.engine.batcher"):
        e = GrapevineEngine(cfg, seed=1)
    mb, bd = e.ecfg.mb, 8 * e.ecfg.mb_choices
    ld, rows, perpath = e.round_layout()["mb"]
    assert mb.path_len - ld >= 2  # two per-path levels at least
    assert perpath == bd * (mb.path_len - ld) > 0
    assert perpath == mb.perpath_bucket_rows(bd) < rows
    text = render_prometheus(e.metrics.registry)
    assert f'grapevine_round_perpath_bucket_rows{{tree="mb"}} {perpath}' in text
    assert f"mb=({ld}, {rows}, {perpath})" in caplog.text
    assert e.metrics.registry.audit()["ok"]


def test_stash_gauges_are_kept_per_tree():
    """One gauge over both trees would hide a filling mailbox stash
    behind the records tree's: the high-water mark and the occupancy
    histogram carry the ``tree`` label, ``health()`` samples each tree
    under its own, and the flat ``stash_high_water`` of the snapshot is
    the largest of them."""
    from grapevine_tpu.obs.exporter import render_prometheus

    m = EngineMetrics()
    m.observe_stash("rec", 3)
    m.observe_stash("mb", 41)
    m.observe_stash("mb", 12)
    hw = m.registry.get("grapevine_stash_high_water")
    assert hw.label_keys == ("tree",)
    assert hw.get(tree="rec") == 3 and hw.get(tree="mb") == 41
    assert m.stash_high_water == 41 == m.snapshot()["stash_high_water"]
    text = render_prometheus(m.registry)
    assert 'grapevine_stash_high_water{tree="mb"} 41' in text
    assert 'grapevine_stash_occupancy_count{tree="mb"} 2' in text
    assert 'grapevine_stash_occupancy_count{tree="rec"} 1' in text

    cfg = GrapevineConfig(
        bucket_cipher_rounds=0, max_messages=64, max_recipients=16,
        mailbox_cap=4, batch_size=4, stash_size=96,
    )
    e = GrapevineEngine(cfg, seed=1)
    a, b = bytes([1]) * 32, bytes([2]) * 32
    e.handle_queries([_req(C.REQUEST_TYPE_CREATE, a, recipient=b)] * 2, NOW)
    h = e.health()
    assert set(h["stash_occupancy"]) == {"rec", "mb"}
    snap = e.metrics.registry.snapshot()
    for tree, n in h["stash_occupancy"].items():
        assert snap[f"grapevine_stash_high_water{{tree={tree}}}"] == n
        assert snap[f"grapevine_stash_occupancy{{tree={tree}}}_count"] == 1
    assert snap["grapevine_stash_occupancy{tree=mb_pm}_count"] == 0


def test_state_size_and_device_memory_gauges():
    """What building the state took and what it holds are set once, at
    construction; the device's peak and limit are sampled with the
    stashes (health and scrape cadence, never per round) from
    ``memory_stats()``, and read 0 on a backend that reports none."""
    import jax

    m = EngineMetrics()
    m.observe_device_memory([{"peak_bytes_in_use": 5, "bytes_limit": 16},
                             {"peak_bytes_in_use": 9, "bytes_limit": 12},
                             {}])
    reg = m.registry
    # the chip with the largest peak, and that chip's limit
    assert reg.get("grapevine_hbm_peak_bytes").get() == 9
    assert reg.get("grapevine_hbm_limit_bytes").get() == 12
    m.observe_device_memory(iter(()))
    assert reg.get("grapevine_hbm_peak_bytes").get() == 0

    cfg = GrapevineConfig(
        bucket_cipher_rounds=0, max_messages=64, max_recipients=16,
        mailbox_cap=4, batch_size=4, stash_size=96,
    )
    e = GrapevineEngine(cfg, seed=1)
    reg = e.metrics.registry
    assert reg.get("grapevine_state_bytes").get() == sum(
        x.nbytes for x in jax.tree.leaves(e.state)) > 0
    assert 0 < reg.get("grapevine_state_init_seconds").get() < 60
    e.health()  # the CPU backend reports no memory statistics
    want = e.state.rec.tree_val.devices().pop().memory_stats() or {}
    assert reg.get("grapevine_hbm_peak_bytes").get() == want.get(
        "peak_bytes_in_use", 0)
    assert reg.get("grapevine_hbm_limit_bytes").get() == want.get(
        "bytes_limit", 0)
    for name in ("grapevine_state_bytes", "grapevine_state_init_seconds",
                 "grapevine_hbm_peak_bytes", "grapevine_hbm_limit_bytes"):
        assert reg.get(name).label_keys == ()
    assert reg.audit()["ok"]


def test_compiled_memory_gauges_read_the_executable_the_engine_holds():
    """``grapevine_hbm_compiled_*``: the compiler's own count for the
    served round, read once at the first health read after the round
    has compiled, from the executable the engine's jit already holds.
    Nothing compiles for it: the jit's cache stays at one program and
    no backend compile is seen while health is read; before a round has
    run the gauges read 0 and nothing is lowered."""
    import jax
    import jax.monitoring

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)

    cfg = GrapevineConfig(
        max_messages=64, max_recipients=16, mailbox_cap=4, batch_size=4,
        stash_size=96,
    )
    e = GrapevineEngine(cfg, seed=1)
    reg = e.metrics.registry
    names = ("grapevine_hbm_compiled_argument_bytes",
             "grapevine_hbm_compiled_temp_bytes",
             "grapevine_hbm_compiled_bytes")
    programs = e._step_jit._cache_size()
    e.health()  # (compiles the stash reductions, once)
    assert [reg.get(n).get() for n in names] == [0, 0, 0]
    # no round program was made for it (the jit's cache is the
    # process's: other engines' programs may sit in it)
    assert e._step_jit._cache_size() == programs
    e.handle_queries([_req(C.REQUEST_TYPE_CREATE, b"\x01" * 32,
                           b"\x02" * 32)], NOW)
    seen, programs = len(compiles), e._step_jit._cache_size()
    assert programs >= 1
    e.health()
    assert len(compiles) == seen and e._step_jit._cache_size() == programs
    args, temp, total = (reg.get(n).get() for n in names)
    state_bytes = reg.get("grapevine_state_bytes").get()
    # the arguments are the state and the batch; donated, so the
    # outputs alias them and the total is little more than both parts
    assert state_bytes <= args < state_bytes + (1 << 20)
    assert temp > 0 and args + temp <= total < args + temp + (1 << 20)
    stats = e.compiled_round_memory()
    assert stats.argument_size_in_bytes == args
    assert stats.temp_size_in_bytes == temp
    for n in names:
        assert reg.get(n).label_keys == ()
    assert reg.audit()["ok"]
    # read once: a later health read lowers nothing again
    e.compiled_round_memory = None
    e.health()


def test_state_init_is_a_host_span_of_a_capture(tmp_path):
    """``grapevine/state_init`` is a TraceAnnotation around the state's
    building: a profiler capture that covers an engine's construction
    holds it among its host spans."""
    import glob

    import jax
    from jax.profiler import ProfileData

    cfg = GrapevineConfig(
        bucket_cipher_rounds=0, max_messages=64, max_recipients=16,
        mailbox_cap=4, batch_size=4, stash_size=96,
    )
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        GrapevineEngine(cfg, seed=1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "grapevine/state_init" in names
