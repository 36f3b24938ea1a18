"""Serving subsystem: ring bucketing, mesh engine, admission + metrics.

The multi-device assertions (shard_map ≡ single-device parity) skip on a
single-device host and run in the CI lane that forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import idd_loops
from repro.core.dram import CommandTrace
from repro.core.estimate_batch import bucketed_trace_batch
from repro.launch.mesh import make_local_mesh
from repro.serving import (EstimationService, RingConfig, ServiceConfig,
                           TraceRing, TraceTooLongError)


def _sweeps(ns=(1, 8, 16, 64)):
    return [idd_loops.validation_sweep(n) for n in ns]


def _corrupt(trace: CommandTrace) -> CommandTrace:
    """A protocol-illegal copy: first ACT->PRE gap squeezed to 2 cycles."""
    return CommandTrace(trace.cmd, trace.bank, trace.row, trace.col,
                        trace.data, trace.dt.at[0].set(2))


# ---------------------------------------------------------------------------
# TraceRing
# ---------------------------------------------------------------------------
def test_ring_empty_flush_is_noop():
    ring = TraceRing()
    assert ring.take() is None
    assert len(ring) == 0


def test_ring_pads_to_bucket_shapes():
    ring = TraceRing(RingConfig(length_buckets=(256,), count_buckets=(4,)))
    traces = _sweeps((1, 8, 16))           # lengths 24, 80, 144
    for tr in traces:
        ring.admit(tr)
    rb = ring.take()
    assert rb.batch.trace.cmd.shape == (4, 256)
    assert rb.tickets == (0, 1, 2)
    assert rb.n_real == 3 and rb.slots == 4 and rb.fill == 0.75
    # the weight mask covers exactly the real commands
    np.testing.assert_array_equal(
        np.asarray(rb.batch.weight).sum(axis=1),
        [int(tr.n) for tr in traces] + [0])
    assert len(ring) == 0 and ring.take() is None


@pytest.mark.parametrize("case", ["past-window", "admitted-as-rows"])
def test_ring_rejects_trace_longer_than_largest_bucket(case, quick_vampire):
    """The limit is one whole window, count x length buckets (4 x 128 =
    512 commands here): past it a trace is refused; under it a trace past
    the largest length bucket is admitted as rows of one ticket, and its
    one report is the whole trace's."""
    config = RingConfig(length_buckets=(64, 128), count_buckets=(4,))
    if case == "past-window":
        with pytest.raises(TraceTooLongError) as ei:
            TraceRing(config).admit(idd_loops.validation_sweep(64))  # 528
        assert ei.value.n == 528 and ei.value.limit == 512
        return
    tr = idd_loops.validation_sweep(16)                  # 144 commands
    ring = TraceRing(config)
    ring.admit(tr)
    rb = ring.take()
    assert rb.tickets == (0,) and rb.rows == (2,) and rb.slots == 4
    np.testing.assert_array_equal(np.asarray(rb.batch.weight).sum(axis=1),
                                  [128, 16, 0, 0])
    svc = EstimationService(quick_vampire, ServiceConfig(ring=config))
    ticket = svc.submit(tr)
    assert svc.step() == 1 and len(svc.ring) == 0
    got = svc.result(ticket)
    whole = quick_vampire.estimate([tr], impl="reference")
    for f in whole._fields:
        # float32 sums of two rows against one: a few ulps of the total
        np.testing.assert_allclose(np.asarray(getattr(got, f)),
                                   np.asarray(getattr(whole, f))[0],
                                   rtol=1e-6)


def test_ring_windows_group_by_vendor_subset_fifo():
    ring = TraceRing(RingConfig(length_buckets=(256,), count_buckets=(4,)))
    trs = _sweeps((1, 4, 8, 16))
    ring.admit(trs[0], group=(0, 1))
    ring.admit(trs[1], group=(0, 1))
    ring.admit(trs[2], group=(2,))
    ring.admit(trs[3], group=(0, 1))
    first = ring.take()
    assert first.group == (0, 1) and first.tickets == (0, 1, 3)
    second = ring.take()
    assert second.group == (2,) and second.tickets == (2,)
    assert ring.take() is None


def test_ring_reuses_pad_buffers_in_place():
    ring = TraceRing(RingConfig(length_buckets=(256,), count_buckets=(4,)))
    ring.admit(_sweeps((8,))[0])
    ring.take()
    ring.admit(_sweeps((16,))[0])
    ring.take()
    assert list(ring._buffers) == [(4, 256)]   # one persistent buffer set


def test_ring_max_batch_caps_window():
    ring = TraceRing(RingConfig(length_buckets=(256,), count_buckets=(2, 4)))
    for tr in _sweeps((1, 4, 8)):
        ring.admit(tr)
    rb = ring.take(max_batch=2)
    assert rb.tickets == (0, 1) and rb.slots == 2
    assert len(ring) == 1


# ---------------------------------------------------------------------------
# bucketed_trace_batch (the core hook the ring pads through on device)
# ---------------------------------------------------------------------------
def test_bucketed_trace_batch_matches_exact_pad(quick_vampire):
    trs = _sweeps((1, 8, 16))
    exact = quick_vampire.estimate(trs)
    tb = bucketed_trace_batch(trs, n_slots=8, length=512)
    assert tb.trace.cmd.shape == (8, 512)
    bucketed = quick_vampire.estimate(tb)
    np.testing.assert_allclose(
        np.asarray(bucketed.avg_current_ma)[:3],
        np.asarray(exact.avg_current_ma), rtol=1e-5)


def test_bucketed_trace_batch_validates_shape():
    trs = _sweeps((1, 8))
    with pytest.raises(ValueError):
        bucketed_trace_batch(trs, n_slots=1, length=512)
    with pytest.raises(ValueError):
        bucketed_trace_batch(trs, n_slots=4, length=64)
    with pytest.raises(ValueError):
        bucketed_trace_batch([], n_slots=4, length=64)


# ---------------------------------------------------------------------------
# EstimationService: admission, modes, metrics, lifecycle
# ---------------------------------------------------------------------------
def test_service_every_mode_matches_direct_estimate(quick_vampire):
    trs = _sweeps()
    for mode, kwargs in (("mean", {}), ("range", {}), ("surface", {}),
                         ("distribution",
                          dict(ones_frac=0.5, toggle_frac=0.25))):
        svc = EstimationService(
            quick_vampire, ServiceConfig(mode=mode, **kwargs))
        tickets, rejections = svc.submit_many(trs)
        assert not rejections
        assert svc.drain() == len(trs)
        direct = quick_vampire.estimate(trs, mode=mode, **kwargs)
        for i, t in enumerate(tickets):
            row = svc.result(t)
            got, want = ((row,), (direct,)) if mode != "range" \
                else (row, direct)
            for g, w in zip(got, want):
                np.testing.assert_allclose(
                    np.asarray(g.energy_pj),
                    np.asarray(w.energy_pj)[i], rtol=1e-5)


def test_service_vendor_subset_requests(quick_vampire):
    trs = _sweeps((1, 8, 16))
    svc = EstimationService(quick_vampire, ServiceConfig())
    ta, _ = svc.submit_many(trs[:2], vendors=(1, 2))
    tb, _ = svc.submit_many(trs[2:], vendors=(0,))
    # two vendor groups -> two dispatch windows
    assert svc.drain() == 3 and svc.metrics().dispatches == 2
    direct12 = quick_vampire.estimate(trs[:2], (1, 2))
    direct0 = quick_vampire.estimate(trs[2:], (0,))
    for i, t in enumerate(ta):
        row = np.asarray(svc.result(t).avg_current_ma)
        assert row.shape == (2,)
        np.testing.assert_allclose(row,
                                   np.asarray(direct12.avg_current_ma)[i],
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(svc.result(tb[0]).avg_current_ma),
                               np.asarray(direct0.avg_current_ma)[0],
                               rtol=1e-5)


def test_service_mixed_admission_rejects_and_still_dispatches(quick_vampire):
    legal = _sweeps((8, 16))
    bad = _corrupt(legal[0])
    svc = EstimationService(quick_vampire, ServiceConfig())
    tickets, rejections = svc.submit_many([legal[0], bad, legal[1]])
    assert tickets[1] is None and len(rejections) == 1
    assert rejections[0].reason == "protocol" and rejections[0].rules
    assert rejections[0].diagnostics[0].rule
    # the legal traces ride through regardless
    assert svc.drain() == 2
    direct = quick_vampire.estimate(legal)
    for i, t in enumerate((tickets[0], tickets[2])):
        np.testing.assert_allclose(
            np.asarray(svc.result(t).avg_current_ma),
            np.asarray(direct.avg_current_ma)[i], rtol=1e-5)
    m = svc.metrics()
    assert m.admitted == 2 and m.rejected == 1
    assert sum(m.rejected_by_rule.values()) >= 1


def test_service_too_long_is_a_structured_rejection(quick_vampire):
    svc = EstimationService(quick_vampire, ServiceConfig(
        ring=RingConfig(length_buckets=(64,), count_buckets=(4,))))
    r = svc.submit(idd_loops.validation_sweep(32))     # 272 > 4 x 64
    assert r.reason == "too-long" and r.rules == ("too-long",)
    assert svc.metrics().rejected_by_rule == {"too-long": 1}


def test_service_shutdown_drain_and_close(quick_vampire):
    trs = _sweeps((1, 8, 16, 64, 4))
    svc = EstimationService(quick_vampire, ServiceConfig(max_batch=2))
    tickets, _ = svc.submit_many(trs)
    assert svc.close() == len(trs)                     # drains every window
    for t in tickets:
        assert np.asarray(svc.result(t).energy_pj).shape == (3,)
    with pytest.raises(RuntimeError):
        svc.submit_many(trs[:1])
    m = svc.metrics()
    assert m.queue_depth == 0 and m.completed == len(trs)
    assert m.dispatches == 3                           # windows of <= 2


def test_service_metrics_snapshot(quick_vampire):
    svc = EstimationService(quick_vampire, ServiceConfig())
    tickets, _ = svc.submit_many(_sweeps((1, 8)))
    assert svc.metrics().queue_depth == 2
    svc.drain()
    m = svc.metrics()
    assert dataclasses.asdict(m)                       # plain-dict friendly
    assert m.dispatched_traces == 2 and m.batch_fill == pytest.approx(0.25)
    assert m.traces_per_s > 0
    assert m.latency_p99_ms >= m.dispatch_p50_ms > 0
    assert m.engine_programs == 1


def test_service_result_before_dispatch_raises(quick_vampire):
    svc = EstimationService(quick_vampire, ServiceConfig())
    t = svc.submit(_sweeps((1,))[0])
    with pytest.raises(KeyError):
        svc.result(t)
    svc.drain()
    svc.result(t)


# ---------------------------------------------------------------------------
# Recompile bound + recalibration hook
# ---------------------------------------------------------------------------
def test_serving_recompile_probe_holds(quick_vampire):
    from repro.analysis import dispatch_audit
    assert dispatch_audit.audit_serving(quick_vampire) == []


def test_treedef_stable_model_update_reuses_programs(quick_vampire):
    trs = _sweeps((1, 8))
    svc = EstimationService(quick_vampire, ServiceConfig())
    t0, _ = svc.submit_many(trs)
    svc.drain()
    before = np.asarray(svc.result(t0[0]).avg_current_ma)
    programs = svc.engine.cache_size()
    bump = lambda x: (x * 1.05 if jnp.issubdtype(x.dtype, jnp.floating)
                      else x)
    svc.engine.update_model(
        jax.tree_util.tree_map(bump, svc.engine.resident))
    t1, _ = svc.submit_many(trs)
    svc.drain()
    after = np.asarray(svc.result(t1[0]).avg_current_ma)
    assert svc.engine.cache_size() == programs         # no recompile
    assert not np.allclose(after, before)              # new params applied


# ---------------------------------------------------------------------------
# Mesh parity: plain dispatch on one device, shard_map on the CI lane
# ---------------------------------------------------------------------------
def test_single_device_mesh_falls_back_bitwise(quick_vampire):
    trs = _sweeps()
    svc_mesh = EstimationService(quick_vampire, ServiceConfig(),
                                 mesh=make_local_mesh(data=1, model=1))
    svc_none = EstimationService(quick_vampire, ServiceConfig())
    assert svc_mesh.engine.n_shards == 1
    tm, _ = svc_mesh.submit_many(trs)
    tn, _ = svc_none.submit_many(trs)
    svc_mesh.drain(), svc_none.drain()
    for a, b in zip(tm, tn):
        np.testing.assert_array_equal(
            np.asarray(svc_mesh.result(a).energy_pj),
            np.asarray(svc_none.result(b).energy_pj))


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs the forced multi-device CPU lane")
def test_shard_map_matches_single_device_bitwise(quick_vampire):
    n_dev = jax.device_count()
    mesh = make_local_mesh(data=n_dev // 2, model=2) if n_dev % 2 == 0 \
        else make_local_mesh(data=n_dev, model=1)
    trs = _sweeps((1, 4, 8, 16, 24, 32, 48, 64))       # 8 % n_shards == 0
    svc_mesh = EstimationService(quick_vampire, ServiceConfig(), mesh=mesh)
    svc_none = EstimationService(quick_vampire, ServiceConfig())
    assert svc_mesh.engine.n_shards == n_dev > 1
    tm, _ = svc_mesh.submit_many(trs)
    tn, _ = svc_none.submit_many(trs)
    svc_mesh.drain(), svc_none.drain()
    for a, b in zip(tm, tn):
        np.testing.assert_array_equal(
            np.asarray(svc_mesh.result(a).energy_pj),
            np.asarray(svc_none.result(b).energy_pj))
    # a window that does not divide the mesh pads, still exact
    t3, _ = svc_mesh.submit_many(trs[:3])
    svc_mesh.drain()
    direct = quick_vampire.estimate(trs[:3])
    np.testing.assert_allclose(
        np.asarray(svc_mesh.result(t3[0]).avg_current_ma),
        np.asarray(direct.avg_current_ma)[0], rtol=1e-5)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs the forced multi-device CPU lane")
def test_fleet_surface_mesh_shards_modules_bitwise(tiny_fleet):
    from repro.core.fleet import fleet_surface_energy
    from repro.core.validate import surface_sweep_trace
    n_dev = jax.device_count()
    n_model = 3 if n_dev % 3 == 0 else 1
    mesh = make_local_mesh(data=n_dev // n_model, model=n_model)
    n_data = mesh.shape["data"]
    tb = bucketed_trace_batch([surface_sweep_trace()] * n_data,
                              n_data, 4096)
    modules = list(tiny_fleet)[:9 - (9 % mesh.shape["model"])]
    sharded = fleet_surface_energy(modules, tb.trace, tb.weight, mesh=mesh)
    plain = fleet_surface_energy(modules, tb.trace, tb.weight)
    np.testing.assert_array_equal(np.asarray(sharded.energy_pj),
                                  np.asarray(plain.energy_pj))


def test_fleet_surface_mesh_fallback_single_device(tiny_fleet):
    from repro.core.fleet import fleet_surface_energy
    from repro.core.validate import surface_sweep_trace
    mesh = make_local_mesh(data=1, model=1)
    tb = bucketed_trace_batch([surface_sweep_trace()], 1, 4096)
    modules = list(tiny_fleet)[:3]
    with_mesh = fleet_surface_energy(modules, tb.trace, tb.weight,
                                     mesh=mesh)
    plain = fleet_surface_energy(modules, tb.trace, tb.weight)
    np.testing.assert_array_equal(np.asarray(with_mesh.energy_pj),
                                  np.asarray(plain.energy_pj))
