"""Fleet-scale estimation: synthetic fleets, the chunked surface dispatch,
the zero-restack stacked-params cache, the kernel autotuner registry, and
the module-axis shard_map twin (multi-device lane)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_sim, estimate_batch, fleet, idd_loops
from repro.core.dram import batch_traces


def _surface_batch():
    return batch_traces([(idd_loops.validation_sweep(8, reps=3), 2),
                         (idd_loops.validation_sweep(16, reps=2), 2)])


# ---------------------------------------------------------------------------
# synthetic fleets
# ---------------------------------------------------------------------------
def test_synth_fleet_shapes_and_vendor_cycle():
    vendors, pp = device_sim.synth_fleet_params(9)
    assert vendors.shape == (9,)
    np.testing.assert_array_equal(vendors, np.arange(9) % 3)
    for leaf in jax.tree_util.tree_leaves(pp):
        assert leaf.shape[0] == 9


def test_synth_fleet_seed_stable_prefix():
    """A smaller fleet is a PREFIX of a larger one: module identity (not
    fleet size) seeds each module's process variation."""
    _, small = device_sim.synth_fleet_params(16)
    _, big = device_sim.synth_fleet_params(64)
    for a, b in zip(jax.tree_util.tree_leaves(small),
                    jax.tree_util.tree_leaves(big)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:16])


def test_synth_fleet_vendor_consistent():
    """Modules of one vendor vary around that vendor's true params — the
    log-space factors are mean-preserving, so a large fleet's per-vendor
    median lands near the vendor center, and vendor identity (not module
    id) sets the center."""
    vendors, pp = device_sim.synth_fleet_params(300)
    base = [device_sim.true_vendor_params(v) for v in range(3)]
    for v in range(3):
        i2n_v = np.asarray(pp.i2n)[vendors == v]
        center = float(np.asarray(base[v].i2n))
        med = float(np.median(i2n_v))
        assert abs(np.log(med / center)) < 0.5
        assert np.all(i2n_v > 0)


def test_synth_fleet_explicit_ids_match_default():
    v_d, pp_d = device_sim.synth_fleet_params(6)
    v_e, pp_e = device_sim.synth_fleet_params(
        vendors=np.arange(6) % 3, module_ids=np.arange(6))
    np.testing.assert_array_equal(v_d, v_e)
    for a, b in zip(jax.tree_util.tree_leaves(pp_d),
                    jax.tree_util.tree_leaves(pp_e)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# chunked surface dispatch
# ---------------------------------------------------------------------------
def test_chunked_vs_oneshot_parity_1k_modules():
    """The acceptance bar: a >=1k-module synthetic fleet's chunked surface
    equals the one-shot dispatch on EVERY report leaf."""
    trace, weight = _surface_batch()
    _, pp = device_sim.synth_fleet_params(1000)
    one = estimate_batch.batched_surface_reports(trace, weight, pp)
    ch = estimate_batch.chunked_surface_reports(trace, weight, pp,
                                                module_chunk=256)
    for f in one._fields:
        np.testing.assert_allclose(np.asarray(getattr(one, f)),
                                   np.asarray(getattr(ch, f)))


def test_chunked_parity_is_bitwise_across_chunkings():
    """Stronger than allclose: the one-shot and every chunking (module
    and trace chunks, dividing or not) run the SAME charge program, so
    results are bitwise identical."""
    trace, weight = _surface_batch()
    _, pp = device_sim.synth_fleet_params(23)      # prime: nothing divides
    one = estimate_batch.batched_surface_reports(trace, weight, pp)
    for mc, tc in ((23, None), (8, None), (5, 1), (7, 2)):
        ch = estimate_batch.chunked_surface_reports(
            trace, weight, pp, module_chunk=mc, trace_chunk=tc)
        for f in one._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(one, f)), np.asarray(getattr(ch, f)),
                err_msg=f"leaf {f} chunking ({mc}, {tc})")


def test_chunked_pallas_matches_oneshot_pallas():
    trace, weight = _surface_batch()
    _, pp = device_sim.synth_fleet_params(10)
    one = estimate_batch.pallas_batched_surface_reports(trace, weight, pp)
    ch = estimate_batch.chunked_surface_reports(trace, weight, pp,
                                                module_chunk=4,
                                                impl="pallas")
    for f in one._fields:
        np.testing.assert_array_equal(np.asarray(getattr(one, f)),
                                      np.asarray(getattr(ch, f)))


def test_chunked_vendor_subset_slice():
    """Slicing one vendor's modules out of the chunked fleet surface
    equals running that subset alone (chunk-size invariance again, from
    the consumer's side)."""
    trace, weight = _surface_batch()
    vendors, pp = device_sim.synth_fleet_params(12)
    full = estimate_batch.chunked_surface_reports(trace, weight, pp,
                                                  module_chunk=5)
    idx = np.flatnonzero(vendors == 1)
    sub_pp = jax.tree_util.tree_map(lambda x: x[idx], pp)
    sub = estimate_batch.chunked_surface_reports(trace, weight, sub_pp,
                                                 module_chunk=3)
    np.testing.assert_array_equal(np.asarray(full.energy_pj)[:, idx],
                                  np.asarray(sub.energy_pj))


def test_chunked_pad_rows_contribute_zero():
    """Trace padding added by the chunked dispatch is zero-weight: a
    trace_chunk that forces pad rows changes nothing, and the pad region
    never leaks into the sliced-off result (checked via a chunking whose
    pad row count differs)."""
    trace, weight = _surface_batch()
    _, pp = device_sim.synth_fleet_params(6)
    no_pad = estimate_batch.chunked_surface_reports(
        trace, weight, pp, module_chunk=6, trace_chunk=2)   # 2 % 2 == 0
    padded = estimate_batch.chunked_surface_reports(
        trace, weight, pp, module_chunk=4, trace_chunk=3)   # pads t and m
    for f in no_pad._fields:
        np.testing.assert_array_equal(np.asarray(getattr(no_pad, f)),
                                      np.asarray(getattr(padded, f)))
    assert np.asarray(no_pad.energy_pj).shape[:2] == (2, 6)


def test_chunked_charge_program_count_fixed_across_fleet_sizes():
    """The scaling contract the dispatch auditor gates: growing the fleet
    at a fixed chunk size must NOT grow the chunk charge program's jit
    cache (program count depends on chunk size, never chunk count)."""
    trace, weight = _surface_batch()
    _, small = device_sim.synth_fleet_params(8)
    _, big = device_sim.synth_fleet_params(32)
    estimate_batch.chunked_surface_reports(trace, weight, small,
                                           module_chunk=4)
    base = estimate_batch._surface_chunk_charge._cache_size()
    estimate_batch.chunked_surface_reports(trace, weight, big,
                                           module_chunk=4)
    assert estimate_batch._surface_chunk_charge._cache_size() == base


# ---------------------------------------------------------------------------
# zero-restack dispatch (the memoized stacked-fleet artifact)
# ---------------------------------------------------------------------------
def test_run_probes_stacks_once_across_calls(tiny_fleet, monkeypatch):
    """The PR 3-style regression: two run_probes calls and a surface map
    over the same fleet perform ONE stack_params, and the jitted
    measurement's program count stays flat."""
    points = [fleet.ProbePoint(("p", n),
                               idd_loops.validation_sweep(n, reps=2), 2,
                               900 + n)
              for n in (4, 8)]
    modules = list(tiny_fleet)
    fleet.FLEET_STACK_CACHE.clear()
    calls = {"n": 0}
    real = fleet.stack_params

    def counting(params):
        calls["n"] += 1
        return real(params)

    monkeypatch.setattr(fleet, "stack_params", counting)
    first = fleet.run_probes(modules, points)
    programs = fleet.fleet_measure_current._cache_size()
    second = fleet.run_probes(modules, points)
    trace, weight = _surface_batch()
    fleet.fleet_surface_energy(modules, trace, weight)
    assert calls["n"] == 1
    assert fleet.fleet_measure_current._cache_size() == programs
    np.testing.assert_array_equal(first, second)
    assert len(fleet.FLEET_STACK_CACHE._entries) == 1   # one entry served all


def test_fleet_stack_cache_identity_keyed_and_bounded(tiny_fleet):
    fleet.FLEET_STACK_CACHE.clear()
    mods = list(tiny_fleet)
    s1 = fleet.fleet_stacked(mods)
    s2 = fleet.fleet_stacked(mods)
    assert s1 is s2                      # memoized, not rebuilt
    sub = fleet.fleet_stacked(mods[:4])  # different fleet -> different entry
    assert sub.i2n.shape[0] == 4
    assert len(fleet.FLEET_STACK_CACHE._entries) == 2
    for i in range(fleet.FLEET_STACK_CACHE.maxsize + 1):
        fleet.fleet_stacked(mods[: 2 + i % 3])
    assert (len(fleet.FLEET_STACK_CACHE._entries)
            <= fleet.FLEET_STACK_CACHE.maxsize)


def test_fleet_stacked_passthrough_for_stacked_params():
    _, pp = device_sim.synth_fleet_params(5)
    assert fleet.fleet_stacked(pp) is pp


def test_stack_params_vectorized_matches_tree_stack(tiny_fleet):
    params = [m.params for m in tiny_fleet]
    fast = fleet.stack_params(params)
    slow = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *params)
    for a, b in zip(jax.tree_util.tree_leaves(fast),
                    jax.tree_util.tree_leaves(slow)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunked_and_mesh_are_mutually_exclusive(tiny_fleet):
    from repro.launch.mesh import make_local_mesh
    trace, weight = _surface_batch()
    with pytest.raises(ValueError, match="mutually exclusive"):
        fleet.fleet_surface_energy(list(tiny_fleet), trace, weight,
                                   mesh=make_local_mesh(data=1, model=1),
                                   module_chunk=3)


# ---------------------------------------------------------------------------
# module-axis shard_map (multi-device lane)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs the forced multi-device CPU lane")
def test_sharded_fleet_surface_bitwise_with_synth_fleet():
    from repro.launch.mesh import make_local_mesh
    n_dev = jax.device_count()
    n_model = 4 if n_dev % 4 == 0 else 2
    mesh = make_local_mesh(data=n_dev // n_model, model=n_model)
    trace, weight = _surface_batch()
    _, pp = device_sim.synth_fleet_params(4 * n_model)
    plain = fleet.fleet_surface_energy(pp, trace, weight)
    sharded = fleet.fleet_surface_energy(pp, trace, weight, mesh=mesh)
    for f in plain._fields:
        np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                      np.asarray(getattr(sharded, f)),
                                      err_msg=f"leaf {f}")


@pytest.mark.parametrize("shape,split", (
    (None, (1, 1)),
    ({"data": 2, "model": 1}, (2, 1)),
    ({"data": 1, "model": 4, "pipe": 1}, (1, 4)),
    ({"data": 1, "model": 2, "pipe": 2}, ValueError),
))
def test_mesh_split_uses_every_device_or_raises(shape, split):
    """A multi-device axis the fleet dispatch does not shard over would
    leave its devices idle: that raises instead of dispatching."""
    from types import SimpleNamespace
    mesh = None if shape is None else SimpleNamespace(shape=shape)
    if split is ValueError:
        with pytest.raises(ValueError, match="not \\(data, model\\)"):
            fleet.mesh_split(mesh)
    else:
        assert fleet.mesh_split(mesh) == split


def test_pad_rows_appends_zero_weight_copies():
    trace, weight = _surface_batch()
    n = trace.cmd.shape[0]
    trace_p, weight_p = fleet.pad_rows(trace, weight, 3)
    assert trace_p.cmd.shape[0] == weight_p.shape[0] == 3 * -(-n // 3)
    np.testing.assert_array_equal(np.asarray(weight_p[n:]), 0)
    np.testing.assert_array_equal(np.asarray(trace_p.cmd[n:]),
                                  np.asarray(trace.cmd[:1]).repeat(
                                      trace_p.cmd.shape[0] - n, axis=0))
    same = fleet.pad_rows(trace, weight, n)
    assert same[0] is trace and same[1] is weight


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs the forced multi-device CPU lane")
def test_sharded_fleet_surface_pads_non_dividing_fleet():
    """A fleet that does not divide the model axis pads (module 0
    replicated, sliced off) instead of dispatching to one device."""
    from repro.launch.mesh import make_local_mesh
    n_dev = jax.device_count()
    mesh = make_local_mesh(data=1, model=n_dev)
    trace, weight = _surface_batch()
    _, pp = device_sim.synth_fleet_params(2 * n_dev + 1)
    plain = fleet.fleet_surface_energy(pp, trace, weight)
    sharded = fleet.fleet_surface_energy(pp, trace, weight, mesh=mesh)
    for f in plain._fields:
        np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                      np.asarray(getattr(sharded, f)),
                                      err_msg=f"leaf {f}")


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs the forced multi-device CPU lane")
def test_sharded_run_probes_bitwise(tiny_fleet):
    from repro.launch.mesh import make_local_mesh
    n_dev = jax.device_count()
    n_model = 3 if n_dev % 3 == 0 else (4 if n_dev % 4 == 0 else 1)
    mesh = make_local_mesh(data=n_dev // n_model, model=n_model)
    modules = list(tiny_fleet)[:9 - (9 % n_model)]
    points = [fleet.ProbePoint(("s", n),
                               idd_loops.validation_sweep(n, reps=2), 2,
                               700 + n)
              for n in range(4, 4 + 2 * mesh.shape["data"])]
    fleet.FLEET_STACK_CACHE.clear()
    plain = fleet.run_probes(modules, points)
    sharded = fleet.run_probes(modules, points, mesh=mesh)
    np.testing.assert_array_equal(plain, sharded)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs the forced multi-device CPU lane")
def test_fleet_stacked_lands_module_sharded(tiny_fleet):
    from repro.launch.mesh import make_local_mesh
    n_dev = jax.device_count()
    n_model = 3 if n_dev % 3 == 0 else 2
    mesh = make_local_mesh(data=n_dev // n_model, model=n_model)
    fleet.FLEET_STACK_CACHE.clear()
    mods = list(tiny_fleet)[:9 - (9 % n_model)]
    stacked = fleet.fleet_stacked(mods, mesh)
    spec = stacked.i2n.sharding.spec
    assert tuple(spec)[:1] == ("model",)


# ---------------------------------------------------------------------------
# autotuner registry
# ---------------------------------------------------------------------------
def test_autotune_shape_bucket_powers_of_two():
    from repro.kernels import autotune
    assert autotune.shape_bucket(8, 1024) == "t8n1024"
    assert autotune.shape_bucket(9, 1025) == "t16n2048"
    assert autotune.shape_bucket(1, 1) == "t1n1"


def test_autotune_best_config_defaults_when_untuned():
    from repro.kernels import autotune
    cfg = autotune.best_config("vampire_energy", 7, 131072)  # absurd bucket
    assert cfg == {"block_n": autotune.DEFAULT_BLOCK,
                   "layout": autotune.DEFAULT_LAYOUT}


def test_autotune_env_kill_switch(monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    cfg = autotune.best_config("vampire_energy", 8, 1024)
    assert cfg == {"block_n": autotune.DEFAULT_BLOCK,
                   "layout": autotune.DEFAULT_LAYOUT}


def test_autotune_table_roundtrip(tmp_path, monkeypatch):
    from repro.kernels import autotune
    path = tmp_path / "table.json"
    monkeypatch.setattr(autotune, "TABLE_PATH", path)
    autotune.update_table("vampire_energy", {
        "t8n1024": {"block_n": 256, "layout": "tvi", "us": 12.0}},
        path=path)
    try:
        table = json.loads(path.read_text())
        key = autotune.backend_key()
        assert table[key]["vampire_energy"]["t8n1024"] == {
            "block_n": 256, "layout": "tvi"}      # winners only, no timings
        cfg = autotune.best_config("vampire_energy", 8, 1024)
        assert cfg == {"block_n": 256, "layout": "tvi"}
    finally:
        autotune.reload_table()


def test_committed_autotune_table_is_valid():
    """The committed table parses and every entry is a sane launch
    config."""
    from repro.kernels import autotune
    assert os.path.exists(autotune.TABLE_PATH)
    with open(autotune.TABLE_PATH) as f:
        table = json.load(f)
    for backend, families in table.items():
        for family, buckets in families.items():
            assert family in autotune.FAMILIES
            for bucket, entry in buckets.items():
                assert bucket == autotune.shape_bucket(
                    int(bucket[1:bucket.index("n")]),
                    int(bucket[bucket.index("n") + 1:]))
                assert entry["block_n"] in autotune.CANDIDATE_BLOCKS
                assert entry["layout"] in autotune.CANDIDATE_LAYOUTS


def test_grid_layouts_agree_bitwise():
    """Both grid-major orders compute the same charge matrix — layout is
    a pure scheduling choice."""
    from repro.core.fleet import stack_params
    from repro.kernels.vampire_energy import ops as vops
    trace, weight = _surface_batch()
    stacked = stack_params([device_sim.true_vendor_params(v)
                            for v in range(3)])
    out = {}
    for layout in ("vti", "tvi"):
        charge, cycles = vops.batched_charge_matrix(
            trace, weight, stacked, grid_layout=layout)
        out[layout] = (np.asarray(charge), np.asarray(cycles))
    np.testing.assert_allclose(out["vti"][0], out["tvi"][0], rtol=1e-6)
    np.testing.assert_array_equal(out["vti"][1], out["tvi"][1])


def test_dispatch_consults_autotune_table(monkeypatch, tmp_path):
    """An entry in the table steers the jitted dispatch: pinning a
    different block size via the table lands a new program in the jit
    cache keyed on that block."""
    from repro.core.fleet import stack_params
    from repro.kernels import autotune
    from repro.kernels.vampire_energy import ops as vops
    trace, weight = _surface_batch()
    stacked = stack_params([device_sim.true_vendor_params(v)
                            for v in range(3)])
    bucket = autotune.shape_bucket(trace.cmd.shape[0], trace.cmd.shape[1])
    path = tmp_path / "table.json"
    monkeypatch.setattr(autotune, "TABLE_PATH", path)
    autotune.reload_table()
    try:
        default = vops.batched_charge_matrix(trace, weight, stacked)
        autotune.update_table("vampire_energy", {
            bucket: {"block_n": 128, "layout": "tvi"}}, path=path)
        assert autotune.best_config(
            "vampire_energy", trace.cmd.shape[0],
            trace.cmd.shape[1]) == {"block_n": 128, "layout": "tvi"}
        tuned = vops.batched_charge_matrix(trace, weight, stacked)
        np.testing.assert_allclose(np.asarray(default[0]),
                                   np.asarray(tuned[0]), rtol=1e-6)
    finally:
        autotune.reload_table()


# ---------------------------------------------------------------------------
# the fleet-chunked dispatch auditor probe
# ---------------------------------------------------------------------------
def test_audit_fleet_chunked_clean():
    from repro.analysis import dispatch_audit
    assert dispatch_audit.audit_fleet_chunked() == []
