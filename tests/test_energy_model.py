"""Unit + property tests for the shared energy integrator."""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_sim, dram, energy_model, idd_loops
from repro.core.energy_model import (trace_energy_scan,
                                     trace_energy_vectorized)

PP = device_sim.true_vendor_params(0)


def _random_trace(rng, n=64):
    cmds, banks, rows, cols, datas, dts = [], [], [], [], [], []
    open_banks = set()
    for _ in range(n):
        r = rng.random()
        if r < 0.2 or not open_banks:
            b = int(rng.integers(0, 8))
            cmds.append(dram.ACT); open_banks.add(b)
        elif r < 0.7:
            b = int(rng.choice(sorted(open_banks)))
            cmds.append(dram.RD if rng.random() < 0.6 else dram.WR)
        elif r < 0.8:
            b = int(rng.choice(sorted(open_banks)))
            cmds.append(dram.PRE); open_banks.discard(b)
        elif r < 0.9:
            b = 0
            cmds.append(dram.NOP)
        else:
            b = 0
            open_banks.clear()
            cmds.append(dram.PREA)
        banks.append(b)
        rows.append(int(rng.integers(0, 1 << 15)))
        cols.append(int(rng.integers(0, 128)))
        datas.append(rng.integers(0, 2 ** 32, size=16, dtype=np.uint32))
        dts.append(int(rng.integers(1, 30)))
    return dram.make_trace(cmds, banks, rows, cols, np.stack(datas), dts)


@pytest.mark.parametrize("seed", range(4))
def test_scan_matches_vectorized_random_traces(seed):
    rng = np.random.default_rng(seed)
    tr = _random_trace(rng, n=96)
    a = trace_energy_scan(tr, PP)
    b = trace_energy_vectorized(tr, PP)
    np.testing.assert_allclose(float(a.avg_current_ma),
                               float(b.avg_current_ma), rtol=1e-5)
    np.testing.assert_allclose(float(a.energy_pj), float(b.energy_pj),
                               rtol=1e-5)


def test_scan_matches_vectorized_on_idd_loops():
    for name, fn in idd_loops.IDD_LOOPS.items():
        tr = fn()
        a = trace_energy_scan(tr, PP)
        b = trace_energy_vectorized(tr, PP)
        np.testing.assert_allclose(float(a.avg_current_ma),
                                   float(b.avg_current_ma), rtol=5e-5,
                                   err_msg=name)


@hypothesis.settings(deadline=None, max_examples=20)
@hypothesis.given(n_ones=st.integers(0, 512))
def test_read_current_increases_with_ones(n_ones):
    tr0, s0 = idd_loops.ones_sweep_point(0, op=dram.RD, reps=16)
    tr1, s1 = idd_loops.ones_sweep_point(n_ones, op=dram.RD, reps=16)
    i0 = float(trace_energy_vectorized(tr0, PP).avg_current_ma)
    i1 = float(trace_energy_vectorized(tr1, PP).avg_current_ma)
    assert i1 >= i0 - 1e-3  # monotone non-decreasing in ones (reads)


@hypothesis.settings(deadline=None, max_examples=20)
@hypothesis.given(n_ones=st.integers(0, 512))
def test_write_current_decreases_with_ones(n_ones):
    tr0, _ = idd_loops.ones_sweep_point(0, op=dram.WR, reps=16)
    tr1, _ = idd_loops.ones_sweep_point(n_ones, op=dram.WR, reps=16)
    i0 = float(trace_energy_vectorized(tr0, PP).avg_current_ma)
    i1 = float(trace_energy_vectorized(tr1, PP).avg_current_ma)
    assert i1 <= i0 + 1e-3


def test_power_down_reduces_idle_current():
    idle = float(trace_energy_vectorized(idd_loops.idd2n(), PP)
                 .avg_current_ma)
    pd = float(trace_energy_vectorized(idd_loops.idd2p1(), PP)
               .avg_current_ma)
    assert pd < idle


def test_energy_scales_with_trace_repetition():
    tr = idd_loops.idd0(reps=8)
    tr2 = dram.tile_trace(tr, 2)
    e1 = float(trace_energy_vectorized(tr, PP).energy_pj)
    e2 = float(trace_energy_vectorized(tr2, PP).energy_pj)
    np.testing.assert_allclose(e2, 2 * e1, rtol=1e-4)


def test_bank_structural_factors_visible_in_read_current():
    ppc = device_sim.true_vendor_params(2)  # vendor C
    tr0, s = idd_loops.bank_read_probe(0)
    tr5, _ = idd_loops.bank_read_probe(5)
    i0 = float(trace_energy_vectorized(tr0, ppc).avg_current_ma)
    i5 = float(trace_energy_vectorized(tr5, ppc).avg_current_ma)
    expected = float(ppc.bank_read_factor[5])
    assert abs(i5 / i0 - expected) < 0.05


# ---------------------------------------------------------------------------
# structural_state: packed keys against the gather formulation
# ---------------------------------------------------------------------------
#: past ``gather_free``'s gate: the gather formulation runs
PAST_GATE = 1 << 15
_EXTREMES = np.array([0xFFFFFFFF, 0x80000000], np.uint32)


def _fields_trace(rng, n, t=None):
    """Every field random: all command codes, banks mostly in range and
    some past either end, columns repeating or anywhere in int32, data
    words with the sign bit and all ones."""
    lead = (n,) if t is None else (t, n)
    bank = rng.integers(0, dram.N_BANKS, lead)
    odd = rng.random(lead) < 0.05
    bank[odd] = rng.choice([-1, -8, -9, 8, 2 ** 31 - 1, -2 ** 31], odd.sum())
    col = rng.integers(-2 ** 31, 2 ** 31, lead)
    near = rng.random(lead) < 0.6
    col[near] = rng.choice([0, 1, 2 ** 31 - 1], near.sum())
    data = rng.integers(0, 2 ** 32, lead + (16,), dtype=np.uint64)
    pick = rng.random(lead + (16,))
    data = np.where(pick < 0.3, _EXTREMES[(pick < 0.15).astype(int)], data)
    return dram.CommandTrace(
        jnp.asarray(rng.integers(0, dram.SRX + 1, lead), jnp.int32),
        jnp.asarray(bank, jnp.int32),
        jnp.asarray(rng.integers(0, 1 << 15, lead), jnp.int32),
        jnp.asarray(col, jnp.int32), jnp.asarray(data, jnp.uint32),
        jnp.asarray(rng.integers(1, 30, lead), jnp.int32))


def _random_carry(rng, rows=None):
    """Banks open, a power-down or self-refresh entry, a previous RD/WR
    with extreme words, columns unset (-1), negative or set to 2^31-1."""
    lead = () if rows is None else (rows,)
    data = rng.integers(0, 2 ** 32, lead + (16,), dtype=np.uint64)
    data[..., :2] = _EXTREMES
    last_col = rng.choice([-1, -5, 0, 3, 2 ** 31 - 1], lead + (8,))
    return energy_model.SegmentCarry(
        open=rng.random(lead + (8,)) < 0.5,
        bg_mode=rng.choice([energy_model.BG_ACTIVE, energy_model.BG_PDN_FAST,
                            energy_model.BG_PDN_SLOW, energy_model.BG_SR],
                           lead).astype(np.int32),
        has_prev=np.ones(lead, np.bool_),
        prev_data=data.astype(np.uint32),
        prev_bank=rng.choice([3, 9, -2 ** 31], lead).astype(np.int32),
        last_col=last_col.astype(np.int32),
        any_act=np.ones(lead, np.bool_))


def _gathered(monkeypatch, fn, *args):
    """``fn(*args)`` with ``structural_state`` on its gather formulation
    (compiled afresh, and the packed programs compiled again after)."""
    with monkeypatch.context() as m:
        m.setattr(energy_model, "gather_free", lambda n: False)
        jax.clear_caches()
        out = jax.block_until_ready(fn(*args))
    jax.clear_caches()
    return out


def _assert_bitwise(a, b):
    for x, y, path in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b),
                          jax.tree_util.tree_flatten_with_path(a)[0]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(path[0]))


@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("n", [1, 1024, 16384, PAST_GATE - 2, PAST_GATE - 1])
def test_structural_state_packed_equals_gathers_bitwise(n, carried,
                                                        monkeypatch):
    """Every field of the packed path equals the gather formulation's, for
    any int32 bank and column, any data word and any carry; the gate
    takes the packed path up to 2^15 - 2 commands and gathers past it."""
    rng = np.random.default_rng(n + carried)
    tr = _fields_trace(rng, n)
    carry = _random_carry(rng) if carried else None
    assert energy_model.gather_free(n) == (n < PAST_GATE - 1)
    got = energy_model.structural_state(tr, carry)
    want = _gathered(monkeypatch, energy_model.structural_state, tr, carry)
    _assert_bitwise(got, want)
    if n > 1:   # the draws reach every interleave mode
        assert set(np.unique(np.asarray(got.il_mode))) == set(range(4))


def _batched_shapes(t, n):
    i = jax.ShapeDtypeStruct((t, n), jnp.int32)
    trace = dram.CommandTrace(i, i, i, i,
                              jax.ShapeDtypeStruct((t, n, 16), jnp.uint32), i)
    carry = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        energy_model.empty_carry(t))
    return trace, carry


@pytest.mark.parametrize("n,gathers", [(4096, False), (16384, False),
                                       (PAST_GATE, True)])
def test_structural_state_lowers_without_gathers(n, gathers):
    """At the fleet's and the ring's row lengths the batched bookkeeping
    has no gather; past the gate it keeps them."""
    text = jax.jit(jax.vmap(energy_model.structural_state)).lower(
        *_batched_shapes(4, n)).as_text()
    assert ("stablehlo.gather" in text) == gathers


@pytest.mark.parametrize("impl", ["vectorized", "pallas"])
def test_served_answers_unchanged_at_the_ring_row_length(impl, monkeypatch):
    """The served estimate of a (4, 16384) carried batch is bitwise the
    gather formulation's, through either impl."""
    from repro.core import estimate_batch, fleet
    rng = np.random.default_rng(16384)
    tr = _fields_trace(rng, 16384, t=4)
    tr = tr._replace(bank=jnp.clip(tr.bank, 0, dram.N_BANKS - 1))
    carry = jax.tree_util.tree_map(jnp.asarray, _random_carry(rng, rows=4))
    weight = jnp.asarray(rng.random((4, 16384)) < 0.9, jnp.float32)
    stacked = fleet.stack_params([device_sim.true_vendor_params(v)
                                  for v in range(3)])
    fn = (estimate_batch.batched_reports if impl == "vectorized"
          else estimate_batch.pallas_batched_reports)
    got = jax.block_until_ready(fn(tr, weight, stacked, carry))
    _assert_bitwise(got, _gathered(monkeypatch, fn, tr, weight, stacked,
                                   carry))
