"""Compile the main path's kernels and the full-width LM decode step for a
TPU v5e chip that is described, not attached (the TPU compiler ships with
jaxlib).  Nothing runs: these tests catch what the chip's compiler refuses
— block shapes off the (8, 128) tiling, too much VMEM, a program that does
not fit the chip's 16 GB — at no chip time.

Sizes are the serving ring's largest window: 64 traces x 16384 commands
x 3 vendors.  Every autotuner candidate block and both grid layouts must
compile, since the autotuner may pick any of them."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune

T, N, V = 64, 16384, 3
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back here, so keep
    # them out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_feature_kernel_compiles(one_chip):
    from repro.kernels.vampire_energy.vampire_energy import \
        batched_features_pallas
    lines = _shape(one_chip, (T, N, 16), jnp.uint32)
    for block in autotune.CANDIDATE_BLOCKS:
        _compile(lambda d, p, m, b=block: batched_features_pallas(
            d, p, m, block_n=b, interpret=False),
            lines, lines, _shape(one_chip, (T, N)))


@pytest.mark.parametrize("surface", (False, True), ids=("mean", "surface"))
def test_vampire_energy_kernel_compiles(one_chip, surface):
    from repro.kernels.vampire_energy import vampire_energy as ve
    int_planes = ("op", "mode", "bank", "open_bits")
    planes = [_shape(one_chip, (T, N),
                     jnp.int32 if name in int_planes else jnp.float32)
              for name in ve.FEATURE_PLANES]
    for block in autotune.CANDIDATE_BLOCKS:
        for layout in autotune.CANDIDATE_LAYOUTS:
            def run(planes, surf, table, cells, b=block, lay=layout):
                feats = dict(zip(ve.FEATURE_PLANES, planes), surf=surf)
                return ve.batched_energy_pallas(
                    feats, table, block_n=b, interpret=False,
                    cells=cells if surface else None, grid_layout=lay)
            _compile(run, planes, _shape(one_chip, (V, T, N)),
                     _shape(one_chip, (V, 59)),
                     _shape(one_chip, (T, N), jnp.int32))


@pytest.mark.parametrize("kind", ("micron", "drampower"))
@pytest.mark.parametrize("surface", (False, True), ids=("mean", "surface"))
def test_baseline_energy_kernel_compiles(one_chip, kind, surface):
    from repro.core.baselines_power import BASELINE_IDD_KEYS
    from repro.kernels.baseline_energy import baseline_energy as be
    planes = [_shape(one_chip, (T, N)) for _ in be.PLANES]
    for block in autotune.CANDIDATE_BLOCKS:
        for layout in autotune.CANDIDATE_LAYOUTS:
            def run(planes, any_act, table, cells, b=block, lay=layout):
                return be.baseline_energy_pallas(
                    kind, dict(zip(be.PLANES, planes)), any_act, table,
                    block_n=b, interpret=False,
                    cells=cells if surface else None, grid_layout=lay)
            _compile(run, planes, _shape(one_chip, (T,)),
                     _shape(one_chip, (V, len(BASELINE_IDD_KEYS))),
                     _shape(one_chip, (T, N), jnp.int32))


def test_full_width_decode_step_fits_one_chip(one_chip):
    """The LM power report's decode step at qwen2.5-3b's published widths
    (the batch and cache length ``chip_smoke.py`` serves)."""
    from repro.configs import registry
    from repro.models.lm import LM
    from repro.models.meta import abstractify
    lm = LM(registry.get_config("qwen2.5-3b"))
    batch, max_len = 4, 72

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda s: _shape(one_chip, s.shape, s.dtype), tree)

    params = placed(jax.eval_shape(lm.init, jax.random.key(0)))
    caches = placed(abstractify(lm.init_cache_meta(batch, max_len)))
    tok = _shape(one_chip, (batch, 1), jnp.int32)
    compiled = jax.jit(lm.decode_step, donate_argnums=(1,)).lower(
        params, caches, tok).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 1e9 < used < HBM_BYTES, used


def test_lint_count_with_segment_carry_fits_one_chip(one_chip):
    """The lint gate's counting program at the window of a whole 1 M-command
    trace (64 rows of 16384) with its (rows, CARRY_WIDTH) carry plane."""
    from repro.analysis import trace_lint
    lint_count, _ = trace_lint._programs()
    plane = _shape(one_chip, (T, N), jnp.int32)
    compiled = lint_count.lower(
        plane, plane, plane,
        _shape(one_chip, (T, trace_lint.CARRY_WIDTH), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
