"""Compile the valuation path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax, and it compiles for a chip that is
described (`topologies.get_topology_desc`) rather than attached. That
refuses what interpret mode cannot see: kernels Mosaic cannot lower, block
layouts it rejects, and programs that do not fit the chip's memory. Every
test here compiles at the real widths `chip_smoke.py` runs (d=768, k=5,
test_batch=256) and checks that the program fits in one v5e chip's HBM.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, so describing it while
pytest collects would break every other worker.
"""

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

import repro  # noqa: F401  (registers the Pallas fills)
from repro.kernels import autotune
from repro.kernels.sti_pipeline import (
    make_fused_step,
    make_sharded_step,
    resolve_distance,
)

# one v5e chip's HBM as the compiler budgets it
HBM_BYTES = int(15.75 * 2**30)
D, K, TB = 768, 5, 256
N_ONE_CHIP = 32768      # chip_smoke.py phase A
N_FOUR_CHIPS = 65536    # chip_smoke.py --four-chips (the paper cell)
N_DISTANCE = N_ONE_CHIP


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 host, with the persistent compile cache off
    (a compile for a described chip cannot be read back without one)."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 -- no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    import numpy as np

    return Mesh(np.asarray(topo.devices[:4]), ("shards",))


@pytest.fixture
def empty_autotune_cache(tmp_path, monkeypatch):
    """Resolve with no autotune cache, as a fresh checkout would."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "none.json"))


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tpu_distance(t: int, n: int):
    """The distance stage `distance="auto"` resolves to on a TPU with no
    autotune cache, with Pallas forced out of interpret mode (this process
    runs on the CPU backend, which would otherwise interpret it)."""
    name, params = autotune.best_distance(t, n, D, backend="tpu")
    if name.startswith("pallas"):
        params = dict(params, interpret=False)
    return resolve_distance(name, t, n, D, distance_params=params)


def test_tpu_defaults_resolve_to_compilable_code(empty_autotune_cache):
    """With no autotune cache, a TPU resolves both fills to the chunked XLA
    scan (Mosaic refuses the Pallas fill) and the distance to the repaired
    Pallas kernel."""
    assert autotune.default_fill("tpu") == ("chunked", {"chunk": 1})
    assert autotune.default_rect_fill("tpu") == ("chunked", {"chunk": 1})
    assert autotune.best_fill(N_ONE_CHIP, TB, backend="tpu")[0] == "chunked"
    assert autotune.best_distance(TB, N_ONE_CHIP, D, backend="tpu") == (
        "pallas", {})


def test_distance_pallas_compiles_at_real_width(one_chip):
    from repro.kernels.distance import distance_pallas

    compiled = jax.jit(
        lambda a, b: distance_pallas(a, b, interpret=False)
    ).lower(
        _sds((TB, D), jnp.float32, one_chip),
        _sds((N_DISTANCE, D), jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _peak_bytes(compiled) <= HBM_BYTES


def test_default_fused_step_compiles_on_one_chip(one_chip,
                                                 empty_autotune_cache):
    """The donated sti step `fill="auto"`, `distance="auto"` resolve to on
    a TPU, at chip_smoke.py's phase A size, fits one chip."""
    n = N_ONE_CHIP
    fill, fill_params = autotune.default_fill("tpu")
    dist, dist_static = _tpu_distance(TB, n)
    step = make_fused_step(K, "sti", fill, tuple(sorted(fill_params.items())),
                           dist, dist_static, donate=True)
    compiled = step.lower(
        _sds((n, n), jnp.float32, one_chip),
        _sds((n,), jnp.float32, one_chip),
        _sds((TB, D), jnp.float32, one_chip),
        _sds((TB,), jnp.int32, one_chip),
        _sds((TB,), jnp.float32, one_chip),
        _sds((n, D), jnp.float32, one_chip),
        _sds((n,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas distance
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n * n * 4     # acc updated in place
    assert _peak_bytes(compiled) <= HBM_BYTES


def test_default_sharded_step_compiles_on_four_chips(four_chips,
                                                     empty_autotune_cache):
    """The sharded sti step over 4 described chips, at chip_smoke.py's
    --four-chips size: each chip holds an (n/4, n) row block and fits."""
    n, num = N_FOUR_CHIPS, 4
    fill, fill_params = autotune.default_rect_fill("tpu")
    dist, dist_static = _tpu_distance(TB // num, n)
    step = make_sharded_step(four_chips, K, "sti", fill,
                             tuple(sorted(fill_params.items())), dist,
                             dist_static, axis="shards", donate=True)

    def on(*spec):
        return NamedSharding(four_chips, P(*spec))

    compiled = step.lower(
        _sds((n, n), jnp.float32, on("shards", None)),
        _sds((n,), jnp.float32, on("shards")),
        _sds((TB, D), jnp.float32, on("shards", None)),
        _sds((TB,), jnp.int32, on("shards")),
        _sds((TB,), jnp.float32, on("shards")),
        _sds((n, D), jnp.float32, on()),
        _sds((n,), jnp.int32, on()),
    ).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    assert _peak_bytes(compiled) <= HBM_BYTES


@pytest.mark.parametrize("kernel, construct", [
    ("pallas_fill", "dynamic_slice"),
    ("megakernel", "sort"),
])
def test_refused_kernels_raise_naming_the_construct(one_chip, kernel,
                                                    construct):
    """An explicit Pallas fill or megakernel that Mosaic cannot lower
    raises an error naming what it refused; nothing swaps in another
    implementation. (When a later change makes one lower, this test moves
    to the compiling side.)"""
    n, tb = 1024, 16
    if kernel == "pallas_fill":
        from repro.kernels.sti_fill import sti_fill_acc_pallas

        fn = jax.jit(lambda a, g, r: sti_fill_acc_pallas(
            a, g, r, interpret=False))
        args = (_sds((n, n), jnp.float32, one_chip),
                _sds((tb, n), jnp.float32, one_chip),
                _sds((tb, n), jnp.int32, one_chip))
    else:
        step = make_fused_step(K, "sti", "megakernel",
                               (("interpret", False),), donate=True)
        fn = step
        args = (_sds((n, n), jnp.float32, one_chip),
                _sds((n,), jnp.float32, one_chip),
                _sds((tb, D), jnp.float32, one_chip),
                _sds((tb,), jnp.int32, one_chip),
                _sds((tb,), jnp.float32, one_chip),
                _sds((n, D), jnp.float32, one_chip),
                _sds((n,), jnp.int32, one_chip))
    with pytest.raises(Exception, match=construct):
        fn.lower(*args).compile()
