"""Compile the main path's device programs for a described TPU v5e chip at
adult's published shape (32,560 x 123 after the 10-fold truncation), and
MIR's seeding at mnist's (60,000 x 780), with Pallas compiled
(``interpret=False``). Nothing runs: the TPU compiler
refuses here what the chip would refuse (f64 Pallas operands, int64 index
maps, blocks over VMEM, programs over HBM), at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import seeding
from repro.kernels.rbf import rbf_kernel_matrix
from repro.kernels.smo_step import (compiled_blocks, feature_major_shape,
                                     fused_smo_step)
from repro.svm.engine import (DenseKernel, EngineState, PallasRBF,
                              SMOResult, chunk_batched_jit, chunk_jit)

N, D = 32560, 123
#: the feature-major X that PallasRBF holds at adult's shape
DT, NT = feature_major_shape(N, D)
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library reads this once, when it loads: without it the
    # compiler writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described chip, with the persistent
    compilation cache off (a cache entry compiled for a described chip
    cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    return compiled


def _state(spec):
    return EngineState(spec((N,), jnp.float64), spec((N,), jnp.float64),
                       spec((), jnp.int64), spec((), bool))


def _compile_step(spec, n, d):
    """The launch over the feature-major X the source holds, at (n, d)."""
    compiled = _compile(
        lambda f, X, Xt, xij, sq: fused_smo_step(
            f, X, xij, sq, 0.37, gamma=0.5, interpret=False, Xt=Xt),
        spec((n,), jnp.float64), spec((n, d), jnp.float32),
        spec(feature_major_shape(n, d), jnp.float32),
        spec((2, d), jnp.float32), spec((n,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()
    _assert_x_not_rebuilt(compiled.as_text(), n, d)


def test_fused_smo_step_compiles_for_v5e(spec):
    """adult: the whole feature axis, 123 rounded up to 128, in one step,
    over 8 lane blocks of 4,096 (32,768 lanes, 0.6% over n), with no
    per-call copy of X."""
    assert compiled_blocks(N, D) == (4096, 128)
    assert (DT, NT) == (128, 32768)
    _compile_step(spec, N, D)


def test_fused_smo_step_compiles_for_v5e_at_mnist(spec):
    """mnist, the widest X: 780 features, 784 in one step, over 59 lane
    blocks of 1,024 (60,416 lanes), with no per-call copy of X."""
    assert compiled_blocks(60000, 780) == (1024, 784)
    assert feature_major_shape(60000, 780) == (784, 60416)
    _compile_step(spec, 60000, 780)


def test_rbf_kernel_matrix_compiles_for_v5e(spec):
    compiled = _compile(
        lambda a, b: rbf_kernel_matrix(a, b, 0.5, interpret=False),
        spec((N, D), jnp.float32), spec((N, D), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_chunk_compiles_for_v5e(spec):
    """K float32 (4.2 GB), y/alpha/f float64: the precision policy's dense
    program fits a 16 GB chip."""
    _compile(lambda K, y, mask, st: chunk_jit(
        DenseKernel(K), y, mask, 100.0, 1e-3, jnp.asarray(10**6, jnp.int64),
        st, n_iters=4096, wss="2"),
        spec((N, N), jnp.float32), spec((N,), jnp.float64),
        spec((N,), bool), _state(spec))


def _assert_lane_dense(text):
    """The kernel is in the program, and its row norms and row pair travel
    lane-dense: no (n, 1) or (n, 2) array, padded to 128 lanes in HBM
    (16.7 MB at adult's n), is built or split in the SMO loop."""
    assert "tpu_custom_call" in text
    assert re.search(rf"f32\[(\d+,)?{N},[12]\]", text) is None


def _assert_one_pass_over_x(text):
    """The kernel's is the loop's only pass over X: the WSS pair's two rows
    are read by index, with no one-hot product of an (n,)-long indicator
    with X (a ``convolution`` of f32[2,n] by X, a second 16 MB stream per
    SMO iteration at adult's n)."""
    assert "convolution" not in text


def _assert_x_not_rebuilt(text, n=N, d=D):
    """The kernel reads the feature-major X it is given as it is: no
    array of X's size, in either layout, is transposed, padded, copied,
    broadcast or built by a fusion in the program (a transpose and pad of
    X would be a 16.8 MB copy per SMO iteration at adult's n)."""
    dt, nt = feature_major_shape(n, d)
    dims = "|".join(f"{a},{b}" for a, b in ((n, d), (d, n), (dt, nt),
                                            (nt, dt)))
    built = re.search(rf"= f32\[(\d+,)?({dims})\]\{{[^}}]*\}} "
                      r"(transpose|pad|copy|copy-start|broadcast|fusion)\(",
                      text)
    assert built is None, built.group(0)


def test_pallas_chunk_compiles_for_v5e(spec):
    compiled = _compile(lambda X, Xt, sq, y, mask, st: chunk_jit(
        PallasRBF(X, 0.5, sq, interpret=False, Xt=Xt), y, mask, 100.0,
        1e-3, jnp.asarray(10**6, jnp.int64), st, n_iters=4096, wss="1"),
        spec((N, D), jnp.float32), spec((DT, NT), jnp.float32),
        spec((N,), jnp.float32), spec((N,), jnp.float64), spec((N,), bool),
        _state(spec))
    _assert_lane_dense(compiled.as_text())
    _assert_one_pass_over_x(compiled.as_text())
    _assert_x_not_rebuilt(compiled.as_text())


def test_batched_pallas_chunk_compiles_for_v5e(spec):
    """Vmapped lanes: the pallas_call batching rule adds a grid axis, and
    the layout holds across it."""
    lanes = 3
    states = EngineState(spec((lanes, N), jnp.float64),
                         spec((lanes, N), jnp.float64),
                         spec((lanes,), jnp.int64), spec((lanes,), bool))
    compiled = _compile(lambda X, Xt, sq, y, masks, Cs, st: chunk_batched_jit(
        PallasRBF(X, 0.5, sq, interpret=False, Xt=Xt), y, masks, Cs, 1e-3,
        jnp.asarray(10**6, jnp.int64), st, n_iters=4096, wss="1"),
        spec((N, D), jnp.float32), spec((DT, NT), jnp.float32),
        spec((N,), jnp.float32), spec((N,), jnp.float64),
        spec((lanes, N), bool), spec((lanes,), jnp.float64), states)
    _assert_lane_dense(compiled.as_text())
    _assert_one_pass_over_x(compiled.as_text())
    _assert_x_not_rebuilt(compiled.as_text())


def test_compiled_launch_refuses_f64_operands(spec):
    with pytest.raises(TypeError, match="float32"):
        jax.jit(lambda f, X, xij, sq: fused_smo_step(
            f, X, xij, sq, 0.37, gamma=0.5, interpret=False)).lower(
                spec((N,), jnp.float64), spec((N, D), jnp.float64),
                spec((2, D), jnp.float64), spec((N,), jnp.float64))


def test_mir_programs_compile_for_v5e_at_mnist(spec):
    """MIR over mnist's dense K (60,000 squared, 14.4 GB; |S + R| 54,000,
    |T| 6,000): the assembly streams K in column blocks and the solve takes
    the (6,000)-square normal equations, each with under 0.5 GB of
    temporaries. The gathered form held (|S + R|, |T|) slabs of 1.30 GB
    each, at least 2.6 GB beside K."""
    n, t = 60000, 6000
    f32, f64, i64 = jnp.float32, jnp.float64, jnp.int64
    prev = SMOResult(spec((n,), f64), spec((n,), f64), spec((), i64),
                     spec((), bool), spec((), f64), spec((), f64))
    sets = (spec((n - 2 * t,), i64), spec((t,), i64), spec((t,), i64))
    assemble = _compile(
        lambda K, y, C, p, S, R, T: seeding._mir_assemble(
            K, y, C, p, S, R, T, block=seeding.MIR_BLOCK),
        spec((n, n), f32), spec((n,), f64), spec((), f64), prev, *sets)
    solve = _compile(seeding._mir_solve, spec((t, t), f32), spec((t,), f64),
                     spec((n,), f64), spec((), f64), spec((n,), f64), *sets)
    for compiled in (assemble, solve):
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_row_sir_programs_compile_for_v5e_at_forest(spec):
    """A SIR-seeded fold with no dense K at Forest's 64,000 x 54, whose K
    (16.4 GB) no chip holds: the K[R, T] block computed from X (6,400
    squared, 164 MB), the replacement over it, f0 through the streaming
    matvec, the matrix-free chunk, and the held-out rows' kernel slab
    (6,400 x 64,000, 1.64 GB) with no second slab beside it, each well
    inside HBM."""
    from repro.core import study
    n, d, t = 64000, 54, 6400
    f32, f64, i64 = jnp.float32, jnp.float64, jnp.int64
    dt, nt = feature_major_shape(n, d)
    source = lambda X, Xt, sq: PallasRBF(X, 0.125, sq,  # noqa: E731
                                         interpret=False, Xt=Xt)
    src = (spec((n, d), f32), spec((dt, nt), f32), spec((n,), f32))
    prev = SMOResult(spec((n,), f64), spec((n,), f64), spec((), i64),
                     spec((), bool), spec((), f64), spec((), f64))
    sets = (spec((n - 2 * t,), i64), spec((t,), i64), spec((t,), i64))
    block = _compile(lambda X, Xt, sq, R, T: seeding._kernel_block(
        source(X, Xt, sq), R, T), *src, sets[1], sets[2])
    assert block.memory_analysis().output_size_in_bytes == 4 * t * t
    _compile(lambda K_RT, y, C, p, S, R, T: seeding._sir_from_block(
        K_RT, y, C, p, S, R, T, None, "random"),
        spec((t, t), f32), spec((n,), f64), spec((), f64), prev, *sets)
    f0 = _compile(lambda X, Xt, sq, y, a: study._streamed_init_f(
        source(X, Xt, sq), y, a), *src, spec((n,), f64), spec((n,), f64))
    assert f0.memory_analysis().temp_size_in_bytes < 2e9
    slab = _compile(lambda X, Xt, sq, test: source(X, Xt, sq).rows_at(test),
                    *src, sets[1]).memory_analysis()
    assert slab.output_size_in_bytes == 4 * t * n
    assert slab.temp_size_in_bytes < 0.1 * slab.output_size_in_bytes
    state = EngineState(spec((n,), f64), spec((n,), f64), spec((), i64),
                        spec((), bool))
    chunk = _compile(lambda X, Xt, sq, y, mask, st: chunk_jit(
        source(X, Xt, sq), y, mask, 10.0, 1e-3, jnp.asarray(10**6, i64), st,
        n_iters=4096, wss="1"), *src, spec((n,), f64), spec((n,), bool),
        state)
    _assert_one_pass_over_x(chunk.as_text())
