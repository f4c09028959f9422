"""The main path's Pallas kernels compile for a TPU v5e at their real widths.

Each test lowers a kernel for one chip of a described (not attached)
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what interpret mode cannot see: tiles that do not fit VMEM, unaligned
slices, unsupported casts.  Nothing runs.  The kernels are called with
``interpret=False`` because the wrappers would choose interpret mode on the
CPU backend the suite runs on.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels.rram_mvm import encode_matmul_rng

CAP = 2048          # capacity block of the paper-scale and resident paths
N_SOLVE = 65536     # meliso-mvm problem size
N_DENOISE = 32768   # resident-phase output length on a 16 GB chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes, dtype=jnp.float32):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            if isinstance(s, tuple) else s for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the kernel is in the program


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("direction", ["mvm", "rmvm"])
def test_ec_tile_step(one_chip, batch, direction):
    step = kops.rram_ec_tile_mvm if direction == "mvm" \
        else kops.rram_ec_tile_rmvm
    _compile(lambda x, xt, a, d: step(x, xt, a, d, interpret=False),
             one_chip, (CAP, batch), (CAP, batch), (CAP, CAP), (CAP, CAP))


@pytest.mark.parametrize("batch", [1, 64])
def test_ec_matmul_block_stack(one_chip, batch):
    """The resident pallas MVM reads the (mb, nb, cap, cap) stack in place."""
    _compile(lambda a, d, x, xt: kops.rram_ec_matmul(a, d, x, xt,
                                                     interpret=False),
             one_chip, (4, 4, CAP, CAP), (4, 4, CAP, CAP),
             (4 * CAP, batch), (4 * CAP, batch))


def test_ec_group_step(one_chip):
    _compile(lambda x, xt, a, d: kops.rram_ec_group_mvm(x, xt, a, d,
                                                        interpret=False),
             one_chip, (3, 512, 1), (3, 512, 1), (3, 512, 512),
             (3, 512, 512))


def test_encode_matmul_rng(one_chip):
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    _compile(lambda s, x, w: encode_matmul_rng(s, x, w, sigma=0.1, levels=8,
                                               interpret=False),
             one_chip, seed, (256, CAP), (CAP, CAP))


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("method", ["stencil", "thomas"])
def test_denoise(one_chip, method, batch):
    fn = kops.denoise_stencil if method == "stencil" else kops.denoise_thomas
    _compile(lambda p: fn(p, lam=1e-12, interpret=False), one_chip,
             (N_DENOISE, batch))


def test_solver_updates(one_chip):
    col = (N_SOLVE, 1)
    _compile(lambda x, b, y: kops.solver_richardson_update(
        x, b, y, 0.3, interpret=False), one_chip, col, col, col)
    _compile(lambda x, r, p, ap, al: kops.solver_cg_update(
        x, r, p, ap, al, interpret=False), one_chip, col, col, col, col,
        (1,))
