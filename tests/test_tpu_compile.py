"""The main path's Pallas kernels compile for a TPU v5e at their real widths.

Each test lowers a kernel for one chip of a described (not attached)
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what interpret mode cannot see: tiles that do not fit VMEM, unaligned
slices, unsupported casts.  Nothing runs.  The kernels are called with
``interpret=False`` because the wrappers would choose interpret mode on the
CPU backend the suite runs on.
"""
import re

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
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo, no_compile_cache):
    """A 2x2 ("data", "model") mesh over the four described chips."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))


def _compile(fn, sharding, *shapes, dtype=jnp.float32):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            if isinstance(s, tuple) else s for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the kernel is in the program
    return text


def _kernel_ops(text):
    """(instruction, innermost meliso scope or "") of each kernel call."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = .*custom-call\(", line)
        if m and 'custom_call_target="tpu_custom_call"' in line:
            path = re.search(r'op_name="([^"]*)"', line)
            scopes = re.findall(r"meliso\.\w+", path.group(1) if path else "")
            out.append((m.group(1), scopes[-1] if scopes else ""))
    return out


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("direction", ["mvm", "rmvm"])
def test_ec_tile_step(one_chip, batch, direction):
    step = kops.rram_ec_tile_mvm if direction == "mvm" \
        else kops.rram_ec_tile_rmvm
    _compile(lambda x, xt, a, d: step(x, xt, a, d, interpret=False),
             one_chip, (CAP, batch), (CAP, batch), (CAP, CAP), (CAP, CAP))


@pytest.mark.parametrize("batch", [1, 8, 64, 128])
def test_ec_matmul_block_stack(one_chip, batch):
    """The resident pallas MVM reads the (mb, nb, cap, cap) stack in place."""
    _compile(lambda a, d, x, xt: kops.rram_ec_matmul(a, d, x, xt,
                                                     interpret=False),
             one_chip, (4, 4, CAP, CAP), (4, 4, CAP, CAP),
             (4 * CAP, batch), (4 * CAP, batch))


def test_ec_matvec_resident_stack(one_chip):
    """The single-column (VPU) form at the resident widths: batch 1 against
    the (16, 16, 2,048, 2,048) block stack, at the shipped tiles, is one
    ``%ec_matmul`` kernel with a 2-D (rows, K) grid."""
    n = 16 * CAP
    text = _compile(lambda a, d, x, xt: kops.rram_ec_matmul(a, d, x, xt,
                                                            interpret=False),
                    one_chip, (16, 16, CAP, CAP), (16, 16, CAP, CAP),
                    (n, 1), (n, 1))
    ops = _kernel_ops(text)
    assert len(ops) == 1 and re.fullmatch(r"%ec_matmul(\.\d+)?", ops[0][0])
    assert ops[0][1] == "meliso.tier1", ops


def test_ec_matmul_packed_resident_stack(one_chip):
    """The packed MXU form at resident-mvm-b64's widths: 64 columns against
    the (16, 16, 2,048, 2,048) f32 block stack, at the shipped tiles, is one
    ``%ec_matmul`` kernel with a 2-D (rows, K) grid, its VMEM within the
    limit it asks for."""
    n = 16 * CAP
    text = _compile(lambda a, d, x, xt: kops.rram_ec_matmul(a, d, x, xt,
                                                            interpret=False),
                    one_chip, (16, 16, CAP, CAP), (16, 16, CAP, CAP),
                    (n, 64), (n, 64))
    ops = _kernel_ops(text)
    assert len(ops) == 1 and re.fullmatch(r"%ec_matmul(\.\d+)?", ops[0][0])
    assert ops[0][1] == "meliso.tier1", ops


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


# Each kernel's op in a device trace is its HLO instruction, named after its
# pallas_call's ``name=``: a refactor of the wrappers cannot rename it.
KERNELS = {
    "ec_matmul": (lambda a, d, x, xt: kops.rram_ec_matmul(
        a, d, x, xt, interpret=False), [(4, 4, CAP, CAP)] * 2
        + [(4 * CAP, 8)] * 2),
    "stencil_denoise": (lambda p: kops.denoise_stencil(
        p, lam=1e-12, interpret=False), [(N_DENOISE, 8)]),
    "thomas_solve": (lambda p: kops.denoise_thomas(
        p, lam=1e-12, interpret=False), [(N_DENOISE, 8)]),
    "cg_update": (lambda x, r, p, ap, al: kops.solver_cg_update(
        x, r, p, ap, al, interpret=False), [(N_SOLVE, 1)] * 4 + [(1,)]),
    "richardson_update": (lambda x, b, y: kops.solver_richardson_update(
        x, b, y, 0.3, interpret=False), [(N_SOLVE, 1)] * 3),
    "encode_matmul": (lambda x, w, e: kops.rram_encode_matmul(
        x, w, e, sigma=0.1, levels=8, interpret=False),
        [(256, CAP), (CAP, CAP), (CAP, CAP)]),
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_op_names(one_chip, kernel):
    fn, shapes = KERNELS[kernel]
    ops = _kernel_ops(_compile(fn, one_chip, *shapes))
    assert ops and all(re.fullmatch(rf"%{kernel}(\.\d+)?", name)
                       for name, _ in ops), ops


def test_encode_matmul_rng_op_name(one_chip):
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    ops = _kernel_ops(_compile(
        lambda s, x, w: encode_matmul_rng(s, x, w, sigma=0.1, levels=8,
                                          interpret=False),
        one_chip, seed, (256, CAP), (CAP, CAP)))
    assert [name for name, _ in ops] == ["%encode_matmul_rng.1"], ops


@pytest.mark.parametrize("batch", [1, 64])
def test_resident_mvm_kernel_names_and_stages(one_chip, monkeypatch, batch):
    """The resident pallas MVM compiled for the chip: the tier-1 kernel is
    ``%ec_matmul`` (what the trace's ``%ec_matmul.<k> custom-call`` events
    are matched by) under ``meliso.tier1``, tier-2 ``%stencil_denoise``
    under ``meliso.tier2``."""
    from repro.core import CrossbarConfig, MCAGeometry, get_device
    from repro.engine import _pallas_corrected
    monkeypatch.setattr(kops, "on_cpu", lambda: False)
    cfg = CrossbarConfig(device=get_device("epiram"),
                         geom=MCAGeometry(4, 4, 512, 512))
    n = 4 * CAP
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    ops = _kernel_ops(_compile(
        lambda at, da, x, k: _pallas_corrected(at, da, x, k, cfg, n, n,
                                               False),
        one_chip, (4, 4, CAP, CAP), (4, 4, CAP, CAP), (n, batch), key))
    kinds = {re.sub(r"\.\d+$", "", name): scope for name, scope in ops}
    assert kinds == {"%ec_matmul": "meliso.tier1",
                     "%stencil_denoise": "meliso.tier2"}, ops
    # One tier-1 kernel event per MVM, whichever form the batch takes.
    assert len([scope for _, scope in ops if scope == "meliso.tier1"]) == 1, ops


def test_paper_cg_2x2_mvm_on_four_chips(four_chips):
    """The paper-cg-2x2 cell's MVM compiled for the four chips of the mesh:
    the 65,536^2 resident=False corrected MVM at the cell's widths, each
    chip on its 16x16 window of 2,048^2 blocks.  One all-reduce, named
    ``meliso.psum``, joins each chip's (32,768, 1) tier-1 partials, and no
    chip holds any of the image: its temporaries are a few blocks' worth."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import CrossbarConfig, MCAGeometry, get_device
    from repro.core.matrices import ImplicitBandedMatrix
    from repro.engine import AnalogEngine
    cfg = CrossbarConfig(device=get_device("epiram"),
                         geom=MCAGeometry(4, 4, 512, 512), k_iters=5, ec=True,
                         ec_mode="fused", denoise_method="neumann", lam=1e-12,
                         h=-1.0)
    producer = ImplicitBandedMatrix(n=N_SOLVE, cap_m=CAP, cap_n=CAP,
                                    seed=N_SOLVE).block
    engine = AnalogEngine(cfg, execution="distributed", mesh=four_chips)
    handle = engine.program(producer, jax.random.PRNGKey(0),
                            shape=(N_SOLVE, N_SOLVE), resident=False)
    replicated = NamedSharding(four_chips, P())
    x = jax.ShapeDtypeStruct((N_SOLVE, 1), jnp.float32, sharding=replicated)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    compiled = jax.jit(engine.mvm_fn(handle)).lower(x, key).compile()
    reduces = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (f32\[[\d,]+\]).* all-reduce\(",
                     line)
        if m:
            path = re.search(r'op_name="([^"]*)"', line)
            reduces.append((m.group(1), re.findall(r"meliso\.\w+",
                                                   path.group(1))[-1:]))
    assert reduces == [("f32[32768,1]", ["meliso.psum"])], reduces
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * CAP * CAP * 4
