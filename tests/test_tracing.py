"""Stage names in compiled programs and the program's host spans.

Every stage of a corrected MVM and of a solver core carries a
``jax.named_scope`` (``meliso.produce``, ``meliso.encode``, ``meliso.dac``,
``meliso.tier1``, ``meliso.psum``, ``meliso.tier2``, ``meliso.solver``): op
metadata that the compiled HLO keeps in each instruction's ``op_name`` and a
device trace shows per op.  The innermost ``meliso.*`` component of the name
path names the op (transforms wrap it: ``vmap(meliso.tier1)``).  The two
dispatch boundaries open ``jax.profiler.TraceAnnotation`` spans
(``meliso.engine.execute``, ``meliso.solver.dispatch``) that a profiler
trace records on the host's clock.
"""
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from conftest import analog_cfg, path_engine, program_path, spd_system
from repro.engine import SPAN_EXECUTE, AnalogEngine
from repro.solvers.base import SPAN_DISPATCH, as_operator, jit_core
from repro.solvers.krylov import cg_pipeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPE = re.compile(r"meliso\.[A-Za-z0-9_]+")
OP_NAME = re.compile(r'op_name="([^"]*)"')
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")
N = 128


def op_scopes(hlo_text: str):
    """(instruction, opcode, innermost meliso scope or "") of every
    instruction of a compiled HLO module, fused computations included."""
    out = []
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m is None:
            continue
        name = OP_NAME.search(line)
        scopes = SCOPE.findall(name.group(1)) if name else []
        out.append((m.group(1), m.group(2), scopes[-1] if scopes else ""))
    return out


def tier1_products(hlo_text: str):
    """(instruction, innermost meliso scope or "") of every product of the
    tier-1 correction: each dot (the MXU form) and each multiply of the
    ``ec_matmul`` kernel's body (the single-column VPU form, whose body the
    CPU interprets into the program)."""
    out = []
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        name = OP_NAME.search(line)
        path = name.group(1) if name else ""
        if m and (m.group(2) == "dot" or (m.group(2) == "multiply"
                                          and "/ec_matmul/" in path)):
            scopes = SCOPE.findall(path)
            out.append((m.group(1), scopes[-1] if scopes else ""))
    return out


def compiled(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _system():
    a, _, b = spd_system(N)
    return a, b[:, None], analog_cfg(N), jax.random.PRNGKey(3)


def _mvm_text(path):
    a, x, cfg, key = _system()
    if path == "local-pallas":
        engine = AnalogEngine(cfg, backend="pallas")
        handle = engine.program(a, key)
    else:
        engine = path_engine(cfg, path)
        handle = program_path(engine, a, key, path)
    return compiled(engine.mvm_fn(handle), x, key)


def _group_text():
    a, x, cfg, key = _system()
    engine = AnalogEngine(cfg)
    group = engine.program_group([a, 0.5 * a], key)
    return compiled(engine.group_mvm_fn(group), x, key)


def _cg_text():
    a, x, cfg, key = _system()
    engine = AnalogEngine(cfg, backend="pallas")
    op = as_operator(engine.program(a, key))
    core = cg_pipeline(op, tol=1e-4, maxiter=4, backend="pallas")
    return compiled(core, x, jnp.zeros_like(x), key)


TIER = {"meliso.dac", "meliso.tier1", "meliso.tier2"}
CASES = {
    "local-reference": (lambda: _mvm_text("local"), TIER),
    "local-pallas": (lambda: _mvm_text("local-pallas"), TIER),
    "streamed": (lambda: _mvm_text("streamed"), TIER | {"meliso.produce"}),
    "streamed-pallas": (lambda: _mvm_text("pallas"),
                        TIER | {"meliso.produce"}),
    "streamed-resident-false": (
        lambda: _mvm_text("virtual"),
        TIER | {"meliso.produce", "meliso.encode", "meliso.psum"}),
    "grouped": (_group_text, TIER),
    "cg-core": (_cg_text, TIER | {"meliso.solver"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stage_scopes_in_compiled_hlo(case):
    """Each path's compiled program names its stages, and every product of
    the tier-1 correction sits under ``meliso.tier1``."""
    build, expected = CASES[case]
    text = build()
    found = {scope for _, _, scope in op_scopes(text) if scope}
    assert expected <= found, f"missing {expected - found}; found {found}"
    products = tier1_products(text)
    assert products, "no tier-1 product in the compiled program"
    assert all(scope == "meliso.tier1" for _, scope in products), products


def test_psum_scope_on_a_2x2_mesh():
    """The psum of tier-1 partials of a 2x2 distributed MVM compiles to an
    all-reduce named ``meliso.psum`` (4 forced host devices, in a child so
    this process keeps one device)."""
    code = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp
        from conftest import analog_cfg, spd_system
        from test_tracing import compiled, op_scopes
        from repro.engine import AnalogEngine
        from repro.launch.mesh import make_mesh
        a, _, b = spd_system(128)
        key = jax.random.PRNGKey(3)
        mesh = make_mesh((2, 2), ("data", "model"))
        engine = AnalogEngine(analog_cfg(128), execution="distributed",
                              mesh=mesh)
        handle = engine.program(a, key)
        ops = op_scopes(compiled(engine.mvm_fn(handle), b[:, None], key))
        print(json.dumps([op for op in ops if "all-reduce" in op[1]]))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    reduces = json.loads(out.stdout.splitlines()[-1])
    assert reduces, "no all-reduce in the 2x2 program"
    assert all(scope == "meliso.psum" for _, _, scope in reduces), reduces


def _host_events(directory):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events.extend((e.name, dict(e.stats)) for e in line.events
                          if e.name.startswith("meliso."))
    return events


def test_dispatch_spans_in_a_profile(tmp_path):
    """A profiled engine MVM and CG core call record the program's host
    spans, with the execute span's arguments."""
    a, x, cfg, key = _system()
    engine = AnalogEngine(cfg, backend="pallas")
    handle = engine.program(a, key)
    core = jit_core(as_operator(handle), lambda op: cg_pipeline(
        op, tol=1e-4, maxiter=4, backend="pallas"))
    jax.block_until_ready(engine.mvm(handle, x, key=key))
    jax.block_until_ready(core(x, jnp.zeros_like(x), key))
    jax.block_until_ready(engine.mvm(handle, jnp.ones((N, 3)), key=key))
    jax.block_until_ready(engine.rmvm(handle, x, key=key))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(engine.mvm(handle, x, key=key))
        jax.block_until_ready(engine.rmvm(handle, jnp.ones((N, 3)), key=key))
        jax.block_until_ready(engine.mvm(handle, jnp.ones((N, 3)), key=key))
        jax.block_until_ready(engine.rmvm(handle, x, key=key))
        jax.block_until_ready(core(x, jnp.zeros_like(x), key))
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    executes = [stats for name, stats in events if name == SPAN_EXECUTE]
    assert [(s["path"], s["direction"], s["cols"], s["tier1"])
            for s in executes] == [
        ("local/pallas", "forward", 1, "vpu"),
        ("local/pallas", "transposed", 3, "mxu"),
        ("local/pallas", "forward", 3, "mxu_packed"),
        ("local/pallas", "transposed", 1, "mxu")]
    dispatches = [stats for name, stats in events if name == SPAN_DISPATCH]
    assert [s["tier1"] for s in dispatches] == ["vpu"]


def test_no_tier1_argument_without_a_kernel(tmp_path):
    """The reference backend runs no tier-1 kernel: its spans carry no
    ``tier1`` argument."""
    a, x, cfg, key = _system()
    handle = AnalogEngine(cfg).program(a, key)
    core = jit_core(as_operator(handle), lambda op: cg_pipeline(
        op, tol=1e-4, maxiter=4))
    jax.block_until_ready(handle.engine.mvm(handle, x, key=key))
    jax.block_until_ready(core(x, jnp.zeros_like(x), key))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(handle.engine.mvm(handle, x, key=key))
        jax.block_until_ready(core(x, jnp.zeros_like(x), key))
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    assert sorted(name for name, _ in events) == [SPAN_EXECUTE, SPAN_DISPATCH]
    assert all("tier1" not in stats for _, stats in events), events



def test_mesh_argument_on_distributed_spans(tmp_path):
    """Both host spans of a distributed engine carry its mesh as ``mesh``
    (``"2x2"``), so a trace shows which dispatches crossed chips; a local
    engine's spans carry none (4 forced host devices, in a child so this
    process keeps one device)."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, "tests")
        import jax, jax.numpy as jnp
        from conftest import analog_cfg, spd_system
        from test_tracing import _host_events
        from repro.engine import AnalogEngine
        from repro.launch.mesh import make_mesh
        from repro.solvers.base import as_operator, jit_core
        from repro.solvers.krylov import cg_pipeline
        a, _, b = spd_system(128)
        x, key = b[:, None], jax.random.PRNGKey(3)
        mesh = make_mesh((2, 2), ("data", "model"))
        calls = []
        for engine in (AnalogEngine(analog_cfg(128), execution="distributed",
                                    mesh=mesh),
                       AnalogEngine(analog_cfg(128))):
            handle = engine.program(a, key)
            core = jit_core(as_operator(handle), lambda op: cg_pipeline(
                op, tol=1e-4, maxiter=4))
            calls += [lambda e=engine, h=handle: e.mvm(h, x, key=key),
                      lambda c=core: c(x, jnp.zeros_like(x), key)]
        for call in calls:
            jax.block_until_ready(call())
        jax.profiler.start_trace({str(tmp_path)!r})
        for call in calls:
            jax.block_until_ready(call())
        jax.profiler.stop_trace()
        print(json.dumps([[name, stats.get("mesh")] for name, stats
                          in _host_events({str(tmp_path)!r})]))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    events = json.loads(out.stdout.splitlines()[-1])
    assert events == [[SPAN_EXECUTE, "2x2"], [SPAN_DISPATCH, "2x2"],
                      [SPAN_EXECUTE, None], [SPAN_DISPATCH, None]], events


def test_cached_executable_keeps_its_own_stage_names(tmp_path, monkeypatch):
    """With the persistent compilation cache on, two programs that differ
    only in their stage names do not share a cached executable, so the
    names a trace shows are those of the program that ran."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch import compile_cache

    def program(stage):
        def fn(x):
            with jax.named_scope(stage):
                return jnp.sin(x) * 2.0
        return jax.jit(fn)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CACHE_DIR", tmp_path)
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_compilation_cache_include_metadata_in_key",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        compile_cache.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        x = jnp.ones((8,))
        first = program("meliso.tier1").lower(x).compile().as_text()
        assert os.listdir(tmp_path), "nothing was written to the cache"
        second = program("meliso.tier2").lower(x).compile().as_text()
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    assert "meliso.tier1" in first
    assert "meliso.tier2" in second and "meliso.tier1" not in second
