"""The four-chip cell, paper-cg-2x2, cut to a size the CPU holds and run on
four virtual CPU devices: the sound program passes the check with the 1x1
cut's iteration counts, and the faults only a mesh can have fail it.  Each
run is a child process with ``--xla_force_host_platform_device_count=4``,
so this process keeps one device.  Then the collective share's reader, on
hand-built traces and on a four-chip trace recorded on a TPU v5e."""
import json
import os
import re
import subprocess
import sys
import textwrap
import types

import pytest

import harness
from conftest import BENCH, ROOT

T = harness.load_module(BENCH / "trace.py")
SHARE = harness.load_module(BENCH / "metrics" / "collective_share.py")
DATA = BENCH / "tests" / "data"
MS = 1_000_000   # ns

PRELUDE = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {tests!r})
    import jax, jax.numpy as jnp
    from conftest import run_small, small_cell
    import repro.core.distributed as D


    class Counting:
        \"\"\"The system under test, keeping each solve's iteration count.\"\"\"

        def __init__(self, system, fault=None):
            self.system, self.fault, self.iters = system, fault, []

        def solve(self, b, key, **kw):
            x, iters, mvms = self.system.solve(b, key, **kw)
            if self.fault == "segments_swapped":   # row halves exchanged
                half = x.shape[0] // 2
                x = jnp.concatenate([x[half:], x[:half]])
            self.iters.append(iters)
            return x, iters, mvms

        def free(self):
            self.system.free()
""").format(tests=str(BENCH / "tests"))


def run_child(code: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "-c", PRELUDE + code], cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_sound_2x2_is_correct_with_the_1x1_iterations():
    res = run_child(textwrap.dedent("""
        out = {}
        for name in ("paper-cg-2x2", "paper-cg"):
            seen = []
            result = run_small(name, wrap=lambda s: seen.append(Counting(s))
                               or seen[-1])
            out[name] = {"result": result,
                         "iters": [int(k) for k in seen[0].iters]}
        print(json.dumps(out))
    """))
    mesh, one = res["paper-cg-2x2"], res["paper-cg"]
    assert mesh["result"]["correct"], mesh["result"]["check"]
    assert mesh["result"]["device"]["count"] == 4
    assert mesh["result"]["window_compiles"] == 0
    # the same seed draws the same requests: each solve takes the 1x1
    # cut's iterations (the warm-up's first)
    common = min(len(mesh["iters"]), len(one["iters"]))
    assert common >= 3, (mesh["iters"], one["iters"])
    assert mesh["iters"][:common] == one["iters"][:common]


@pytest.mark.parametrize("fault", ["psum_identity", "segments_swapped"])
def test_planted_mesh_fault_is_not_correct(fault):
    res = run_child(textwrap.dedent(f"""
        if {fault!r} == "psum_identity":
            # each row half gets only its own chip's half of the contraction
            D._psum_partials = lambda p, axes: p
        result = run_small("paper-cg-2x2",
                           wrap=lambda s: Counting(s, {fault!r}))
        print(json.dumps(result))
    """))
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert not res["correct"], (fault, res["check"])


def _run(trace):
    return types.SimpleNamespace(trace=trace, reduce=T, family="solve")


def ev(name, start_ms, end_ms):
    return T.Event(name, int(start_ms * MS), int(end_ms * MS))


def test_collective_share_on_a_hand_built_four_chip_trace():
    # Window 0-100 ms.  Each chip computes 0-40 and 60-90 (70 ms).  Chips 0
    # and 1 then psum 40-44 (an all-reduce named after the psum), chip 2
    # runs an asynchronous permute whose halves take 40-41 and 50-52 and a
    # fusion named after an all-gather 52-55, chip 3 a reduce-scatter 85-95
    # overlapping its compute by 5 ms.  A plain fusion and a reduce are no
    # collectives.
    compute = [ev("%fusion.3 fusion", 0, 40), ev("%reduce.58 reduce", 60, 90)]
    devices = {
        "/device:TPU:0": compute + [ev("%psum.17 all-reduce", 40, 44)],
        "/device:TPU:1": compute + [ev("%psum.17 all-reduce", 40, 44)],
        "/device:TPU:2": compute + [
            ev("%collective-permute-start.1 collective-permute-start", 40, 41),
            ev("%collective-permute-done.1 collective-permute-done", 50, 52),
            ev("%all-gather-fusion.2 fusion", 52, 55)],
        "/device:TPU:3": compute + [
            ev("%reduce-scatter.4 reduce-scatter", 85, 95)]}
    tr = T.Trace(window=(0, 100 * MS), host=[], devices=devices)
    # collectives: 4 + 4 + (1 + 2 + 3) + 10 = 24 ms; busy: 74 + 74 + 76 + 75
    got = SHARE.read(_run(tr))
    assert got == {"collective_share.solve": pytest.approx(100 * 24 / 299)}


def test_collective_share_reads_nothing_on_one_chip():
    tr = T.Trace(window=(0, 100 * MS), host=[], devices={
        "/device:TPU:0": [ev("%fusion.3 fusion", 0, 40),
                          ev("%reduce.58 reduce", 40, 50)]})
    assert SHARE.read(_run(tr)) == {}
    assert SHARE.read(_run(None)) == {}
    for path in sorted(DATA.glob("trace_v5e_*.json")):  # one-chip cells
        assert SHARE.read(_run(T.aligned(T.load(path)))) == {}, path.name


def test_collective_share_on_a_recorded_four_chip_trace():
    # A quarter of a millisecond of paper-cg-2x2's traced window, recorded
    # on a four-chip TPU v5e host and aligned as ``trace.read_xplane`` keeps
    # it: chip 0 ends one MVM and runs one CG iteration's collectives;
    # chips 1-3, each moved onto the host's clock by its own first op, are
    # inside their block scans (ops that cross the excerpt's edges kept
    # whole).  The pattern matches exactly the collective ops and nothing
    # else of the trace.
    tr = T.load(DATA / "collectives_v5e_paper_cg_2x2.json")
    assert len(tr.devices) == 4
    hits = T.kernel_events(tr, SHARE.PATTERN)
    assert [e.name for e in hits["/device:TPU:0"]] == [
        "%psum.17 all-reduce", "%all-gather.18 all-gather",
        "%collective-permute-start collective-permute-start",
        "%all-gather.19 all-gather",
        "%collective-permute-done collective-permute-done",
        "%all-reduce.7 all-reduce"]
    assert not any(hits[d] for d in hits if d != "/device:TPU:0")
    others = {e.name.split(" ")[1] for evs in tr.devices.values()
              for e in evs if not re.search(SHARE.PATTERN, e.name)}
    assert others == {"fusion", "copy", "slice", "dynamic-update-slice",
                      "copy-start", "copy-done", "custom-call", "reshape",
                      "dynamic-slice", "while"}, others
    # the psum 5.71 us, the gathers 5.96 and 5.33, the permute's halves
    # 0.32 and 0.006, the dot's all-reduce 2.66: 19.99 us of chip 0's busy
    # time, over the four chips' 1,003.75 us
    got = SHARE.read(_run(tr))["collective_share.solve"]
    coll = sum(e.seconds for e in hits["/device:TPU:0"])
    assert coll == pytest.approx(19.987e-6)
    assert got == pytest.approx(100 * coll / (4 * T.busy_s(tr)))
    assert got == pytest.approx(1.991, abs=1e-3)
