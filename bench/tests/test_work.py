"""The work of one corrected MVM of each configuration, against hand
arithmetic, and the roofline bound it gives on a TPU v5e."""
import pytest

import harness
import meliso_engine
import peaks

tier1 = harness.load_module(harness.BENCH / "metrics" / "tier1_roofline.py")


def config(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def test_paper_work_batch_1():
    # 65,536^2, two n x n products per MVM; nothing resident, so only the
    # input, its DAC image and the output (3 x 4 B x n) move.
    w = meliso_engine.work(config("meliso-mvm"), 1)
    assert w == {"flop": 4 * 65536 ** 2, "bytes": 3 * 4 * 65536}
    assert w["flop"] == 17_179_869_184


def test_resident_work_batch_64():
    # 32,768^2 float32 A_tilde and dA read once: 8 B per element (8 GiB).
    w = meliso_engine.work(config("meliso-resident-32k"), 64)
    assert w["flop"] == 4 * 32768 ** 2 * 64 == 274_877_906_944
    assert w["bytes"] == 8 * 32768 ** 2 + 3 * 4 * 32768 * 64
    assert w["bytes"] == 8_615_100_416


@pytest.mark.parametrize("cols", [1, 64])
def test_resident_roofline_is_bytes_bound(cols):
    w = meliso_engine.work(config("meliso-resident-32k"), cols)
    least, bound = tier1.least_seconds(w, peaks.peaks("TPU v5 lite"))
    assert bound == "bytes"
    # 8 GiB at 819 GB/s: 10.49 ms, plus the panels.
    assert least == pytest.approx(w["bytes"] / 819e9)
    assert 10.48e-3 < least < 10.53e-3


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
