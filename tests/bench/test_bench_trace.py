"""The trace reduction on a small synthetic trace shaped like a TPU v5e
trace of ``bench/run.py`` (``window_trace.pbtxt`` says what it holds)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    text = (pathlib.Path(__file__).with_name("window_trace.pbtxt")
            .read_text())
    return devtrace.reduce(ProfileData.from_text_proto(text))


def test_window_busy_and_kernel_time(reduced):
    assert reduced["window_s"] == pytest.approx(1e-3)
    # union of kernel 1320-1380, copy 1390-1400, kernel 1900-1950 and
    # async copy 1940-1960 us; the op at 500-600 us is outside the window
    assert reduced["busy_s"] == pytest.approx(130e-6)
    assert reduced["devices"] == 1
    assert reduced["kernel_s"] == {"wavefront": pytest.approx(110e-6)}
    assert reduced["kernel_events"] == {"wavefront": 2}


def test_breakdown(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["%traced.1 tpu_custom_call"] == pytest.approx(110e-6)
    assert set(ops) == {"%traced.1 tpu_custom_call", "%copy.8",
                        "%copy-start"}
    gaps = dict(reduced["idle_gaps"])
    assert gaps == {"host: bench.round": pytest.approx(540e-6),
                    "host: bench.wait": pytest.approx(300e-6),
                    "host: np.asarray(jax.Array)": pytest.approx(30e-6)}
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_innermost_span_and_union():
    starts, labels = devtrace.innermost(
        [("a", 0, 10), ("b", 2, 5), ("c", 12, 14)])
    assert starts == [0, 2, 5, 10, 12, 14]
    assert labels == ["a", "b", "a", "outside any span", "c",
                      "outside any span"]
    assert devtrace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]


def test_op_name():
    assert devtrace.op_name('%traced.1 = (f32[8]) custom-call(f32[8] %p),'
                            ' custom_call_target="tpu_custom_call"') \
        == "%traced.1 tpu_custom_call"
    assert devtrace.op_name("%copy.8 = f32[8] copy(f32[8] %p)") == "%copy.8"
