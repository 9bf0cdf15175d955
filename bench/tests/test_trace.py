"""``bench/trace.py`` on a small CPU trace committed beside this file
(``record_cpu_trace.py`` made it: two programs, three calls each, then a
50 ms sleep, all inside ``bench.window``)."""
from pathlib import Path

import pytest

from bench import trace as tr

XPLANE = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(str(XPLANE))


def _raw_busy_ns(window):
    """Union of the program executions inside the window, each from its
    first operation to its last, read from the raw events without the
    module under test."""
    from jax.profiler import ProfileData
    lo, hi = window
    runs = {}
    for plane in ProfileData.from_file(str(XPLANE)).planes:
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                if "hlo_module" in st:
                    s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                    r = runs.setdefault(st["run_id"], [s, e])
                    r[0], r[1] = min(r[0], s), max(r[1], e)
    iv = sorted((max(s, lo), min(e, hi)) for s, e in runs.values()
                if e > lo and s < hi)
    total, cur = 0, None
    for s, e in iv:
        if cur is None or s > cur[1]:
            if cur:
                total += cur[1] - cur[0]
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    return total + (cur[1] - cur[0] if cur else 0)


def test_program_counts(reduced):
    names = sorted(p.name for d in reduced.programs for p in reduced.programs[d])
    assert names == ["jit_affine"] * 3 + ["jit_sin_matmul"] * 3
    assert reduced.program_count() == 6


def test_idle_share(reduced):
    busy_ns = _raw_busy_ns(reduced.window)
    assert busy_ns > 0
    assert reduced.busy_s() == pytest.approx(busy_ns * 1e-9, rel=1e-12)
    assert reduced.idle_share() == pytest.approx(
        1 - busy_ns / (reduced.window[1] - reduced.window[0]), rel=1e-12)
    # the 50 ms sleep leaves the device idle for most of the window
    assert 0.9 < reduced.idle_share() < 1.0
    assert reduced.window_s() > 0.05


def test_breakdown_names_programs_and_spans(reduced):
    b = reduced.breakdown()
    assert {name for name, _ in b["device_ops"]} == {"jit_affine",
                                                     "jit_sin_matmul"}
    gaps = dict(b["idle_gaps"])
    assert gaps["bench.sleep"] >= 0.05
    assert sum(gaps.values()) == pytest.approx(
        reduced.window_s() - reduced.busy_s(), rel=1e-9)


def test_no_kernels_on_the_cpu(reduced):
    assert reduced.kernels == []
    assert reduced.kernels_named("_gram") == []


def test_shapes_from_hlo_text():
    text = ("%custom-call.1 = f32[4096,256]{1,0} custom-call(f32[4096,256]"
            "{1,0} %p0, bf16[256,128]{1,0} %p1)")
    assert tr.shapes(text) == [("f32", (4096, 256)), ("f32", (4096, 256)),
                               ("bf16", (256, 128))]
