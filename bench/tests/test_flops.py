"""Operations and bytes from operand shapes, and the table of peaks."""
import pytest

from bench import flops


def test_gram_real_and_complex():
    # G = A^T A, A (4096, 256) f32: 4096*256*256 multiply-adds
    assert flops.gram(4096, 256, "f32") == (2 * 4096 * 256 * 256,
                                            (4096 * 256 + 256 * 256) * 4)
    # complex: 8 real flops per complex multiply-add
    assert flops.gram(10, 3, "c64") == (8 * 10 * 3 * 3, (30 + 9) * 8)


def test_complex_gram_counts_as_its_planar_real_form():
    # A^H A on (n, k) complex and the real Gram of (n, 2k) planar operands
    # do the same multiply-adds: 8 n k^2 == 2 n (2k)^2
    n, k = 4096, 64
    assert flops.gram(n, k, "c64")[0] == flops.gram(n, 2 * k, "f32")[0]


def test_tall_apply():
    assert flops.tall_apply(4096, 512, 256, "f32") == (
        2 * 4096 * 512 * 256, (4096 * 512 + 512 * 256 + 4096 * 256) * 4)
    n, k, m = 100, 7, 5
    # complex via one doubled real GEMM: (n, 2k) @ (2k, 2m)
    assert flops.tall_apply(n, k, m, "c64")[0] == flops.tall_apply(
        n, 2 * k, 2 * m, "f32")[0]


def test_peaks_of_v5e_and_unknown_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v4")
    with pytest.raises(KeyError):
        flops.peaks("source")


def test_least_seconds_names_its_bound():
    t, bound = flops.least_seconds(197e12, 1.0, "TPU v5 lite")
    assert t == pytest.approx(1.0) and bound == "compute"
    t, bound = flops.least_seconds(1.0, 819e9, "TPU v5 lite")
    assert t == pytest.approx(1.0) and bound == "memory"


def test_roofline_share_from_op_shapes():
    from bench.trace import Event
    text = ("%_gram.3 = f32[256,256]{1,0:T(8,128)} custom-call(f32[4096,256]"
            "{1,0:T(8,128)} %a), custom_call_target=\"tpu_custom_call\"")
    fl, nb = flops.gram(4096, 256, "f32")
    least, _ = flops.least_seconds(fl, nb, "TPU v5 lite")
    op = Event(text, 0, 1000)

    def work(operands):
        (dtype, (n, k)), = operands[:1]
        return flops.gram(n, k, dtype)
    share, bound = flops.roofline_share([op, op], work, "TPU v5 lite")
    assert share == pytest.approx(100 * least / 1e-6)
    assert bound in ("compute", "memory")
    assert flops.roofline_share([], work, "TPU v5 lite") == (None, None)


# Kernel executions as a v5e trace of the 8x8, chi=64 contraction names
# them, with their device durations in ns.
GRAM_TPU = ('%_gram.10 = f32[128,128]{1,0:T(8,128)S(1)} custom-call(f32[16384,'
            '128]{1,0:T(8,128)S(1)} %get-tuple-element.3740), custom_call_'
            'target="tpu_custom_call", operand_layout_constraints={f32[16384,'
            '128]{1,0}}, frontend_attributes={kernel_metadata={}}', 29811)
TALL_TPU = ('%_tall_apply.5 = f32[16384,128]{1,0:T(8,128)S(1)} custom-call('
            'f32[16384,128]{1,0:T(8,128)S(1)} %get-tuple-element.3741, f32[128,'
            '128]{1,0:T(8,128)S(1)} %pad.17), custom_call_target="tpu_custom_'
            'call", operand_layout_constraints={f32[16384,128]{1,0}, f32[128,'
            '128]{1,0}}, frontend_attributes={kernel_metadata={}}', 24455)
CROSS_TPU = ('%_tiled_matmul.5 = f32[128,128]{1,0:T(8,128)S(1)} custom-call'
             '(f32[128,16384]{1,0:T(8,128)S(1)} %pad.13.clone, f32[16384,128]'
             '{1,0:T(8,128)S(1)} %get-tuple-element.3742), custom_call_target='
             '"tpu_custom_call", operand_layout_constraints={f32[128,16384]'
             '{1,0}, f32[16384,128]{1,0}}, frontend_attributes={kernel_'
             'metadata={}}', 34711)


def _kernel_trace():
    from bench.trace import Event, Reduced
    kernels = [Event(text, 1000 * i, dur) for i, (text, dur) in
               enumerate([GRAM_TPU, TALL_TPU, CROSS_TPU])]
    return Reduced((0, 10 ** 9), {}, kernels, [])


def test_roofline_readers_on_chip_kernel_names():
    from bench import run as R
    ctx = {"trace": _kernel_trace(), "device_kind": "TPU v5 lite"}
    gram = R.load_module("metrics", "gram_roofline").read(ctx)
    tall = R.load_module("metrics", "tall_apply_roofline").read(ctx)
    # the gram site: the _gram kernel, memory-bound, (16384*128 + 128*128)
    # * 4 bytes at 819 GB/s, and the _tiled_matmul cross term,
    # (128*16384 + 16384*128 + 128*128) * 4 bytes
    least = ((16384 * 128 + 128 * 128) + (2 * 16384 * 128 + 128 * 128)) \
        * 4 / 819e9
    assert gram == pytest.approx(100 * least / ((29811 + 34711) * 1e-9))
    # tall_apply: (16384*128 + 128*128 + 16384*128) * 4 bytes
    assert tall == pytest.approx(
        100 * (2 * 16384 * 128 + 128 * 128) * 4 / 819e9 / 24455e-9)
    assert 0 < gram < 100 and 0 < tall < 100
