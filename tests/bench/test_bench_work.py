"""Work counts from request shapes, the peak table and the roofline."""
import pytest

from harness import peaks, work


def test_pca_counts_by_hand():
    # mnist-28x28: 2 m n^2 + 9 n^3 flops; input, components, 5 vectors
    f, b = work.request_work("pca", (70000, 784))
    assert f == 2 * 70000 * 784**2 + 9 * 784**3
    assert b == 4 * (70000 * 784 + 784 * 784 + 5 * 784)


def test_svd_counts_by_hand():
    f, b = work.request_work("svd", (256, 64))
    assert f == 4 * 256 * 64**2 + 9 * 64**3
    assert b == 4 * (2 * 256 * 64 + 64 * 64 + 64)


def test_eigh_counts_by_hand():
    f, b = work.request_work("eigh", (48, 48))
    assert f == 9 * 48**3
    assert b == 4 * (2 * 48 * 48 + 48)


def test_counts_ignore_padding_and_batch():
    # the work is the request's own: a 57-wide request counts 57, not its
    # 64-wide bucket
    assert work.request_work("eigh", (57, 57))[0] == 9 * 57**3


def test_unknown_op():
    with pytest.raises(ValueError):
        work.request_work("qr", (4, 4))


def test_roofline_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = work.roofline_seconds(197e12, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.roofline_seconds(1.0, 819e9, p)
    assert bound == "bandwidth" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
