"""The benchmark's yardstick: work from shapes, and the peaks table."""

import pytest

from bench import harness, nets, work

V5E = "TPU v5 lite"


def _sum(cfg_name, side):
    cfg = harness.load_config(cfg_name)
    rows = work.net_work(nets.load(cfg), cfg, side, 1, work.load_peaks(V5E))
    return rows, sum(r["flops"] for r in rows), sum(r["least_s"] for r in rows)


def test_vgg13_s3_at_224_by_hand():
    # 2 * 9 * (224^2 (3*64 + 64*64) + 112^2 (64*128 + 128*128)
    #          + 56^2 (128*256 + 256*256)) = 14.97 GFLOP per image
    rows, flops, least = _sum("vgg13-s3", 224)
    assert flops == 2 * 9 * (224 ** 2 * (3 * 64 + 64 * 64)
                             + 112 ** 2 * (64 * 128 + 128 * 128)
                             + 56 ** 2 * (128 * 256 + 256 * 256))
    assert flops == pytest.approx(14.97e9, rel=1e-3)
    # the 224 and first 112 px layers are HBM-bound in float32; the
    # rest compute-bound at 197 TFLOP/s: 107 us in all
    assert [r["bound"] for r in rows] == ["hbm"] * 3 + ["compute"] * 3
    assert least == pytest.approx(107.0e-6, rel=1e-2)
    # layer 2 (64->64 at 224 px): in + out activations + weights, f32
    assert rows[1]["bytes"] == 4 * (2 * 224 * 224 * 64 + 9 * 64 * 64)


def test_vgg16_s3_at_512_by_hand():
    rows, flops, least = _sum("vgg16-s3", 512)
    assert flops == 2 * 9 * (512 ** 2 * (3 * 64 + 64 * 64)
                             + 256 ** 2 * (64 * 128 + 128 * 128)
                             + 128 ** 2 * (128 * 256 + 2 * 256 * 256))
    assert flops == pytest.approx(97.54e9, rel=1e-3)
    assert least == pytest.approx(655.0e-6, rel=1e-2)
    assert len(rows) == 7


def test_batch_counts_weights_once():
    cfg = harness.load_config("vgg13-s3")
    net = nets.load(cfg)
    one = work.net_work(net, cfg, 224, 1, work.load_peaks(V5E))
    eight = work.net_work(net, cfg, 224, 8, work.load_peaks(V5E))
    for a, b in zip(one, eight):
        w = 4 * 9 * a["c_in"] * a["c_out"]
        assert b["flops"] == 8 * a["flops"]
        assert b["bytes"] - w == 8 * (a["bytes"] - w)


def test_image_flops_shrinks_with_the_side():
    cfg = harness.load_config("vgg13-s3")
    net = nets.load(cfg)
    assert work.image_flops(net, cfg, 112) * 4 == work.image_flops(net, cfg, 224)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks"):
        work.load_peaks("TPU v9 imaginary")
    assert work.load_peaks(V5E)["flops_per_s"] == 197e12
    assert work.load_peaks(V5E)["hbm_bytes_per_s"] == 819e9
