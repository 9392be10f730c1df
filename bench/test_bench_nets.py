"""Net modules: the chain's readings pinned, a net brought by its own
module served, checked, controlled and counted through the harness on
the CPU, and every benchmark configuration's reference and program
agreeing in shape at the buckets its cells serve.
"""

import io
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, nets, reference, traffic, work

DATA = pathlib.Path(__file__).resolve().parent / "testdata"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2 ** 40 + 3

# Read on the CPU before the chain moved into bench/nets/chain.py: the
# weights from SEED (keys, sum of |w|, sum of w^2, the first number of
# layers 0 and 1), and the reference's output on two 32-px images (sum,
# max, and every 997th number from the first).
PINNED_WEIGHTS = {
    "vgg13-s3": ([0, 1, 3, 4, 7, 8, 10, 11, 14, 15, 17, 18],
                 34230.144302511726, 1804.9740225249986,
                 [-0.2620272636413574, 0.0667538046836853]),
    "vgg16-s3": ([0, 1, 3, 4, 7, 8, 10, 11, 14, 15, 17, 18, 20, 21],
                 48123.42686886079, 2319.994909925294,
                 [-0.2620272636413574, 0.0667538046836853]),
}
PINNED_OUTPUT = {
    "vgg13-s3": (10457.572590447497, 8.479166984558105,
                 [0.5520718097686768, 0.6283997893333435, 1.8692132234573364,
                  3.7761542797088623, 3.7171502113342285, 0.2467009574174881]),
    "vgg16-s3": (9358.060519217746, 7.511348724365234,
                 [0.012231290340423584, 1.342233657836914, 1.6016147136688232,
                  0.9558473825454712, 1.4116132259368896, 0.0]),
}


@pytest.mark.parametrize("name", sorted(PINNED_WEIGHTS))
def test_chain_weights_and_reference_are_pinned(name):
    cfg = harness.load_config(name)
    net = nets.load(cfg)
    assert "net" not in cfg and net is nets.load(cfg, nets.NET_DIR)
    w = net.make_weights(cfg, SEED)
    keys, abs_sum, sq_sum, firsts = PINNED_WEIGHTS[name]
    assert sorted(w) == keys
    a = [np.asarray(w[k], np.float64) for k in keys]
    assert sum(np.abs(v).sum() for v in a) == pytest.approx(abs_sum, rel=1e-6)
    assert sum((v * v).sum() for v in a) == pytest.approx(sq_sum, rel=1e-6)
    assert [float(a[0].flat[0]), float(a[1].flat[0])] == pytest.approx(firsts)

    x = np.random.default_rng([SEED, 7]).standard_normal(
        (2, 32, 32, 3)).astype(np.float32)
    y = np.asarray(net.forward(cfg, w, jnp.asarray(x)))
    total, top, samples = PINNED_OUTPUT[name]
    assert y.shape == (2, 4, 4, 256)
    assert y.astype(np.float64).sum() == pytest.approx(total, rel=1e-6)
    assert float(y.max()) == pytest.approx(top, rel=1e-6)
    assert y.reshape(-1)[::997][:6] == pytest.approx(samples, rel=1e-6,
                                                     abs=1e-6)
    # the check's jitted path reads the same as the forward
    for got, want in zip(reference.run(net, cfg, w, list(x)), y):
        assert reference.rel_err(got, want) < 1e-6


@pytest.mark.parametrize("name,side,flops", [
    ("vgg13-s3", 224, 14_970_912_768),
    ("vgg13-s3", 128, 4_888_461_312),
    ("vgg16-s3", 512, 97_542_733_824),
])
def test_image_flops_pinned(name, side, flops):
    cfg = harness.load_config(name)
    assert work.image_flops(nets.load(cfg), cfg, side) == flops


def test_chain_passes_a_convs_pad_to_the_program():
    cfg = {"name": "padded", "layers": [
        {"kind": "conv", "c_in": 3, "c_out": 4, "k": 3, "pad": 0},
        {"kind": "conv", "c_in": 4, "c_out": 4, "k": 3}]}
    net = nets.load(cfg)
    spec = net.netspec(cfg)
    assert [lay.pad for lay in spec.layers] == [0, 1]
    assert spec.out_shape(16, 16, 3) == (14, 14, 4)
    assert [(g["ho"], g["wo"]) for g in net.conv_shapes(cfg, 16)] == \
        [(14, 14), (14, 14)]
    y = jax.eval_shape(lambda: net.forward(
        cfg, net.make_weights(cfg, 1), jnp.zeros((1, 16, 16, 3))))
    assert y.shape == (1, 14, 14, 4)


# ---------------------------------------------------- a net module plugged in

def _bench():
    m = {"better": "lower", "source": "host_clock",
         "workloads": ["stages.closed"]}
    return {
        "workloads": [{"name": "stages.closed", "config": "stages",
                       "traffic": "tiny-closed", "chips": 1,
                       "why": "rehearsal"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "images_per_s", "unit": "images/s", **m},
        ],
        "per_layer": [],
    }


def _run(net_dir, seconds=0.6):
    out, err = io.StringIO(), io.StringIO()
    result = harness.run_cell(
        "stages.closed", SEED, seconds, False, t_start=time.monotonic(),
        bench=_bench(), require_chip=False, peaks=PEAKS, out=out, err=err,
        config_dir=DATA, traffic_dir=DATA, net_dir=net_dir)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == result
    return result


def test_a_net_module_is_served_checked_and_counted():
    cfg = harness.load_config("stages", DATA)
    assert cfg["net"] == "stages" and "layers" not in cfg
    result = _run(DATA)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 8
    chk = result["check"]
    assert 0 < chk["worst_rel_err"]["value"] < chk["worst_rel_err"]["limit"]
    net = nets.load(cfg, DATA)
    # 3->8 at 16 px, then 8->16 and 16->16 at 8 px
    assert work.image_flops(net, cfg, 16) == 2 * 9 * (
        16 * 16 * 3 * 8 + 8 * 8 * (8 * 16 + 16 * 16))


def test_a_net_module_whose_forward_drops_the_bias_is_not_correct(tmp_path):
    src = (DATA / "stages.py").read_text()
    broken = src.replace("x = x + weights[i]", "x = x")
    assert broken != src
    (tmp_path / "stages.py").write_text(broken)
    result = _run(tmp_path, seconds=0.4)
    assert result["correct"] is False
    assert result["check"]["worst_rel_err"]["value"] > \
        result["check"]["worst_rel_err"]["limit"]


@pytest.mark.parametrize("precision,correct", [("high", False),
                                               ("highest", True)])
def test_control_through_a_net_module(precision, correct):
    cfg = harness.load_config("stages", DATA)
    mix = traffic.load("tiny-closed", DATA)
    server = harness.prepare(cfg, mix, SEED, DATA)
    win = harness.drive(server, 0.4, SEED)
    harness.release(server)
    ctl = control._control(server.net, cfg, server.weights, server.images,
                           win, precision)
    got = harness.check(server.net, cfg, server.weights, server.images, ctl)
    got["compiles_in_window"] = win.compiles
    line = harness.limits_line(got, cfg["rel_err_limit"])
    assert harness.is_correct(line, got["compared"]) is correct


# ---------------------------------------------- reference and program agree

def _buckets_by_config() -> dict:
    bench = harness.load_benchmark()
    out = {c["name"]: set() for c in bench["configs"]}
    for w in bench["workloads"]:
        out[w["config"]] |= set(traffic.load(w["traffic"])["buckets"])
    return {k: sorted(v) for k, v in out.items()}


BUCKETS = _buckets_by_config()


@pytest.mark.parametrize("name", sorted(BUCKETS))
def test_reference_and_program_agree_in_shape(name):
    """The module's reference and the program's net give the same output
    shape at every bucket of the configuration's cells, and its weights
    are the ones the program's net binds, in shape."""
    from repro.convserve.graph import init_weights

    cfg = harness.load_config(name)
    net = nets.load(cfg)
    spec = net.netspec(cfg)
    c = net.input_channels(cfg)
    weights = jax.eval_shape(lambda: net.make_weights(cfg, SEED))
    bound = init_weights(spec)
    assert {k: v.shape for k, v in weights.items()} == \
        {k: v.shape for k, v in bound.items()}
    for b in BUCKETS[name]:
        y = jax.eval_shape(lambda w, x: net.forward(cfg, w, x), weights,
                           jax.ShapeDtypeStruct((1, b, b, c), jnp.float32))
        assert y.shape[1:] == spec.out_shape(b, b, c), b
