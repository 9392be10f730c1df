"""Net modules: what net a configuration is.

A configuration names its net module by the key `"net"` (default
`"chain"`); the module is the file `bench/nets/<net>.py`, loaded by
its path.  The harness, the reference, the control and the work counts
reach the net only through these five functions of its module, each
taking the configuration as it was read from its file:

    input_channels(cfg) -> int
        channels of the images the net takes
    make_weights(cfg, seed) -> dict
        float32 weights from the seed, made on the device in one jitted
        call; the keys are those the module's `netspec` binds
    forward(cfg, weights, x, precision="highest")
        the plain reference on an NHWC batch of equal-sized images.  It
        imports nothing of the program, and every product in it goes
        through `bench.reference.conv` at `precision`, so that the
        control computed one precision down means something; a dense
        layer is a 1x1 conv on a 1x1 map
    netspec(cfg)
        the net as the program's `NetSpec`: the only function that
        imports `repro`
    conv_shapes(cfg, side) -> list
        every layer that does matrix work, at a square input of `side`
        pixels, as the geometry dicts `bench.work.layer_work` takes

A configuration that brings a new net adds its module here and names
it; no other file of the benchmark changes.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib

NET_DIR = pathlib.Path(__file__).resolve().parent


def load(cfg: dict, directory: pathlib.Path = NET_DIR):
    """The net module a configuration names."""
    return _load_path(pathlib.Path(directory) / f"{cfg.get('net', 'chain')}.py")


@functools.lru_cache(maxsize=None)
def _load_path(path: pathlib.Path):
    """One module object per file, so that what is cached against the
    module (the reference's compiled forward) is found again."""
    spec = importlib.util.spec_from_file_location(f"bench_net_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
