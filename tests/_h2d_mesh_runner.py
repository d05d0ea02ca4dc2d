"""Subprocess entry for the mesh h2d test: on 4 fake host devices, an
engine whose histogram and spatial launches shard over a ``data`` mesh
must hand each launch device arrays already split over that axis (the
launch's own sharding, so no reshard runs inside the launch), while a
bucket the mesh does not divide takes single-device inputs. Prints
H2D_MESH_OK on success."""
import dataclasses
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
sys.path.insert(0, _SRC)

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.core import fcm as F  # noqa: E402
from repro.data import phantom  # noqa: E402
from repro.serving.fcm_engine import FCMServeEngine  # noqa: E402


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    want = NamedSharding(mesh, PartitionSpec("data"))
    eng = FCMServeEngine(F.FCMConfig(max_iters=300), batch_sizes=(1, 4),
                         cache_size=0, mesh=mesh)
    imgs = [phantom.phantom_slice(32, 32, noise=3.0 + i, seed=40 + i)[0]
            for i in range(4)]
    seen = []

    def spy(launch):
        def wrapped(*args):
            seen.append([a.sharding for a in args])
            return launch(*args)
        return wrapped

    for route in ("histogram", "spatial"):
        ref = eng.segment(imgs, method=route)      # builds the programs
        eng.segment(imgs[:1], method=route)
        for key, prog in list(eng._programs.items()):
            if key[0] == route:
                eng._programs[key] = dataclasses.replace(
                    prog, launch=spy(prog.launch))
        seen.clear()
        got = eng.segment(imgs, method=route)           # bucket 4: mesh
        assert seen and all(s.is_equivalent_to(want, 2) for s in seen[0]), \
            (route, seen)
        for a, b in zip(got, ref):
            assert (a.labels == b.labels).all()
        seen.clear()
        eng.segment(imgs[:1], method=route)             # bucket 1
        assert seen and all(len(s.device_set) == 1 for s in seen[0]), \
            (route, seen)
    print("H2D_MESH_OK")


if __name__ == "__main__":
    main()
