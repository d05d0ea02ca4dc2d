"""Compile the main path's Pallas kernels for a described v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles
each kernel for a chip that is described, not attached. Interpret-mode
parity tests cannot see what the chip's compiler refuses (block layouts,
1-D vectors, float iotas, scoped VMEM), so every kernel is compiled here
at the serving shapes of ``chip_smoke.py`` (B=64 and B=1 slices of
217x181) and the resident kernels also at their dispatch bounds. The
serving routes' reference programs, which run no kernel, are compiled
at bucket 64 too: a degraded chunk must not meet the chip's compiler
for the first time when its fast program fails.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fcm_centers as KC
from repro.kernels import fcm_resident as KR
from repro.kernels import ops as kops
from repro.kernels import slic_assign as KSL
from repro.superpixel import slic as SL

H, W = 217, 181                 # the serving slice
N = H * W
C = 4                           # configs/fcm_brainweb
GY, GX = SL.grid_shape(H, W, 256)   # the superpixel route's center grid


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _f32(*shape):
    return shape, jnp.float32


def _tiles(rows, multiple=1):
    """128-row tiles after padding to a ``multiple`` of them."""
    return -(-rows // (multiple * 128)) * multiple


@pytest.mark.parametrize("b", [64, 1])
def test_bin_and_labels_compile(one_chip, b):
    _compile(one_chip, lambda px: kops.histogram_counts(px, 256,
                                                        interpret=False),
             ((b, N), jnp.uint8))
    _compile(one_chip, lambda xs, v: kops.defuzzify_labels_batched(
        xs, v, impl="pallas", interpret=False), _f32(b, N), _f32(b, C))


# (b, rows, c, D): the histogram route's 256 bins at its serving
# buckets, then the kernel's dispatch bound at c=8, D=8.
@pytest.mark.parametrize("b,rows,c,d", [(64, 256, C, 1), (1, 256, C, 1),
                                        (1, KR.MAX_ROWS, KR.MAX_C,
                                         KR.MAX_FEAT)])
def test_resident_compiles(one_chip, b, rows, c, d):
    r = _tiles(rows)
    _compile(one_chip, lambda x, w, v0, tol: KR.resident_solve_pallas(
        x, w, v0, tol, 2.0, 300), _f32(b, d, r, 128), _f32(b, r, 128),
        _f32(b, c, d), _f32(b))


# The pixel route (one feature per pixel), the superpixel route's
# K=gy*gx RGB rows, then the dispatch bound at c=8, D=8.
@pytest.mark.parametrize("b,rows,c,d", [
    (64, N, C, 1), (1, N, C, 1), (64, GY * GX, C, 3), (1, GY * GX, C, 3),
    (1, KR.STREAM_MAX_ROWS, KR.MAX_C, KR.MAX_FEAT)])
def test_resident_streamed_compiles(one_chip, b, rows, c, d):
    r = _tiles(rows, KR.STREAM_CHUNK_ROWS)
    _compile(one_chip, lambda x, w, v0, tol:
             KR.resident_streamed_solve_pallas(x, w, v0, tol, 2.0, 300),
             _f32(b, d, r, 128), _f32(b, r, 128), _f32(b, c, d), _f32(b))


# The spatial route's padded 217x181 slice (8 neighbors), then the
# dispatch bound: 65,536 padded pixels at c=8.
@pytest.mark.parametrize("b,h,w,c", [(64, H, W, C), (1, H, W, C),
                                     (1, 256, 256, KR.STENCIL_MAX_C)])
def test_resident_stencil_compiles(one_chip, b, h, w, c):
    hp, wp = h + (-h) % 8, w + (-w) % 128
    assert KR.stencil_pixels((h, w)) <= KR.STENCIL_MAX_PIXELS
    _compile(one_chip, lambda x, v, v0, tol:
             KR.resident_stencil_solve_pallas(x, v, v0, tol, 2.0, 1.0, 8,
                                              300),
             _f32(b, hp, wp), _f32(b, hp, wp), _f32(b, c), _f32(b))


def test_slic_assign_compiles(one_chip):
    """The superpixel route's ingest: 217x181 RGB, n_segments=256."""
    k = GY * GX
    br = KSL.auto_block_rows(k, W)
    hp, wp = H + (-H) % br, W + (-W) % 128
    _compile(one_chip, lambda xp, cen: KSL.slic_assign_pallas(
        xp, cen, GY, GX, H / GY, W / GX, 0.5, br), _f32(3, hp, wp),
        _f32(k, 5))


def test_fused_partials_compiles(one_chip):
    """The paper's 1 MiB case runs the per-step fused kernel."""
    m = (1 << 20) // 128
    _compile(one_chip, lambda x, w, v: KC.fused_partials_pallas(
        x, w, v, 2.0, 64), _f32(m, 128), _f32(m, 128), _f32(C))


@pytest.mark.parametrize("route", ["histogram", "pixel", "spatial",
                                   "superpixel"])
def test_reference_program_compiles(one_chip, route):
    """The launch a breaker-degraded or salvaged 64-lane bucket of
    217x181 slices runs: the route's program on the registry's
    reference impls."""
    from repro.serving import fcm_engine as E

    k = GY * GX
    key, shapes = {
        "histogram": (("px", N), [((64, N), jnp.uint8), _f32(64, 256)]),
        "pixel": (("px", H, W), [_f32(64, N)]),
        "spatial": (("sp", np.dtype(np.uint8), H, W),
                    [((64, N), jnp.uint8)]),
        "superpixel": (("spx", k, 3), [_f32(64, k, 3), _f32(64, k)]),
    }[route]
    prog = E.ROUTES[route].make_program(E.FCMServeEngine(), key, 64,
                                        reference=True)
    assert prog.impls and all(i.endswith("/reference") for i in prog.impls)
    prog.launch.lower(*[jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                        for s, dt in shapes]).compile()
