"""Validate the trip-count-scaling HLO analyzer against unrolled oracles."""
import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import (analyze_hlo, buffer_shapes,
                                       materializes_shape)


def _flops(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return analyze_hlo(txt)


def test_scan_matches_unroll_flops():
    def f_scan(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    def f_unroll(x, w):
        for i in range(8):
            x = jnp.tanh(x @ w[i])
        return x

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    s_scan = _flops(f_scan, x, w)
    s_unr = _flops(f_unroll, x, w)
    analytic = 2 * 128 * 256 * 256 * 8
    assert s_scan.flops == analytic, (s_scan.flops, analytic)
    assert s_unr.flops == analytic
    assert s_scan.while_trips == [8]


def test_nested_scan_flops():
    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return jnp.sin(c2 @ wi), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, w)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    s = _flops(f, x, w)
    analytic = 2 * 64 * 64 * 64 * 3 * 5
    assert s.flops == analytic, (s.flops, analytic)


def test_batched_dot_flops():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 32, 48), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 48, 16), jnp.float32)
    s = _flops(f, a, b)
    assert s.flops == 2 * 4 * 32 * 48 * 16


def test_bytes_scale_with_trip_count():
    def f(x):
        def body(c, _):
            return c * 2.0 + 1.0, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    s = _flops(f, x)
    # each iteration reads + writes ~4MB; 10 iterations >= 80MB
    assert s.bytes_accessed >= 10 * 2 * 4 * 1024 * 1024 * 0.9


def test_buffer_shapes_and_materializes_shape():
    def f(a, b):
        return (a @ b).T  # transposed output: axis order must not matter

    a = jax.ShapeDtypeStruct((17, 23), jnp.float32)
    b = jax.ShapeDtypeStruct((23, 5), jnp.float32)
    txt = jax.jit(f).lower(a, b).compile().as_text()
    shapes = {s for _, s in buffer_shapes(txt)}
    assert (17, 23) in shapes and (23, 5) in shapes
    assert materializes_shape(txt, (17, 5))   # the product, any layout
    assert materializes_shape(txt, (5, 17))   # ... order-insensitive
    assert not materializes_shape(txt, (17, 23, 5))


def test_fused_level_step_never_materializes_dense_field():
    """The tentpole claim, statically: the fused level-step lowering never
    even NAMES an (X, Y, Z, 3)-extent buffer — the dense displacement field
    exists only as per-block VMEM tiles — while the unfused composition
    (the positive control, proving the probe can see it) does.  Block tiles
    are pinned below the full grid so the per-block shapes cannot
    accidentally equal the dense field's."""
    import numpy as np

    from repro.core import ffd
    from repro.kernels import ops

    vol, tile = (12, 11, 9), (3, 3, 3)
    g = ffd.grid_shape_for_volume(vol, tile)
    rng = np.random.default_rng(0)
    phi = jnp.asarray(rng.standard_normal(g + (3,)), jnp.float32)
    mov = jnp.asarray(rng.random(vol), jnp.float32)
    fix = jnp.asarray(rng.random(vol), jnp.float32)

    def fused(p, m, f):
        return ops.fused_similarity_loss(p, m, f, tile, sim_spec=("ssd",),
                                         block_tiles=(1, 1, 1))

    def unfused(p, m, f):
        disp = ffd.dense_field(p, tile, vol)
        return jnp.mean((ffd.warp_volume(m, disp) - f) ** 2)

    fused_txt = jax.jit(fused).lower(phi, mov, fix).compile().as_text()
    unfused_txt = jax.jit(unfused).lower(phi, mov, fix).compile().as_text()
    assert not materializes_shape(fused_txt, vol + (3,))
    assert materializes_shape(unfused_txt, vol + (3,))


def test_collective_bytes_counted_inside_loops():
    """Needs >1 device -> fresh process with forced host devices."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as PS
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("d",))
        def f(x):
            def body(c, _):
                s = jax.lax.with_sharding_constraint(c, PS("d", None))
                return jnp.tanh(s @ s.T @ s), None
            y, _ = jax.lax.scan(body, x, None, length=4)
            return y
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        with jax.set_mesh(mesh):
            txt = jax.jit(f).lower(x).compile().as_text()
        s = analyze_hlo(txt)
        n = sum(s.collective_counts.values())
        assert n > 0, "expected collectives inside the loop"
        assert all(c % 4 == 0 for c in s.collective_counts.values() if c), s.collective_counts
        print("COLL_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "COLL_OK" in r.stdout, r.stderr[-2000:]
