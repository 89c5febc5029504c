"""Pallas kernel sweeps: shapes x dtypes x lattices x collision models vs the
pure-jnp oracle (interpret mode on CPU)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.lbm_collide.ops import fused_stream_collide
from repro.kernels.lbm_collide.ref import CT_FLUID, CT_LID, CT_WALL
from repro.lbm.lattice import D3Q19, D3Q27


def _random_state(rng, B, lattice, shape, dtype):
    w = np.asarray(lattice.w, dtype=dtype)
    f = w[None, :, None, None, None] * (
        1.0 + 0.05 * rng.standard_normal((B, lattice.Q, *shape))
    ).astype(dtype)
    mask = np.zeros((B, *shape), np.int32)
    mask[:, 0] = CT_WALL
    mask[:, -1] = CT_LID
    mask[:, :, 0] = CT_WALL
    return jnp.asarray(f), jnp.asarray(mask)


@pytest.mark.parametrize("lattice", [D3Q19, D3Q27], ids=["d3q19", "d3q27"])
@pytest.mark.parametrize("collision", ["bgk", "trt"])
@pytest.mark.parametrize(
    "shape", [(4, 4, 4), (8, 6, 10), (5, 7, 3)], ids=["cube", "rect", "odd"]
)
def test_pallas_matches_ref(lattice, collision, shape):
    rng = np.random.default_rng(42)
    f, mask = _random_state(rng, 2, lattice, shape, np.float32)
    kw = dict(
        omega=1.55,
        lattice=lattice,
        collision=collision,
        u_wall=(0.04, 0.01, 0.0),
    )
    out_p = fused_stream_collide(f, mask, backend="pallas", **kw)
    out_r = fused_stream_collide(f, mask, backend="ref", **kw)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), rtol=3e-5, atol=3e-6)


@pytest.mark.parametrize("dtype", [np.float32, np.float64], ids=["f32", "f64"])
def test_pallas_dtype_sweep(dtype):
    import jax

    with jax.enable_x64(True) if dtype == np.float64 else _null():
        rng = np.random.default_rng(7)
        f, mask = _random_state(rng, 1, D3Q19, (6, 6, 6), dtype)
        kw = dict(omega=1.2, lattice=D3Q19, collision="bgk")
        out_p = fused_stream_collide(f, mask, backend="pallas", **kw)
        out_r = fused_stream_collide(f, mask, backend="ref", **kw)
        tol = 1e-12 if dtype == np.float64 else 3e-6
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), rtol=tol * 10, atol=tol)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


@pytest.mark.parametrize("omega", [0.6, 1.0, 1.9])
def test_pallas_omega_sweep(omega):
    rng = np.random.default_rng(0)
    f, mask = _random_state(rng, 3, D3Q19, (6, 6, 6), np.float32)
    out_p = fused_stream_collide(f, mask, backend="pallas", omega=omega)
    out_r = fused_stream_collide(f, mask, backend="ref", omega=omega)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), rtol=3e-5, atol=3e-6)


def test_wall_cells_frozen_and_lid_injects_momentum():
    rng = np.random.default_rng(1)
    f, mask = _random_state(rng, 1, D3Q19, (8, 8, 8), np.float32)
    out = fused_stream_collide(
        f, mask, backend="pallas", omega=1.5, u_wall=(0.1, 0.0, 0.0)
    )
    m = np.asarray(mask[0])
    fo, fi = np.asarray(out[0]), np.asarray(f[0])
    # wall/lid cells keep their PDFs
    np.testing.assert_allclose(fo[:, m != CT_FLUID], fi[:, m != CT_FLUID])
    # fluid next to the moving lid gains x-momentum
    c = np.asarray(D3Q19.c, np.float32)
    mom_x = np.einsum("qxyz,q->xyz", fo, c[:, 0])
    assert mom_x[-2][m[-2] == CT_FLUID].mean() > 1e-5


@pytest.mark.parametrize(
    "backend,want_interpret",
    [("cpu", True), ("gpu", False), ("tpu", False)],
)
def test_build_time_flag_resolution_per_backend(monkeypatch, backend, want_interpret):
    """Pin the build-time resolution of the kernel's ``interpret`` flag.

    ``interpret=None`` must resolve to "interpret iff CPU" (the old hardwired
    ``interpret=True`` silently ran the Pallas interpreter on accelerators).
    Explicit bools always win over the backend probe.
    """
    import jax

    from repro.kernels.lbm_collide.lbm_collide import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(None) is want_interpret
    # explicit overrides ignore the backend entirely
    for flag in (True, False):
        assert resolve_interpret(flag) is flag


def test_flag_resolution_happens_at_build_time(monkeypatch):
    """The backend probe runs when the program is built, not when it runs.

    Build a fused superstep under a monkeypatched backend, then restore it:
    the program must keep the resolution it was built with (here: the probe
    is consulted during ``make_fused_superstep``, so patching afterwards has
    no effect on the built program's kernels).
    """
    from repro.kernels.lbm_collide import ops

    calls = []
    real = ops.resolve_interpret
    monkeypatch.setattr(
        ops, "resolve_interpret", lambda v=None: calls.append(v) or real(v)
    )
    ops.make_stream_collide(omega=1.6, backend="pallas")
    assert calls, "make_stream_collide must resolve interpret at build time"
