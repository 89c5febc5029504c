"""Pure-jnp oracle for the fused LBM stream+collide step.

One fused update of a single block array ``f`` of shape ``(Q, X, Y, Z)``
holding *post-collision* PDFs:

1. **pull streaming** with halfway bounce-back: the population arriving at
   cell ``x`` along ``c_q`` is ``f_q(x - c_q)`` if the source cell is fluid;
   if the source is a wall, it is the reflected own population
   ``f_opp(q)(x)`` plus the moving-wall momentum term
   ``6 w_q (c_q . u_wall)`` (velocity bounce-back, paper §5.1.1's lid);
2. **collision**: BGK or TRT (magic parameter 3/16, paper §5.2).

Rolls wrap around array edges, so with an all-fluid mask the block behaves
as a fully periodic box (used by the physics tests); in the AMR driver the
outermost layer is a ghost layer refreshed by halo exchange before every
step, making the wrapped values irrelevant.

Cell types: 0 = fluid, 1 = no-slip obstacle, 2 = moving wall (``u_wall``).
Non-fluid cells keep their PDF values unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...lbm.lattice import D3Q19, Lattice

__all__ = [
    "stream_collide_ref",
    "stream_collide_coeffs",
    "collision_coeffs",
    "precompute_stream_masks",
    "equilibrium",
    "moments",
    "CT_FLUID",
    "CT_WALL",
    "CT_LID",
]

CT_FLUID = 0
CT_WALL = 1
CT_LID = 2

# The moment contractions must be float32-exact: at default precision the TPU
# runs an f32 dot as one bfloat16 pass, which would make the oracle less
# accurate than the kernel it checks. CPU backends ignore the setting.
_F32 = jax.lax.Precision.HIGHEST


def moments(f: jnp.ndarray, lattice: Lattice) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Density (X,Y,Z) and velocity (3,X,Y,Z) from PDFs (Q,X,Y,Z)."""
    c = jnp.asarray(lattice.c, dtype=f.dtype)  # (Q,3)
    rho = jnp.sum(f, axis=0)
    mom = jnp.einsum("qxyz,qd->dxyz", f, c, precision=_F32)
    u = mom / rho[None]
    return rho, u


def equilibrium(rho: jnp.ndarray, u: jnp.ndarray, lattice: Lattice) -> jnp.ndarray:
    """Second-order Maxwell equilibrium, shape (Q, X, Y, Z)."""
    c = jnp.asarray(lattice.c, dtype=rho.dtype)  # (Q,3)
    w = jnp.asarray(lattice.w, dtype=rho.dtype)  # (Q,)
    cu = jnp.einsum("qd,dxyz->qxyz", c, u, precision=_F32)  # (Q,X,Y,Z)
    usq = jnp.sum(u * u, axis=0)  # (X,Y,Z)
    return w[:, None, None, None] * rho[None] * (
        1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq[None]
    )


def collision_coeffs(
    omega: float,
    *,
    lattice: Lattice = D3Q19,
    u_wall: tuple[float, float, float] = (0.0, 0.0, 0.0),
    collision: str = "bgk",
    magic: float = 3.0 / 16.0,
    dtype=np.float32,
) -> dict[str, np.ndarray]:
    """Host-derived per-step scalar coefficients for :func:`stream_collide_coeffs`.

    Every omega/u_wall-dependent quantity the kernel consumes is reduced here
    to a small set of dtype-precision scalars (and one ``(Q,)`` lid vector),
    computed in float64 exactly like the original closure path. The kernel
    then only ever combines them as ``coefficient * array``, so passing them
    as *traced* operands (the batched ensemble path, one value per member)
    does the same arithmetic as baking them in as constants (the single-run
    path): the rounding to ``dtype`` happens here, once, either way.
    """
    c = np.asarray(lattice.c)  # repro: host-ok(lattice constants are host numpy, folded into the program)
    w = np.asarray(lattice.w)  # repro: host-ok(lattice constants are host numpy, folded into the program)
    uw = np.asarray(u_wall, dtype=np.float64)  # repro: host-ok(lattice constants are host numpy, folded into the program)
    # velocity bounce-back momentum term per direction: 6 w_q (c_q . u_wall)
    lid = np.array(
        [6.0 * w[q] * float(c[q] @ uw) for q in range(lattice.Q)], dtype=dtype
    )
    if collision == "bgk":
        return {"lid": lid, "om": dtype(omega)}
    if collision == "trt":
        tau_plus = 1.0 / omega
        tau_minus = magic / (tau_plus - 0.5) + 0.5
        return {
            "lid": lid,
            "om_p": dtype(1.0 / tau_plus),
            "om_m": dtype(1.0 / tau_minus),
        }
    raise ValueError(f"unknown collision model {collision!r}")


def precompute_stream_masks(mask, lattice: Lattice = D3Q19) -> dict[str, np.ndarray]:
    """Hoist the mask-derived streaming selectors out of the kernel.

    The cell-type mask only changes at AMR events, yet the kernel re-rolls it
    (and re-compares against the cell-type codes) for every direction, every
    substep. For the compiled superstep paths — where the mask is a build-time
    constant — this precomputes, on the host, exactly the booleans the kernel
    derives: ``fluid_src[q]`` / ``lid_src[q]`` are the rolled-mask comparisons
    for direction ``q``, ``fluid`` is the local-cell selector. Feeding them
    through :func:`stream_collide_coeffs`'s ``premask`` argument does the
    same arithmetic (identical booleans drive identical selects).

    ``mask`` may be a single block ``(X, Y, Z)`` or a stack ``(B, X, Y, Z)``;
    rolls act on the trailing three axes and the ``q`` axis leads:
    ``fluid_src``/``lid_src`` are ``(Q, *mask.shape)`` bool.
    """
    # repro: host-ok(mask selector precompute is host-side by design, once per program build)
    m = np.asarray(mask)
    Q = lattice.Q
    c = np.asarray(lattice.c)  # repro: host-ok(lattice constants are host numpy, folded into the program)
    fluid_src = np.empty((Q,) + m.shape, dtype=bool)
    lid_src = np.empty((Q,) + m.shape, dtype=bool)
    for q in range(Q):
        rolled = np.roll(
            m, shift=(int(c[q, 0]), int(c[q, 1]), int(c[q, 2])), axis=(-3, -2, -1)
        )
        fluid_src[q] = rolled == CT_FLUID
        lid_src[q] = rolled == CT_LID
    return {"fluid_src": fluid_src, "lid_src": lid_src, "fluid": m == CT_FLUID}


def stream_collide_coeffs(
    f: jnp.ndarray,
    mask: jnp.ndarray | None,
    coeffs: dict,
    *,
    lattice: Lattice = D3Q19,
    collision: str = "bgk",
    premask: dict | None = None,
) -> jnp.ndarray:
    """One fused stream+collide step on a single block (Q, X, Y, Z).

    ``coeffs`` comes from :func:`collision_coeffs` and may hold either host
    scalars (closed over as constants — the classic path) or traced arrays
    (per-member physics parameters under ``vmap`` — the ensemble path); both
    execute the identical op sequence. When ``premask`` (from
    :func:`precompute_stream_masks`) is given, the mask rolls/compares are
    skipped in favor of the precomputed selectors and ``mask`` may be None.
    """
    dtype = f.dtype
    Q = lattice.Q
    c = np.asarray(lattice.c)  # repro: host-ok(lattice constants are host numpy, folded into the program)
    opp = np.asarray(lattice.opposite)  # repro: host-ok(lattice constants are host numpy, folded into the program)
    lid = coeffs["lid"]

    # -- pull streaming with bounce-back ------------------------------------
    f_in = []
    for q in range(Q):
        cq = c[q]
        pulled = jnp.roll(f[q], shift=(int(cq[0]), int(cq[1]), int(cq[2])), axis=(0, 1, 2))
        if premask is not None:
            is_fluid_src = premask["fluid_src"][q]
            is_lid_src = premask["lid_src"][q]
        else:
            src_mask = jnp.roll(
                mask, shift=(int(cq[0]), int(cq[1]), int(cq[2])), axis=(0, 1, 2)
            )
            is_fluid_src = src_mask == CT_FLUID
            is_lid_src = src_mask == CT_LID
        bounced = f[opp[q]] + lid[q] * is_lid_src.astype(dtype)
        f_in.append(jnp.where(is_fluid_src, pulled, bounced))
    f_in = jnp.stack(f_in)

    # -- collision -------------------------------------------------------------
    rho, u = moments(f_in, lattice)
    feq = equilibrium(rho, u, lattice)
    if collision == "bgk":
        f_out = f_in + coeffs["om"] * (feq - f_in)
    elif collision == "trt":
        om_p = coeffs["om_p"]
        om_m = coeffs["om_m"]
        f_opp_in = f_in[opp]
        feq_opp = feq[opp]
        f_plus = 0.5 * (f_in + f_opp_in)
        f_minus = 0.5 * (f_in - f_opp_in)
        feq_plus = 0.5 * (feq + feq_opp)
        feq_minus = 0.5 * (feq - feq_opp)
        f_out = f_in - om_p * (f_plus - feq_plus) - om_m * (f_minus - feq_minus)
    else:
        raise ValueError(f"unknown collision model {collision!r}")

    if premask is not None:
        fluid = jnp.asarray(premask["fluid"])[None].astype(dtype)
    else:
        fluid = (mask == CT_FLUID)[None].astype(dtype)
    return f_out * fluid + f * (1 - fluid)


def stream_collide_ref(
    f: jnp.ndarray,
    mask: jnp.ndarray,
    omega: float,
    lattice: Lattice = D3Q19,
    u_wall: tuple[float, float, float] = (0.0, 0.0, 0.0),
    collision: str = "bgk",
    magic: float = 3.0 / 16.0,
) -> jnp.ndarray:
    """One fused stream+collide step on a single block (Q, X, Y, Z)."""
    coeffs = collision_coeffs(
        omega,
        lattice=lattice,
        u_wall=u_wall,
        collision=collision,
        magic=magic,
        dtype=f.dtype.type,
    )
    return stream_collide_coeffs(f, mask, coeffs, lattice=lattice, collision=collision)
