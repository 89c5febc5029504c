"""Pallas TPU kernel: fused LBM stream+collide, one AMR block per grid step.

TPU adaptation of the paper's compute hot loop (§3/§5: the D3Q19/D3Q27
stream-collide accounts for nearly all FLOPs of the simulation):

* The AMR domain partitioning already tiles the mesh into fixed-size blocks.
  We map **one AMR block per Pallas grid step**: ``grid=(num_blocks,)`` with
  a full-block BlockSpec, so each step runs out of VMEM with a single HBM
  round-trip per block (the kernel is memory-bound, AI ~ 1.5 flop/byte).
  Whole-block tiles bound the block size. On a v5e an 18^3 tile (16^3 cells
  plus the ghost layer, f32 D3Q19) needs about 16 MiB of scoped VMEM, so the
  kernel raises the scoped limit to :data:`VMEM_LIMIT_BYTES` (32 MiB); a 34^3
  tile (32^3 cells, the paper's Fig. 16 size) needs over 40 MiB, exceeds
  both the default 16 MiB limit and this one, and is refused at compile
  time.
* Streaming is realized as static single-cell rolls of VMEM-resident planes
  (vector shifts on the VPU — no MXU work exists in LBM), fused with the
  collision so PDFs are read and written exactly once per time step.
* The ghost layer travels with the block; the kernel leaves halo exchange
  entirely to the halo/driver layer (the halo-aware superstep programs fill
  ghosts with one XLA scatter right before calling it).

The kernel is validated against ``ref.stream_collide_ref`` in interpret
mode on CPU; on TPU the same ``pallas_call`` lowers natively (Mosaic) with
the block resident in VMEM. Whether to interpret is resolved once at
program-build time from the active JAX backend (see :func:`resolve_interpret`).
For best TPU layout the innermost (Z) extent should be padded to the
128-lane width by the caller; correctness does not depend on it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...lbm.lattice import D3Q19, Lattice
from .ref import CT_FLUID, CT_LID

__all__ = [
    "lbm_stream_collide_pallas",
    "resolve_interpret",
]

# Scoped-VMEM budget for one whole-block tile. An 18^3 f32 D3Q19 tile needs
# just over 16 MiB (the default scoped limit) on a v5e once the grid has more
# than a handful of blocks; the chip has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve the Pallas ``interpret`` flag once, at program-build time.

    ``None`` (the default everywhere) means "interpret exactly when the
    active JAX backend is CPU": on a real TPU/GPU the kernel lowers natively,
    on this CPU-only container it runs the interpreter. Passing an explicit
    bool overrides the backend probe (e.g. forcing interpret mode on an
    accelerator to debug a lowering issue). Callers resolve *before* closing
    the flag into a jitted program so the decision is taken exactly once per
    program build, not per trace."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _roll(a, shift: tuple[int, int, int]):
    """``jnp.roll`` over the axes with a non-zero shift only: a zero shift
    lowers to a zero-size slice, which Mosaic refuses."""
    axes = tuple(i for i, s in enumerate(shift) if s)
    if not axes:
        return a
    return jnp.roll(a, shift=tuple(shift[i] for i in axes), axis=axes)


def _stream_collide_body(
    f,
    mask,
    *,
    lattice: Lattice,
    omega: float,
    u_wall: tuple[float, float, float],
    collision: str,
    magic: float,
):
    """Shared stream+collide body on one VMEM-resident (Q, X, Y, Z) block."""
    dtype = f.dtype
    Q = lattice.Q
    c = np.asarray(lattice.c)  # repro: host-ok(lattice constants are host numpy, baked into the traced program)
    w = np.asarray(lattice.w)  # repro: host-ok(lattice constants are host numpy, baked into the traced program)
    opp = np.asarray(lattice.opposite)  # repro: host-ok(lattice constants are host numpy, baked into the traced program)
    uw = np.asarray(u_wall, dtype=np.float64)  # repro: host-ok(lattice constants are host numpy, baked into the traced program)

    is_fluid_src = []
    pulled = []
    for q in range(Q):
        sh = (int(c[q, 0]), int(c[q, 1]), int(c[q, 2]))
        pulled.append(_roll(f[q], sh))
        is_fluid_src.append(_roll(mask, sh))

    f_in = []
    for q in range(Q):
        lid_term = dtype.type(6.0 * w[q] * float(c[q] @ uw))
        bounced = f[opp[q]] + lid_term * (is_fluid_src[q] == CT_LID).astype(dtype)
        f_in.append(jnp.where(is_fluid_src[q] == CT_FLUID, pulled[q], bounced))

    # moments (unrolled over Q -> pure VPU element-wise work)
    rho = f_in[0]
    for q in range(1, Q):
        rho = rho + f_in[q]
    ux = uy = uz = jnp.zeros_like(rho)
    for q in range(Q):
        if c[q, 0]:
            ux = ux + dtype.type(float(c[q, 0])) * f_in[q]
        if c[q, 1]:
            uy = uy + dtype.type(float(c[q, 1])) * f_in[q]
        if c[q, 2]:
            uz = uz + dtype.type(float(c[q, 2])) * f_in[q]
    inv_rho = 1.0 / rho
    ux, uy, uz = ux * inv_rho, uy * inv_rho, uz * inv_rho
    usq = ux * ux + uy * uy + uz * uz

    feq = []
    for q in range(Q):
        cu = jnp.zeros_like(rho)
        if c[q, 0]:
            cu = cu + dtype.type(float(c[q, 0])) * ux
        if c[q, 1]:
            cu = cu + dtype.type(float(c[q, 1])) * uy
        if c[q, 2]:
            cu = cu + dtype.type(float(c[q, 2])) * uz
        feq.append(
            dtype.type(w[q])
            * rho
            * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq)
        )

    if collision == "bgk":
        om = dtype.type(omega)
        f_out = [f_in[q] + om * (feq[q] - f_in[q]) for q in range(Q)]
    elif collision == "trt":
        tau_plus = 1.0 / omega
        tau_minus = magic / (tau_plus - 0.5) + 0.5
        om_p = dtype.type(1.0 / tau_plus)
        om_m = dtype.type(1.0 / tau_minus)
        f_out = []
        for q in range(Q):
            qo = int(opp[q])
            f_p = 0.5 * (f_in[q] + f_in[qo])
            f_m = 0.5 * (f_in[q] - f_in[qo])
            fe_p = 0.5 * (feq[q] + feq[qo])
            fe_m = 0.5 * (feq[q] - feq[qo])
            f_out.append(f_in[q] - om_p * (f_p - fe_p) - om_m * (f_m - fe_m))
    else:
        raise ValueError(f"unknown collision model {collision!r}")

    fluid = (mask == CT_FLUID).astype(dtype)
    return jnp.stack([f_out[q] * fluid + f[q] * (1 - fluid) for q in range(Q)])


def _kernel(f_ref, mask_ref, out_ref, **cfg):
    f = f_ref[0]  # (Q, X, Y, Z) resident in VMEM
    mask = mask_ref[0]  # (X, Y, Z)
    out_ref[0] = _stream_collide_body(f, mask, **cfg)


def _cfg(omega, lattice, u_wall, collision, magic):
    return dict(
        lattice=lattice,
        omega=float(omega),
        u_wall=tuple(float(v) for v in u_wall),
        collision=collision,
        magic=float(magic),
    )


def lbm_stream_collide_pallas(
    f: jax.Array,
    mask: jax.Array,
    *,
    omega: float,
    lattice: Lattice = D3Q19,
    u_wall: tuple[float, float, float] = (0.0, 0.0, 0.0),
    collision: str = "bgk",
    magic: float = 3.0 / 16.0,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused stream+collide over a stack of blocks.

    Args:
      f:    (B, Q, X, Y, Z) post-collision PDFs (ghost layer included).
      mask: (B, X, Y, Z) int32 cell types (0 fluid / 1 wall / 2 lid).
      interpret: None (default) resolves per :func:`resolve_interpret`.
    Returns:
      (B, Q, X, Y, Z) updated PDFs.
    """
    B, Q, X, Y, Z = f.shape
    assert mask.shape == (B, X, Y, Z), (f.shape, mask.shape)
    kern = functools.partial(_kernel, **_cfg(omega, lattice, u_wall, collision, magic))
    # a stable name for the kernel's custom call in every program that calls
    # it; profile events carry the whole HLO instruction, operands included,
    # so the name must not also be one the trace reduction looks for in the
    # text (``stream_collide``), or every op reading the kernel's output
    # would count as kernel time
    return pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Q, X, Y, Z), lambda b: (b, 0, 0, 0, 0)),
            pl.BlockSpec((1, X, Y, Z), lambda b: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, X, Y, Z), lambda b: (b, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(f.shape, f.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name="lbm_collide",
    )(f, mask)
