"""Jitted public wrapper around the fused LBM stream+collide kernel.

Dispatches between the Pallas kernel (TPU target; interpret mode on CPU) and
the pure-jnp reference (oracle / fallback). All simulation-constant
parameters (lattice, omega, wall velocity, collision model) are closed over
so the jitted step takes only the block stack and the mask. Whether the
Pallas path interprets is resolved once at program-build time from the
active JAX backend (:func:`~.lbm_collide.resolve_interpret`).

The compiled superstep paths here implement the halo-in-tile data plane:
ghost exchange is merged into one fill per destination level
(:func:`~repro.lbm.halo.lower_halo_fill`) and fused into the same program
as the stencil (:func:`make_halo_stream_collide`), the double-buffered pdf
tuples are donated (``donate_argnums``) so each substep ping-pongs in
place, and the rank-sharded absorb can split into interior/boundary
programs so cross-rank payload routing overlaps interior stepping.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...lbm.halo import (
    SLAB_DIRECTIONS,
    LevelSlabFill,
    lower_halo_fill,
    lower_slab_placement,
    slab_slices,
    split_halo_fill,
)
from ...lbm.lattice import D3Q19, Lattice
from ...telemetry import get_tracer
from .lbm_collide import lbm_stream_collide_pallas, resolve_interpret
from .ref import (
    collision_coeffs,
    precompute_stream_masks,
    stream_collide_coeffs,
    stream_collide_ref,
)

_TR = get_tracer()

__all__ = [
    "fused_stream_collide",
    "make_stream_collide",
    "make_arena_stream_collide",
    "make_halo_stream_collide",
    "apply_compiled_ghost_plan",
    "make_fused_superstep",
    "make_ensemble_superstep",
    "make_rank_emit",
    "make_rank_absorb",
    "make_rank_absorb_split",
    "make_device_superstep",
    "boundary_slot_sets",
    "resolve_interpret",
]


def make_stream_collide(
    *,
    omega: float,
    lattice: Lattice = D3Q19,
    u_wall: tuple[float, float, float] = (0.0, 0.0, 0.0),
    collision: str = "bgk",
    backend: str = "pallas",  # "pallas" | "ref"
    interpret: bool | None = None,
):
    """Build a jitted ``step(f_blocks, mask_blocks) -> f_blocks`` function.

    ``interpret=None`` (the default) resolves to "interpret iff the active
    backend is CPU", once, here at build time — the flag is then baked into
    the program, so a process that starts on TPU lowers the kernel natively
    without every call site having to thread the decision through."""

    if backend == "pallas":
        interpret = resolve_interpret(interpret)

        @jax.jit
        def step(f: jax.Array, mask: jax.Array) -> jax.Array:
            return lbm_stream_collide_pallas(
                f,
                mask,
                omega=omega,
                lattice=lattice,
                u_wall=u_wall,
                collision=collision,
                interpret=interpret,
            )

    elif backend == "ref":
        ref = functools.partial(
            stream_collide_ref,
            omega=omega,
            lattice=lattice,
            u_wall=u_wall,
            collision=collision,
        )

        @jax.jit
        def step(f: jax.Array, mask: jax.Array) -> jax.Array:
            return jax.vmap(ref)(f, mask)

    else:
        raise ValueError(f"unknown backend {backend!r}")

    return step


def make_arena_stream_collide(
    *,
    omega: float,
    lattice: Lattice = D3Q19,
    u_wall: tuple[float, float, float] = (0.0, 0.0, 0.0),
    collision: str = "bgk",
    backend: str = "pallas",
    interpret: bool | None = None,
):
    """Arena entry point: an in-place ``step(f_buf, mask) -> None`` over a
    persistent :class:`~repro.core.fields.LevelArena` buffer.

    ``f_buf`` is the level's contiguous ``(B, Q, X, Y, Z)`` SoA buffer; it is
    handed to the fused kernel whole (one host->device transfer, no
    per-block restacking) and the result is written back into the same
    buffer, so all per-block views bound by the arena stay valid. ``mask``
    may be a precomputed device array — masks only change on AMR events, so
    callers can cache the transfer across substeps.
    """
    step = make_stream_collide(
        omega=omega,
        lattice=lattice,
        u_wall=u_wall,
        collision=collision,
        backend=backend,
        interpret=interpret,
    )

    def step_arena(f_buf: np.ndarray, mask: jax.Array | np.ndarray) -> None:
        out = step(jnp.asarray(f_buf), jnp.asarray(mask))
        # repro: host-ok(arena-mode copy-out contract: results land in the host arena each step)
        np.copyto(f_buf, np.asarray(out))

    return step_arena


# -- halo-in-tile stepping -------------------------------------------------------


def make_halo_stream_collide(
    dst_slot: np.ndarray,
    dst_cell: np.ndarray,
    *,
    level: int,
    mask: np.ndarray,
    omega: float,
    lattice: Lattice = D3Q19,
    u_wall: tuple[float, float, float] = (0.0, 0.0, 0.0),
    collision: str = "bgk",
    magic: float = 3.0 / 16.0,
    backend: str = "pallas",
    interpret: bool | None = None,
    slabs=None,
):
    """Build a halo-aware ``step(f, vals) -> f`` for one level's block stack:
    the ghost fill targeting (``dst_slot``, ``dst_cell``) and the
    stream+collide stencil run in one program, so no exchanged buffer is
    materialized between substep programs.

    ``vals`` is the ``(N, Q)`` concatenated fill values (gathered by the
    enclosing superstep from pre-step buffers, in the merged fill's segment
    order). Without ``slabs`` the fill is a single merged XLA scatter. With
    ``slabs`` (a :class:`~repro.lbm.halo.LevelSlabFill`) the ghost slabs of
    same-level neighbours are block-granular copies read from ``f`` itself,
    ``vals`` fills only the remaining targets (``None`` when there are
    none), and the stack is assembled in one pass (:func:`_halo_fill`).
    The filled stack feeds the Pallas kernel (``pallas``) or the jnp stencil
    (``ref``).
    On ``ref`` the mask being a build-time constant lets the streaming
    selectors be precomputed on the host
    (:func:`~.ref.precompute_stream_masks`), dropping the per-substep mask
    rolls entirely.

    ``mask`` is the level's host ``(B, X, Y, Z)`` cell-type stack, closed
    over as a constant (programs are rebuilt on mask refresh / AMR events).
    ``level`` names the fill's ops ``ghost_fill/slab/L<level>`` (slab
    copies) and ``ghost_fill/scatter/L<level>`` (per-cell values) in the
    program's op metadata.
    """
    # repro: host-ok(build-time mask normalization, outside the stepping loop)
    mask = np.asarray(mask)
    assert dst_cell.size > 0 or slabs is not None, (
        "use make_stream_collide when there is no fill"
    )
    _count_fill_cells(level, slabs, dst_cell.size)
    if slabs is None:
        targets, placement = (jnp.asarray(dst_slot), jnp.asarray(dst_cell)), None
    else:
        targets, placement = None, lower_slab_placement(
            dst_slot, dst_cell, slabs.cells, slabs.ghost, mask.shape[0]
        )

    if backend == "pallas":
        interpret = resolve_interpret(interpret)
        mask_j = jnp.asarray(mask)

        def stencil(f: jax.Array) -> jax.Array:
            return lbm_stream_collide_pallas(
                f,
                mask_j,
                omega=omega,
                lattice=lattice,
                u_wall=u_wall,
                collision=collision,
                magic=magic,
                interpret=interpret,
            )

    elif backend == "ref":
        pm = precompute_stream_masks(mask, lattice)
        fs = jnp.asarray(pm["fluid_src"])  # (Q, B, X, Y, Z)
        ls = jnp.asarray(pm["lid_src"])
        fl = jnp.asarray(pm["fluid"])  # (B, X, Y, Z)

        def stencil(f: jax.Array) -> jax.Array:
            coeffs = collision_coeffs(
                omega,
                lattice=lattice,
                u_wall=u_wall,
                collision=collision,
                magic=magic,
                dtype=f.dtype.type,
            )

            def blk(fb, fsb, lsb, flb):
                return stream_collide_coeffs(
                    fb,
                    None,
                    coeffs,
                    lattice=lattice,
                    collision=collision,
                    premask={"fluid_src": fsb, "lid_src": lsb, "fluid": flb},
                )

            return jax.vmap(blk, in_axes=(0, 1, 1, 0))(f, fs, ls, fl)

    else:
        raise ValueError(f"unknown backend {backend!r}")

    def step(f: jax.Array, vals: jax.Array | None) -> jax.Array:
        return stencil(_halo_fill(f, vals, level, targets, slabs, placement))

    return step


def _count_fill_cells(level: int, slabs, n_scatter: int, **labels) -> None:
    """Record, per built fill, how many ghost cells the slab copies fill and
    how many are written cell by cell (``halo.slab_cells`` and
    ``halo.scatter_cells``, labelled by level and any further ``labels``)."""
    if not _TR.enabled:
        return
    n_slab = 0 if slabs is None else slabs.num_cells
    _TR.metrics.counter("halo.slab_cells").inc(n_slab, level=level, **labels)
    _TR.metrics.counter("halo.scatter_cells").inc(n_scatter, level=level, **labels)


def _halo_fill(f: jax.Array, vals, level: int, targets, slabs, placement) -> jax.Array:
    """One level's ghost fill. Without a slab part: the merged per-cell
    scatter of ``vals`` to ``targets`` (``(dst_slot, dst_cell)`` device
    arrays). With one: :func:`_slab_fill` of the stack's own pieces."""
    if slabs is None:
        db, dc = targets
        with jax.named_scope(f"ghost_fill/scatter/L{level}"):
            return _flat3(f).at[db, :, dc].set(vals).reshape(f.shape)
    return _slab_fill(_stack_cut(f, slabs.cells, slabs.ghost), vals, level, slabs, placement)


def _stack_cut(f: jax.Array, cells, ghost):
    """``cut(d, part)``: the piece of the ``(B, Q, X, Y, Z)`` stack ``f`` that
    :func:`~repro.lbm.halo.slab_slices` names for direction ``d`` (``part`` 0:
    the ghost slab, or the interior for ``(0, 0, 0)``; 1: the interior
    boundary slab a neighbour in direction ``-d`` copies)."""

    def cut(d, part):
        return f[(slice(None), slice(None), *slab_slices(d, cells, ghost)[part])]

    return cut


def _slab_fill(cut, vals, level: int, slabs, placement) -> jax.Array:
    """The same-level slabs copied block by block from the pieces ``cut``
    gives, ``vals`` (if any) written cell by cell into the slabs by
    ``placement`` (:func:`~repro.lbm.halo.lower_slab_placement`), and the
    stack assembled in one pass, with no scatter."""
    with jax.named_scope(f"ghost_fill/slab/L{level}"):
        pieces = _slab_pieces(cut, slabs)
    if vals is not None:
        with jax.named_scope(f"ghost_fill/scatter/L{level}"):
            pieces = _place_rows(pieces, vals, placement)
    with jax.named_scope(f"ghost_fill/slab/L{level}"):
        return _assemble(pieces)


def _slab_pieces(cut, slabs) -> dict:
    """The 27 pieces of a level stack's tiles (``cut``, see
    :func:`_stack_cut`), keyed by direction (``(0, 0, 0)`` is the interior),
    with the same-level ghost slabs filled: for each direction the
    neighbours' interior boundary slabs are gathered along the block axis (a
    clamped index per destination slot, plus a per-slot select that keeps the
    old ghost values where the slab has no same-level source)."""
    pieces = {}
    for k, d in enumerate((*SLAB_DIRECTIONS, (0, 0, 0))):
        cur = cut(d, 0)
        ss = slabs.src_slot[k] if d != (0, 0, 0) else None
        if ss is None or not (ss >= 0).any():
            pieces[d] = cur
            continue
        vals = jnp.take(
            cut(d, 1),
            jnp.asarray(np.maximum(ss, 0)),
            axis=0,
            mode="clip",
        )
        if not (ss >= 0).all():
            has = jnp.asarray(ss >= 0)[:, None, None, None, None]
            vals = jnp.where(has, vals, cur)
        pieces[d] = vals
    return pieces


def _place_rows(pieces: dict, vals: jax.Array, placement: dict) -> dict:
    """Write per-cell fill values into the ghost-slab pieces: for each
    direction, the value rows of the slots that have targets there are
    gathered into slab-shaped rows, placed along the block axis, and
    selected cell by cell (:func:`~repro.lbm.halo.lower_slab_placement`)."""
    q = vals.shape[1]
    for d, (rows, pos, hit) in placement.items():
        w = pieces[d].shape[2:]
        rv = vals[jnp.asarray(rows)]  # (S, cells of the slab, Q)
        rv = jnp.swapaxes(rv, 1, 2).reshape(rows.shape[0], q, *w)
        placed = jnp.take(rv, jnp.asarray(pos), axis=0, mode="clip")
        pieces[d] = jnp.where(jnp.asarray(hit.reshape(-1, 1, *w)), placed, pieces[d])
    return pieces


def _assemble(pieces: dict) -> jax.Array:
    """Concatenate the 27 pieces back into the ``(B, Q, X, Y, Z)`` stack: one
    pass that writes the whole stack once."""
    r = (-1, 0, 1)
    return jnp.concatenate([
        jnp.concatenate([
            jnp.concatenate([pieces[(dx, dy, dz)] for dz in r], axis=4)
            for dy in r
        ], axis=3)
        for dx in r
    ], axis=2)


def _device_plan_ops(plan, level_index: dict[int, int]) -> list[tuple]:
    """Lower a :class:`~repro.lbm.halo.CompiledGhostPlan` for one field into
    device-ready (dst idx, src idx, kind, index arrays) tuples, mapping levels
    to positions in the superstep's buffer tuple."""
    ops = []
    for op in plan.ops:
        ops.append(
            (
                level_index[op.dst_level],
                level_index[op.src_level],
                op.kind,
                jnp.asarray(op.dst_slot),
                jnp.asarray(op.dst_cell),
                jnp.asarray(op.src_slot),
                jnp.asarray(op.src_cell),
            )
        )
    return ops


def _flat3(a: jax.Array) -> jax.Array:
    """(B, *lead, X, Y, Z) -> (B, C, cells) with C the flattened lead axes."""
    return a.reshape(a.shape[0], -1, a.shape[-3] * a.shape[-2] * a.shape[-1])


def _gather_vals(s: jax.Array, kind: str, sb, sc) -> jax.Array:
    """Gather (and sender-side resample) one exchange segment: (N, C) values."""
    flat = _flat3(s)
    if kind == "fine":
        v = flat[sb, :, sc]  # (N, 8, C): octet gather in canonical order
        acc = v[:, 0]
        for k in range(1, 8):  # fixed-sequence sum == host _extract
            acc = acc + v[:, k]
        if jnp.issubdtype(s.dtype, jnp.floating):
            return acc * s.dtype.type(0.125)
        return (acc / 8).astype(s.dtype)  # int fields: truncating divide
    return flat[sb, :, sc]  # same / coarse: plain (possibly replicating) gather


def _run_plan_ops(ops: list[tuple], bufs: list[jax.Array]) -> list[jax.Array]:
    """Execute lowered exchange ops functionally on (B, *lead, X, Y, Z)
    per-level buffers (pure gathers/scatters — safe inside jit)."""
    for dst, src, kind, db, dc, sb, sc in ops:
        vals = _gather_vals(bufs[src], kind, sb, sc)
        d = bufs[dst]
        bufs[dst] = _flat3(d).at[db, :, dc].set(vals).reshape(d.shape)
    return bufs


def _lower_fill_gathers(fill, level_index: dict[int, int]) -> tuple:
    """Device-ready gather specs for a merged fill's value segments."""
    return tuple(
        (
            level_index[seg.src_level],
            seg.kind,
            jnp.asarray(seg.src_slot),
            jnp.asarray(seg.src_cell),
        )
        for seg in fill.segments
    )


def _concat_vals(bufs, gathers, level: int, extra=()) -> jax.Array:
    """Concatenate gathered segment values (plus any pre-built extra value
    arrays, e.g. inbound message slices) in merged-fill order for the fill of
    ``level``. Each segment's gather is named ``ghost_fill/<kind>/L<level>``
    in the program's op metadata, so a profile can tell the fills apart."""
    parts = []
    for si, kind, sb, sc in gathers:
        with jax.named_scope(f"ghost_fill/{kind}/L{level}"):
            parts.append(_gather_vals(bufs[si], kind, sb, sc))
    parts += list(extra)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _merge_rank_fills(local_plan, messages, spec, nslots: dict[int, int],
                      level_index: dict[int, int]) -> dict[int, dict]:
    """One rank's ghost fill per destination level: the rank-local
    same-level slabs (``slabs``, a :class:`~repro.lbm.halo.LevelSlabFill` or
    ``None``) and the per-cell rest (``dst``: the local residual's targets
    first, then each inbound message's scatter segments in (message,
    segment) order), whose values are the local residual's gathers
    (``gath``) followed by the ``(message, offset, rows)`` payload slices
    (``msg``), row for row. ``nslots`` maps a level to its stack's slot
    count."""
    fills = {
        l: split_halo_fill(f, spec, nslots[l])
        for l, f in (
            lower_halo_fill(local_plan)
            if local_plan is not None and local_plan.ops
            else {}
        ).items()
    }
    per: dict[int, dict] = {
        l: {
            "dst": [] if res is None else [(res.dst_slot, res.dst_cell)],
            "gath": () if res is None else _lower_fill_gathers(res, level_index),
            "msg": [],
            "slabs": slabs,
        }
        for l, (slabs, res) in fills.items()
    }
    for mi, m in enumerate(messages):
        off = 0
        for dl, db, dc, n in m.scatter:
            e = per.setdefault(dl, {"dst": [], "gath": (), "msg": [], "slabs": None})
            e["dst"].append((db, dc))
            e["msg"].append((mi, off, n))
            off += n
    return per


def _fill_targets(parts) -> tuple[np.ndarray, np.ndarray]:
    """Concatenated (dst_slot, dst_cell) of a level's scatter parts; empty
    when the slabs fill every ghost cell."""
    if not parts:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def apply_compiled_ghost_plan(plan, bufs: dict[int, jax.Array]) -> dict[int, jax.Array]:
    """Run one compiled single-field ghost exchange on per-level buffers.

    ``bufs`` maps level -> (B, *lead, X, Y, Z) array; a new dict with updated
    arrays is returned (pure — usable standalone or under jit). This is the
    building block :func:`make_fused_superstep` composes; exposed separately
    so tests can pin compiled-vs-host exchange equivalence directly.
    """
    assert len({op.field for op in plan.ops}) <= 1, (
        "apply_compiled_ghost_plan executes one field's buffers; compile "
        "multi-field exchanges as one plan per field"
    )
    levels = sorted(bufs)
    index = {l: i for i, l in enumerate(levels)}
    out = _run_plan_ops(
        _device_plan_ops(plan, index), [jnp.asarray(bufs[l]) for l in levels]
    )
    return dict(zip(levels, out))


def make_fused_superstep(
    *,
    levels,
    plans,
    steppers,
    masks,
    unroll_limit: int = 32,
    halo_stepper_factory=None,
    spec=None,
):
    """Compile one full coarse step — the whole ``2^lmax`` substep cycle with
    interleaved ghost exchange — into a single jitted device program.

    Per substep ``s`` the active level set is ``{l : s % 2^(lmax-l) == 0}``,
    which depends only on the number of trailing zeros of ``s``; there are
    therefore just ``lmax+1`` distinct *activity patterns*. Each pattern
    becomes one branch. With ``halo_stepper_factory`` set the branch runs the
    halo-in-tile schedule: every active level's ghost fill is merged into one
    scatter (:func:`~repro.lbm.halo.lower_halo_fill`), all fill values are
    gathered up front from the pre-step buffers (sources are interior cells,
    disjoint from every fill target, so this is bitwise equal to the
    sequential per-op schedule), and each level then steps through its fused
    fill+stencil program — no intermediate exchanged buffer is materialized.
    Without the factory the legacy per-op gather/scatter schedule runs.
    Short cycles (``nsub <= unroll_limit``, i.e. essentially always) are
    unrolled straight-line — on CPU the ``fori_loop`` carry and ``switch``
    result copies cost more than the whole substep — while deeper
    hierarchies run the loop as ``lax.fori_loop`` dispatching through
    ``lax.switch`` on the pattern of ``s`` to bound program size. Nothing
    touches the host either way: the only transfers are the caller's initial
    upload and whatever diagnostics later flush back.

    The pdf tuple is donated: XLA aliases the inputs into the outputs and
    the superstep ping-pongs the double-buffered populations in place —
    callers must treat the passed-in arrays as consumed (the engines
    re-``store`` the returned arrays into their residency immediately).

    Args:
        levels: refinement levels in use (the buffer tuple's order is the
            ascending sort of this).
        plans: pattern index ``p`` (0..lmax) -> compiled ghost plan for the
            active set ``{l : l >= lmax - p}``.
        steppers: level -> ``step(f, mask) -> f`` (from
            :func:`make_stream_collide`; closed over, traced inline). Used
            for active levels with no fill, and for every level in the
            legacy schedule.
        masks: level -> device mask stack for that level's buffer.
        halo_stepper_factory: optional ``(level, dst_slot, dst_cell,
            slabs=None) -> step(f, vals)`` builder (see
            :func:`make_halo_stream_collide`).
        spec: the pdf field's block geometry (:class:`~repro.lbm.grid.
            LBMBlockSpec`), required with ``halo_stepper_factory``: the
            ghost slabs that same-level neighbours fill whole are copied
            block by block and only the rest is written cell by cell
            (:func:`~repro.lbm.halo.split_halo_fill`).

    Returns:
        A jitted ``superstep(pdfs: tuple) -> tuple`` advancing one coarse
        step; ``pdfs`` holds one (B, Q, X, Y, Z) buffer per level, ascending.
    """
    assert halo_stepper_factory is None or spec is not None, "halo fills need spec"
    levels = tuple(sorted(levels))
    index = {l: i for i, l in enumerate(levels)}
    lmax = levels[-1]
    nsub = 1 << lmax
    masks_t = tuple(jnp.asarray(masks[l]) for l in levels)

    def make_branch(p: int):
        active = tuple(sorted((l for l in levels if l >= lmax - p), reverse=True))
        if halo_stepper_factory is None:
            ops = _device_plan_ops(plans[p], index)

            def branch(pdfs):
                bufs = _run_plan_ops(ops, list(pdfs))
                for l in active:  # finest first, as the host driver orders
                    i = index[l]  # its per-level kernel calls
                    bufs[i] = steppers[l](bufs[i], masks_t[i])
                return tuple(bufs)

            return branch

        fills = {
            l: split_halo_fill(f, spec, masks_t[index[l]].shape[0])
            for l, f in lower_halo_fill(plans[p]).items()
        }
        assert set(fills) <= set(active), (sorted(fills), active)
        gathers = {
            l: _lower_fill_gathers(res, index)
            for l, (_, res) in fills.items()
            if res is not None
        }
        hsteps = {
            l: halo_stepper_factory(
                l,
                *_fill_targets([] if res is None else [(res.dst_slot, res.dst_cell)]),
                slabs=slabs,
            )
            for l, (slabs, res) in fills.items()
        }

        def branch(pdfs):
            bufs = list(pdfs)
            # all fill values gather from the pre-step buffers (every source
            # is an interior cell; every target a ghost cell — disjoint); a
            # level's slab copies read its own buffer, which no finer level's
            # step writes, so they read pre-step values too
            vals = {l: _concat_vals(bufs, g, l) for l, g in gathers.items()}
            for l in active:  # finest first
                i = index[l]
                if l in fills:
                    bufs[i] = hsteps[l](bufs[i], vals.get(l))
                else:
                    bufs[i] = steppers[l](bufs[i], masks_t[i])
            return tuple(bufs)

        return branch

    branches = [make_branch(p) for p in range(lmax + 1)]
    # pattern of substep s = trailing zeros of s (s=0 activates everything)
    pattern = [
        lmax if s == 0 else min((s & -s).bit_length() - 1, lmax) for s in range(nsub)
    ]

    def superstep(pdfs):
        pdfs = tuple(pdfs)
        if nsub <= unroll_limit:
            for s in range(nsub):
                pdfs = branches[pattern[s]](pdfs)
            return pdfs
        pattern_dev = jnp.asarray(pattern, dtype=jnp.int32)

        def body(s, carry):
            return jax.lax.switch(pattern_dev[s], branches, carry)

        return jax.lax.fori_loop(0, nsub, body, pdfs)

    return jax.jit(superstep, donate_argnums=0)


def make_ensemble_superstep(
    *,
    levels,
    plans,
    masks,
    lattice: Lattice = D3Q19,
    collision: str = "bgk",
    unroll_limit: int = 32,
):
    """Compile one coarse step for a whole *ensemble* of independent members
    sharing one forest topology: :func:`make_fused_superstep` with a leading
    member axis ``vmap``-ped over per-member physics coefficients.

    Per-member relaxation rates and wall velocities enter as *batched
    operands* (not closed-over constants), so one compiled program serves
    every member of the batch — the inference-serving amortization: compile
    once per (topology, activity-pattern set), dispatch once per coarse step
    for all members. Because the coefficients are pre-rounded to the field
    dtype on the host (:func:`~repro.kernels.lbm_collide.ref.collision_coeffs`)
    and only ever combine as ``coefficient * array``, each member's slice of
    the batched program does a solo fused run's arithmetic with the same
    parameters; how the backend rounds it may differ with the batch shape,
    so the two agree to float32 round-off on every interior cell.

    Args:
        levels: refinement levels in use (ascending buffer-tuple order).
        plans: pattern index ``p`` (0..lmax) -> compiled ghost plan for the
            active set ``{l : l >= lmax - p}`` (per-*member* slot layout —
            all members share it, since they share the topology).
        masks: level -> (B, X, Y, Z) mask stack shared by every member.
        lattice / collision: the (topology-compatible) kernel configuration
            shared by the whole ensemble.

    Returns:
        A jitted ``superstep(pdfs: tuple, coeffs: dict) -> tuple`` advancing
        one coarse step: ``pdfs`` holds one ``(M, B, Q, X, Y, Z)`` buffer per
        level (``M`` = ensemble members, leading axis), ``coeffs`` maps level
        -> per-member coefficient arrays (leading ``M`` axis, from
        ``collision_coeffs`` stacked across members).
    """
    levels = tuple(sorted(levels))
    index = {l: i for i, l in enumerate(levels)}
    lmax = levels[-1]
    nsub = 1 << lmax
    masks_t = tuple(jnp.asarray(masks[l]) for l in levels)
    # host-precomputed streaming selectors, mirroring the solo fused path's
    # merged-fill steppers (make_halo_stream_collide, backend="ref"), so the
    # batched program traces the same arithmetic as a solo fused run
    premasks = {
        # repro: host-ok(build-time d2h of the mask stack for selector precompute, once per program build)
        l: precompute_stream_masks(np.asarray(masks[l]), lattice) for l in levels
    }
    pm_t = {
        l: (
            jnp.asarray(pm["fluid_src"]),  # (Q, B, X, Y, Z)
            jnp.asarray(pm["lid_src"]),
            jnp.asarray(pm["fluid"]),  # (B, X, Y, Z)
        )
        for l, pm in premasks.items()
    }

    def step_level(fb: jax.Array, mb: jax.Array, coeffs: dict) -> jax.Array:
        return jax.vmap(
            lambda f, m: stream_collide_coeffs(
                f, m, coeffs, lattice=lattice, collision=collision
            )
        )(fb, mb)

    def step_level_filled(
        fb: jax.Array, l: int, db, dc, vals: jax.Array, coeffs: dict
    ) -> jax.Array:
        # merged fill scatter + premask stencil, same shape as the solo
        # halo stepper (vmap over blocks, selectors batched along axis 1)
        fb = _flat3(fb).at[db, :, dc].set(vals).reshape(fb.shape)
        fs, ls, fl = pm_t[l]

        def blk(f, fsb, lsb, flb):
            return stream_collide_coeffs(
                f,
                None,
                coeffs,
                lattice=lattice,
                collision=collision,
                premask={"fluid_src": fsb, "lid_src": lsb, "fluid": flb},
            )

        return jax.vmap(blk, in_axes=(0, 1, 1, 0))(fb, fs, ls, fl)

    def make_branch(p: int):
        active = tuple(sorted((l for l in levels if l >= lmax - p), reverse=True))
        fills = lower_halo_fill(plans[p])
        assert set(fills) <= set(active), (sorted(fills), active)
        gathers = {l: _lower_fill_gathers(f, index) for l, f in fills.items()}
        scatters = {
            l: (jnp.asarray(f.dst_slot), jnp.asarray(f.dst_cell))
            for l, f in fills.items()
        }

        def branch(carry):
            pdfs, coeffs = carry
            bufs = list(pdfs)
            # all fill values gather from the pre-step buffers, exactly as
            # the solo fused superstep's halo-in-tile branch does
            vals = {l: _concat_vals(bufs, gathers[l], l) for l in fills}
            for l in active:  # finest first, matching the solo kernel order
                i = index[l]
                if l in fills:
                    db, dc = scatters[l]
                    bufs[i] = step_level_filled(
                        bufs[i], l, db, dc, vals[l], coeffs[l]
                    )
                else:
                    bufs[i] = step_level(bufs[i], masks_t[i], coeffs[l])
            return tuple(bufs), coeffs

        return branch

    branches = [make_branch(p) for p in range(lmax + 1)]
    pattern = [
        lmax if s == 0 else min((s & -s).bit_length() - 1, lmax) for s in range(nsub)
    ]

    def member_superstep(pdfs, coeffs):
        carry = (tuple(pdfs), coeffs)
        if nsub <= unroll_limit:
            for s in range(nsub):
                carry = branches[pattern[s]](carry)
            return carry[0]
        pattern_dev = jnp.asarray(pattern, dtype=jnp.int32)

        def body(s, carry):
            return jax.lax.switch(pattern_dev[s], branches, carry)

        return jax.lax.fori_loop(0, nsub, body, carry)[0]

    return jax.jit(jax.vmap(member_superstep, in_axes=(0, 0)))


def make_rank_emit(messages, level_index: dict[int, int]):
    """Compile one rank's message-building side of a sharded exchange.

    ``messages`` are the :class:`~repro.lbm.halo.CompiledRankMessage` specs
    whose ``src_rank`` is this rank; ``level_index`` maps the rank's levels
    to positions in its buffer tuple. Returns a jitted
    ``emit(pdfs: tuple) -> tuple`` producing one device-resident ``(N, C)``
    payload per message (sender-side resampled, segments concatenated in the
    spec's canonical order) — the arrays handed to the ``Comm`` fabric, so
    nothing touches the host. Returns ``None`` when the rank sends nothing.

    ``emit`` deliberately never donates its inputs: it only *reads* the pdf
    buffers, and they must stay live for the interior/absorb programs
    dispatched after it in the same substep. The donation happens there —
    the runtime sequences the donated write after emit's pending reads.
    """
    if not messages:
        return None
    specs = tuple(
        tuple(
            (level_index[src_level], kind, jnp.asarray(sb), jnp.asarray(sc))
            for src_level, kind, sb, sc in m.gather
        )
        for m in messages
    )

    @jax.jit
    def emit(pdfs):
        out = []
        for segs in specs:
            parts = [_gather_vals(pdfs[li], kind, sb, sc) for li, kind, sb, sc in segs]
            out.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0))
        return tuple(out)

    return emit


def boundary_slot_sets(messages, masks) -> dict[int, frozenset[int]]:
    """Per-level sets of block slots whose ghost layer depends on inbound
    cross-rank messages (the *boundary* blocks of a rank). ``masks`` maps
    the rank's levels to their (B, ...) stacks (only shapes are read)."""
    bnd: dict[int, set[int]] = {l: set() for l in masks}
    for m in messages:
        for dl, db, _dc, _n in m.scatter:
            bnd.setdefault(dl, set()).update(int(s) for s in np.unique(db))
    return {l: frozenset(s) for l, s in bnd.items()}


def make_rank_absorb(
    messages,
    local_plan,
    level_index: dict[int, int],
    steppers,
    masks,
    active_levels,
    *,
    halo_stepper_factory=None,
    spec=None,
):
    """Compile one rank's receive+exchange+step side of a sharded substep.

    ``messages`` are the inbound :class:`~repro.lbm.halo.CompiledRankMessage`
    specs (``dst_rank`` == this rank) in plan order — the caller passes the
    received payloads in the same order; ``local_plan`` is the rank's
    intra-rank :class:`~repro.lbm.halo.CompiledGhostPlan` (or None);
    ``steppers``/``masks`` map the rank's levels to ``step(f, mask) -> f``
    kernels and device mask stacks; ``active_levels`` is this substep
    pattern's active set intersected with the rank's levels.

    With ``halo_stepper_factory`` set, the local-plan fills and the inbound
    message scatters targeting each level are merged into *one* fill per
    level and fused into that level's stencil program (halo-in-tile) — local
    fill values gather from the pre-step buffers, message values are sliced
    straight from the payload operands; the local fills' same-level ghost
    slabs are block-granular copies, as in :func:`make_fused_superstep`,
    which needs ``spec`` (the pdf field's block geometry) with the factory.
    The pdf tuple is donated so the substep runs ping-pong in place (payload
    operands are never donated — the fabric may still hold them).

    Returns a jitted ``absorb(pdfs: tuple, msgs: tuple) -> tuple`` — one
    device program per (rank, activity pattern), no host contact.
    """
    assert halo_stepper_factory is None or spec is not None, "halo fills need spec"
    order = tuple(sorted(active_levels, reverse=True))  # finest first, as the
    masks_t = {l: jnp.asarray(masks[l]) for l in order}  # host driver does

    if halo_stepper_factory is None:
        scatters = tuple(
            tuple(
                (level_index[dst_level], jnp.asarray(db), jnp.asarray(dc), n)
                for dst_level, db, dc, n in m.scatter
            )
            for m in messages
        )
        local_ops = _device_plan_ops(local_plan, level_index) if local_plan else []

        def absorb(pdfs, msgs):
            bufs = list(pdfs)
            for segs, msg in zip(scatters, msgs):
                off = 0
                for li, db, dc, n in segs:
                    d = bufs[li]
                    bufs[li] = (
                        _flat3(d).at[db, :, dc].set(msg[off : off + n]).reshape(d.shape)
                    )
                    off += n
            bufs = _run_plan_ops(local_ops, bufs)
            for l in order:
                i = level_index[l]
                bufs[i] = steppers[l](bufs[i], masks_t[l])
            return tuple(bufs)

    else:
        per = _merge_rank_fills(
            local_plan, messages, spec, {l: masks[l].shape[0] for l in masks},
            level_index,
        )
        assert set(per) <= set(order), (sorted(per), order)
        hsteps = {
            l: halo_stepper_factory(l, *_fill_targets(e["dst"]), slabs=e["slabs"])
            for l, e in per.items()
        }

        def absorb(pdfs, msgs):
            bufs = list(pdfs)
            vals = {
                l: _concat_vals(
                    bufs,
                    e["gath"],
                    l,
                    extra=[msgs[mi][off : off + n] for mi, off, n in e["msg"]],
                )
                for l, e in per.items()
                if e["gath"] or e["msg"]
            }
            for l in order:
                i = level_index[l]
                if l in per:
                    bufs[i] = hsteps[l](bufs[i], vals.get(l))
                else:
                    bufs[i] = steppers[l](bufs[i], masks_t[l])
            return tuple(bufs)

    return jax.jit(absorb, donate_argnums=0)


def make_rank_absorb_split(
    messages,
    local_plan,
    level_index: dict[int, int],
    steppers,
    masks,
    active_levels,
):
    """Split one rank's substep into an interior and a boundary program so
    cross-rank payload routing overlaps interior stepping.

    *Boundary* blocks are the slots whose ghost layer depends on inbound
    messages (:func:`boundary_slot_sets`); everything else is *interior* —
    by construction an interior block's ghosts are filled entirely by the
    rank-local plan. The interior program ``interior(pdfs) -> pdfs`` gathers
    **all** local fill values from the pre-step buffers, scatters them
    (including the boundary blocks' local-sourced ghosts — their gathers
    happened before any stepping, preserving exchange semantics), then
    steps only the interior slots of each active level. The boundary
    program ``boundary(pdfs, msgs) -> pdfs`` scatters the inbound payloads
    and steps the boundary slots. The advance loop dispatches every rank's
    interior program *before* routing messages on the host, so the fabric
    work hides behind interior compute; the two programs together do the
    unsplit absorb's arithmetic (per-block stepping is independent, and
    every ghost fill lands before the slot that reads it steps). Both
    programs donate their pdf tuple.
    """
    order = tuple(sorted(active_levels, reverse=True))
    # repro: host-ok(build-time d2h of mask stacks for program lowering, once per arena version)
    masks_np = {l: np.asarray(masks[l]) for l in order}
    bnd = boundary_slot_sets(messages, masks_np)
    idx_int = {
        l: np.asarray(
            [s for s in range(masks_np[l].shape[0]) if s not in bnd.get(l, ())],
            dtype=np.int32,
        )
        for l in order
    }
    idx_bnd = {
        l: np.asarray(sorted(bnd.get(l, ())), dtype=np.int32) for l in order
    }
    masks_t = {l: jnp.asarray(masks_np[l]) for l in order}
    sub_mask = {
        ("int", l): jnp.asarray(masks_np[l][idx_int[l]]) for l in order
    }
    sub_mask.update(
        (("bnd", l), jnp.asarray(masks_np[l][idx_bnd[l]])) for l in order
    )
    fills = (
        lower_halo_fill(local_plan) if local_plan is not None and local_plan.ops else {}
    )
    local_j = {
        l: (jnp.asarray(f.dst_slot), jnp.asarray(f.dst_cell), _lower_fill_gathers(f, level_index))
        for l, f in fills.items()
    }
    scatters = tuple(
        tuple(
            (level_index[dst_level], jnp.asarray(db), jnp.asarray(dc), n)
            for dst_level, db, dc, n in m.scatter
        )
        for m in messages
    )

    def _step_subset(bufs, l, idx, which):
        i = level_index[l]
        if idx.size == 0:
            return
        if idx.size == masks_np[l].shape[0]:
            bufs[i] = steppers[l](bufs[i], masks_t[l])
            return
        sel = jnp.asarray(idx)
        sub = steppers[l](bufs[i][sel], sub_mask[(which, l)])
        bufs[i] = bufs[i].at[sel].set(sub)

    def interior(pdfs):
        bufs = list(pdfs)
        # every local fill (interior *and* boundary targets) gathers and
        # lands here, from pre-step sources
        for l, (db, dc, gath) in local_j.items():
            vals = _concat_vals(bufs, gath, l)
            i = level_index[l]
            d = bufs[i]
            bufs[i] = _flat3(d).at[db, :, dc].set(vals).reshape(d.shape)
        for l in order:
            _step_subset(bufs, l, idx_int[l], "int")
        return tuple(bufs)

    def boundary(pdfs, msgs):
        bufs = list(pdfs)
        for segs, msg in zip(scatters, msgs):
            off = 0
            for li, db, dc, n in segs:
                d = bufs[li]
                bufs[li] = (
                    _flat3(d).at[db, :, dc].set(msg[off : off + n]).reshape(d.shape)
                )
                off += n
        for l in order:
            _step_subset(bufs, l, idx_bnd[l], "bnd")
        return tuple(bufs)

    return (
        jax.jit(interior, donate_argnums=0),
        jax.jit(boundary, donate_argnums=0),
    )


def make_device_superstep(
    *,
    mesh,
    levels,
    plans,
    schedules,
    steppers,
    spec,
    counts,
    unroll_limit: int = 32,
):
    """Compile one coarse step as a single SPMD program over real XLA devices.

    The ``device_sharded`` analogue of the per-rank program set built by
    ``FusedShardedEngine``: one ``shard_map`` over a 1-D ``mesh`` (axis
    ``"ranks"``, one device per rank) runs the whole ``2^lmax`` substep cycle,
    and the simulated ``Comm`` fabric's per-pair messages become
    ``jax.lax.ppermute`` calls *inside* the program. Per-rank asymmetry — the
    gather/scatter index arrays of :func:`compile_rank_halo_plan` differ on
    every rank — is expressed as ``lax.switch`` on ``lax.axis_index``: each
    branch closes over exactly one rank's index constants, so the program
    does the same arithmetic as the host-fabric engines (including the
    canonical fixed-order octet sum); how it rounds is the backend's.

    Each activity pattern ``p``'s parts carry device labels in the HLO's
    ``op_name`` metadata: ``halo_emit/p<p>`` (the outbound gathers),
    ``halo_ppermute/p<p>`` (the permute rounds, collective-permute
    instructions on the device) and ``halo_absorb/p<p>`` (the ghost fill).

    The ghost fill takes the form :func:`make_fused_superstep`'s does, per
    rank and level: the ghost slabs that a same-level neighbour on the same
    rank fills whole are copied block by block from the stack itself
    (``ghost_fill/slab/L<l>``); every other target, cross-level or inbound
    from another rank, is written into the slab pieces by gather and select
    (``ghost_fill/scatter/L<l>``, :func:`~repro.lbm.halo.
    lower_slab_placement`), and the stack is assembled once. A level whose
    fill has no slab part takes the same path with no slab sources. Which
    cells take which form follows from the rank's plan alone; the program
    holds no scatter. The slab-shaped pieces the fills read (every ghost
    slab, the interior, and the source slabs some rank copies) are the same
    on every rank, so they are cut once before the per-rank switch, and
    each rank's branch copies and places from them. Each build records
    ``halo.slab_cells`` and ``halo.scatter_cells`` per level and rank
    (telemetry on).

    Buffers are the equal-blocks-per-rank padded stacks: each per-level
    operand is ``(nranks, Bmax_l, ...)`` sharded on the leading axis, so every
    shard sees ``(1, Bmax_l, ...)`` and rank-local slot ids address it
    directly. Pad slots have no plan entries (:func:`~repro.lbm.halo.
    verify_padded_plan`), so the fill leaves them as they are. Payloads for
    one :class:`~repro.lbm.halo.PpermuteRound` are zero-padded to the
    round's ``num_cells`` so all participants ship one shape; receivers
    place only the logical rows.

    Args:
        mesh: 1-D ``jax.sharding.Mesh`` whose single axis enumerates ranks.
        levels: global refinement levels in use (buffer tuple order is the
            ascending sort, same for every rank).
        plans: pattern index ``p`` -> :class:`CompiledRankHaloPlan` for the
            active set ``{l : l >= lmax - p}``.
        schedules: pattern index ``p`` -> ppermute rounds from
            :func:`~repro.lbm.halo.schedule_ppermute_rounds` over
            ``plans[p].messages``.
        steppers: level -> ``step(f, mask) -> f`` (shared with every other
            engine — same kernel, same trace).
        spec: the pdf field's block geometry (:class:`~repro.lbm.grid.
            LBMBlockSpec`), which fixes the ghost slabs.
        counts: level -> ``Bmax_l``, the padded per-rank slot count
            (:func:`~repro.lbm.halo.padded_block_counts`).

    Returns:
        A jitted ``superstep(pdfs: tuple, masks: tuple) -> tuple`` advancing
        one coarse step; each tuple holds one padded global per-level stack.
        Masks are operands (not closed-over constants) because they are
        sharded alongside the pdfs.
    """
    levels = tuple(sorted(levels))
    index = {l: i for i, l in enumerate(levels)}
    lmax = levels[-1]
    nsub = 1 << lmax
    axis = mesh.axis_names[0]
    nranks = mesh.shape[axis]

    def make_emit_branch(rank: int, rounds):
        # per round: this rank's outbound gather (or a zero payload)
        specs = []
        for rnd in rounds:
            mine = [m for m in rnd.messages if m.src_rank == rank]
            assert len(mine) <= 1, (rank, rnd.perm)
            if mine:
                m = mine[0]
                segs = tuple(
                    (index[sl], kind, jnp.asarray(sb), jnp.asarray(sc))
                    for sl, kind, sb, sc in m.gather
                )
                specs.append((segs, m.num_cells, rnd.num_cells))
            else:
                specs.append((None, 0, rnd.num_cells))

        def emit(bufs):
            C = _flat3(bufs[0]).shape[1]
            dt = bufs[0].dtype
            out = []
            for segs, n, cap in specs:
                if segs is None:
                    out.append(jnp.zeros((cap, C), dt))
                    continue
                parts = [
                    _gather_vals(bufs[li], kind, sb, sc) for li, kind, sb, sc in segs
                ]
                v = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
                if n < cap:
                    v = jnp.concatenate([v, jnp.zeros((cap - n, C), dt)], axis=0)
                out.append(v)
            return tuple(out)

        return emit

    def rank_fill(rank: int, rounds, plan):
        """``(ks, per, fills)`` of one rank: ``ks[mi]`` the round of its
        inbound message ``mi`` (messages in round order), ``per`` its merged
        fill (:func:`_merge_rank_fills`), and ``fills`` level -> the slab part
        and the placement of the per-cell targets."""
        inbound = [
            (k, m) for k, rnd in enumerate(rounds) for m in rnd.messages
            if m.dst_rank == rank
        ]
        per = _merge_rank_fills(
            plan.local.get(rank), [m for _, m in inbound], spec, counts, index
        )
        fills = {}
        for l, e in per.items():
            dst_slot, dst_cell = _fill_targets(e["dst"])
            slabs = e["slabs"] or LevelSlabFill(
                tuple(spec.cells), spec.ghost,
                np.full((len(SLAB_DIRECTIONS), counts[l]), -1, np.int32),
            )
            _count_fill_cells(l, slabs, dst_cell.size, rank=rank)
            fills[l] = (slabs, lower_slab_placement(
                dst_slot, dst_cell, slabs.cells, slabs.ghost, counts[l]
            ))
        return [k for k, _ in inbound], per, fills

    def make_exchange_branch(ks, per, fills):
        def exchange(bufs, cuts, recvs=()):
            bufs = list(bufs)
            # every value gathers from the pre-fill buffers: sources are
            # interior cells, targets ghost cells (as in make_rank_absorb)
            vals = {
                l: _concat_vals(
                    bufs, e["gath"], l,
                    extra=[recvs[ks[mi]][off : off + n] for mi, off, n in e["msg"]],
                )
                for l, e in per.items()
                if e["gath"] or e["msg"]
            }
            for l, (slabs, placement) in fills.items():
                i = index[l]
                bufs[i] = _slab_fill(
                    lambda d, part, c=cuts[i]: c[d, part], vals.get(l), l, slabs, placement
                )
            return tuple(bufs)

        return exchange

    def make_pattern_branch(p: int):
        rounds = schedules[p]
        active = tuple(sorted((l for l in levels if l >= lmax - p), reverse=True))
        emits = [make_emit_branch(r, rounds) for r in range(nranks)]
        ranks = [rank_fill(r, rounds, plans[p]) for r in range(nranks)]
        exchanges = [make_exchange_branch(*rf) for rf in ranks]
        perms = [list(rnd.perm) for rnd in rounds]
        # the pieces any rank's fill reads, per level: every ghost slab and
        # the interior, and the source slabs of the directions some rank copies
        parts: dict[int, set] = {}
        for _, _, fills in ranks:
            for l, (slabs, _) in fills.items():
                keys = parts.setdefault(l, {(d, 0) for d in (*SLAB_DIRECTIONS, (0, 0, 0))})
                keys.update(
                    (d, 1) for k, d in enumerate(SLAB_DIRECTIONS)
                    if (slabs.src_slot[k] >= 0).any()
                )

        def cut_pieces(bufs):
            # the pieces are the same on every rank, so they are cut here, once,
            # and not in each rank's switch branch: there a branch operand's
            # fixed layout makes XLA relayout the whole stack for each cut
            cuts = {}
            for l, keys in sorted(parts.items()):
                cut = _stack_cut(bufs[index[l]], tuple(spec.cells), spec.ghost)
                with jax.named_scope(f"ghost_fill/slab/L{l}"):
                    cuts[index[l]] = {key: cut(*key) for key in sorted(keys)}
            return cuts

        def branch(bufs, masks):
            if nranks == 1:
                with jax.named_scope(f"halo_absorb/p{p}"):
                    bufs = exchanges[0](bufs, cut_pieces(bufs))
            else:
                ridx = jax.lax.axis_index(axis)
                if rounds:
                    with jax.named_scope(f"halo_emit/p{p}"):
                        payloads = jax.lax.switch(ridx, emits, tuple(bufs))
                    with jax.named_scope(f"halo_ppermute/p{p}"):
                        recvs = tuple(
                            # repro: collective-ok(ppermute is a partial permutation — pure p2p halo routing, bytes attributed via DeviceComm.ppermute)
                            jax.lax.ppermute(pl, axis, perm)
                            for pl, perm in zip(payloads, perms)
                        )
                    with jax.named_scope(f"halo_absorb/p{p}"):
                        bufs = jax.lax.switch(
                            ridx, exchanges, tuple(bufs), cut_pieces(bufs), recvs
                        )
                else:
                    with jax.named_scope(f"halo_absorb/p{p}"):
                        bufs = jax.lax.switch(
                            ridx, exchanges, tuple(bufs), cut_pieces(bufs)
                        )
            bufs = list(bufs)
            for l in active:  # finest first, as the host driver orders
                i = index[l]
                bufs[i] = steppers[l](bufs[i], masks[i])
            return tuple(bufs)

        return branch

    branches = [make_pattern_branch(p) for p in range(lmax + 1)]
    pattern = [
        lmax if s == 0 else min((s & -s).bit_length() - 1, lmax) for s in range(nsub)
    ]

    def mapped(pdfs, masks):
        bufs = tuple(b[0] for b in pdfs)  # shard_map hands (1, Bmax, ...)
        m = tuple(mm[0] for mm in masks)
        if nsub <= unroll_limit:
            for s in range(nsub):
                bufs = branches[pattern[s]](bufs, m)
        else:
            pattern_dev = jnp.asarray(pattern, dtype=jnp.int32)

            def body(s, carry):
                return jax.lax.switch(
                    pattern_dev[s],
                    [lambda c, br=br: br(c, m) for br in branches],
                    carry,
                )

            bufs = jax.lax.fori_loop(0, nsub, body, bufs)
        return tuple(b[None] for b in bufs)

    pspec = jax.sharding.PartitionSpec(axis)
    sm = jax.shard_map(
        mapped,
        mesh=mesh,
        in_specs=(pspec, pspec),
        out_specs=pspec,
        check_vma=False,  # lax.switch on axis_index is deliberately per-device
    )
    return jax.jit(sm, donate_argnums=0)


def fused_stream_collide(
    f: jax.Array,
    mask: jax.Array,
    *,
    omega: float,
    lattice: Lattice = D3Q19,
    u_wall: tuple[float, float, float] = (0.0, 0.0, 0.0),
    collision: str = "bgk",
    backend: str = "pallas",
    interpret: bool | None = None,
) -> jax.Array:
    """One fused stream+collide step over (B, Q, X, Y, Z) block stacks."""
    return make_stream_collide(
        omega=omega,
        lattice=lattice,
        u_wall=u_wall,
        collision=collision,
        backend=backend,
        interpret=interpret,
    )(f, mask)
