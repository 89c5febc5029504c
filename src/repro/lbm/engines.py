"""Stepping engines: one class per ``stepping_mode``, one shared contract.

:class:`~repro.lbm.driver.AMRLBM` owns the control plane (forest, AMR
pipeline, criterion, ``Comm``, particles, diagnostics); a :class:`StepEngine`
owns the data plane for one stepping mode — storage (none / ``LevelArena`` /
``RankArenas``), the kernel steppers, cached exchange plans, device masks and
compiled programs, and the per-mode advance loop. The engines replace the
five-way ``if/elif`` dispatch that had accumulated in the driver over PRs
1–4: every mode now implements the same small surface and inherits the
invalidation / residency / statistics hooks instead of duplicating them.

Engine surface (see ARCHITECTURE.md for the mode matrix):

* :meth:`StepEngine.advance` — run whole coarse steps (substep cycle
  included); attributes wall time and traffic to ``sim.data_stats``.
* :meth:`StepEngine.exchange_ghosts` — host-visible ghost refresh, used by
  the advance loop of the host modes and by mode-independent consumers
  (post-AMR refresh, pre-advection refresh for particles).
* :meth:`StepEngine.adopt` — rebind storage after a forest topology change.
* :meth:`StepEngine.sync_caches` / :meth:`StepEngine.masks_refreshed` —
  invalidation by mechanism: caches are keyed to the storage version (every
  ``adopt`` bumps it), so no call site can replay a stale plan, mask, or
  compiled program.
* :meth:`StepEngine.pdfs_written` — the pdf counterpart of
  ``masks_refreshed``: host ``Block.data["pdf"]`` views were written after
  ``materialize_host()``, so every device copy of the pdfs is stale.
* :meth:`StepEngine.materialize_host` — flush device-newer state so every
  ``Block.data`` view is current (no-op for host-resident modes).
* :meth:`StepEngine.particle_batches` — the advection batch source for the
  Lagrangian tracer layer (host modes batch a level, sharded modes batch per
  rank so a rank's tracers read only the rank's own memory).

Mode notes: ``restack`` is the seed baseline (re-stack every substep);
``arena`` steps persistent per-level SoA buffers in place; ``fused``
compiles the whole coarse step into one device program over a
:class:`~repro.core.fields.DeviceResidency`; ``sharded`` runs the rank-
partitioned data plane with host-side p2p halo messages; ``fused_sharded``
composes the last two — per-rank device residency, compiled rank-halo plans
(:func:`~repro.lbm.halo.compile_rank_halo_plan`), and per-rank jitted
substep programs, with host contact only at AMR events.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core import LevelArena, RankArenas
from ..core.pipeline import StageStats
from ..telemetry import NULL_SPAN, get_tracer
from ..kernels.lbm_collide.ops import (
    boundary_slot_sets,
    make_arena_stream_collide,
    make_device_superstep,
    make_fused_superstep,
    make_halo_stream_collide,
    make_rank_absorb,
    make_rank_absorb_split,
    make_rank_emit,
    make_stream_collide,
)
from .grid import CellType
from .halo import (
    compile_ghost_plan,
    compile_rank_halo_plan,
    fill_ghost_layers,
    fill_ghost_layers_sharded,
    padded_block_counts,
    schedule_ppermute_rounds,
    verify_padded_plan,
)
from .lattice import omega_for_level

if TYPE_CHECKING:  # pragma: no cover - typing only
    from ..core.forest import Block, BlockForest
    from .driver import AMRLBM

__all__ = ["StepEngine", "ENGINES", "make_engine"]

ENGINES: dict[str, type["StepEngine"]] = {}

_TR = get_tracer()


def make_engine(sim: "AMRLBM") -> "StepEngine":
    mode = sim.cfg.stepping_mode
    assert mode in ENGINES, (mode, sorted(ENGINES))
    return ENGINES[mode](sim)


def _register(cls: type["StepEngine"]) -> type["StepEngine"]:
    ENGINES[cls.mode] = cls
    return cls


class StepEngine:
    """Shared state and hooks; subclasses fill in storage + the step loop."""

    mode: str = ""

    def __init__(self, sim: "AMRLBM") -> None:
        self.sim = sim
        self.cfg = sim.cfg
        self.arena: LevelArena | None = None
        self.arenas: RankArenas | None = None
        self._steppers: dict[int, Callable] = {}
        self._fused_steppers: dict[int, Callable] = {}
        # device mask cache; keys: level (arena) or (level, ranks) (sharded)
        self._mask_dev: dict = {}
        # ghost-exchange plans keyed by active level set; valid between arena
        # adoptions (restack rebinds arrays per substep, so no caching there)
        self._halo_plans: dict | None = {}
        self._cache_version = -1  # last storage version the caches were built for
        self._program_fresh = False  # a device program was built, not yet called

    # -- kernel steppers -------------------------------------------------------
    stepper_factory = staticmethod(make_arena_stream_collide)

    def _stepper_kwargs(self, level: int) -> dict:
        cfg = self.cfg
        return dict(
            omega=omega_for_level(cfg.omega, level),
            lattice=self.sim.spec.lattice,
            u_wall=cfg.u_lid,
            collision=cfg.collision,
            backend=cfg.kernel_backend,
        )

    def _halo_stepper_factory(self, masks_host: dict[int, np.ndarray]):
        """``(level, dst_slot, dst_cell, slabs=None) -> step(f, vals)``
        builder for the halo-in-tile superstep paths; ``masks_host`` are host
        mask stacks (copied — the factory's premask constants must not alias
        mutable arena storage)."""

        def factory(level: int, dst_slot: np.ndarray, dst_cell: np.ndarray,
                    slabs=None):
            return make_halo_stream_collide(
                dst_slot, dst_cell, level=level, mask=masks_host[level],
                slabs=slabs, **self._stepper_kwargs(level),
            )

        return factory

    def _stepper(self, level: int) -> Callable:
        if level not in self._steppers:
            self._steppers[level] = self.stepper_factory(**self._stepper_kwargs(level))
        return self._steppers[level]

    def _fused_stepper(self, level: int) -> Callable:
        """Pure ``step(f, mask) -> f`` for compiled programs (traced inline
        by the device-resident engines; cached separately from the in-place
        arena steppers)."""
        if level not in self._fused_steppers:
            self._fused_steppers[level] = make_stream_collide(
                **self._stepper_kwargs(level)
            )
        return self._fused_steppers[level]

    def _first_call(self):
        """The ``compile:superstep`` stage around the first call of a device
        program the engine has just built (its trace, compile or cache load,
        and dispatch); the shared no-op span on every later call."""
        if not self._program_fresh:
            return NULL_SPAN
        self._program_fresh = False
        return _TR.stage("superstep", cat="compile")

    # -- storage / invalidation ------------------------------------------------
    def storage_version(self) -> int:
        if self.arena is not None:
            return self.arena.version
        if self.arenas is not None:
            return self.arenas.version
        return -1

    def adopt(self, forest: "BlockForest") -> None:
        """Rebind storage after a topology change (AMR event, restore)."""
        if self.arena is not None:
            self.arena.adopt(forest)
        if self.arenas is not None:
            self.arenas.adopt(forest)

    def sync_caches(self) -> None:
        """Drop device masks and ghost plans if the arena(s) rebound storage
        since they were built — invalidation by mechanism, not by call-site
        discipline (any future adopt site is covered automatically)."""
        version = self.storage_version()
        if self._halo_plans is not None and self._cache_version != version:
            self._mask_dev.clear()
            self._halo_plans.clear()
            self._cache_version = version

    def masks_refreshed(self) -> None:
        """Host-side mask write happened: device mask copies are stale."""
        self._mask_dev.clear()

    def pdfs_written(self) -> None:
        """Host ``Block.data["pdf"]`` views were written after
        :meth:`materialize_host`: any device copy of the pdfs is stale and
        the next step uploads the host state (no-op in the host-resident
        modes, which step the host views themselves)."""

    def materialize_host(self) -> None:
        """Flush device-newer buffers so ``Block.data`` views are current
        (no-op in the host-resident modes)."""

    # -- ghost exchange --------------------------------------------------------
    def exchange_ghosts(self, active: set[int] | None = None) -> None:
        """Refresh pdf ghost layers for the active levels, attributing the
        wall time (and, for the sharded engines, the p2p traffic the exchange
        put on the fabric) to the "halo" data-plane stage."""
        self.sync_caches()  # an external adopt() must not replay stale plans
        # arena storage is versioned (adopt bumps it on every topology /
        # storage change), so the plan-cache guard is an O(1) token compare
        # instead of the default O(blocks) binding scan
        token = self.storage_version() if self._halo_plans is not None else None
        with _TR.stage("halo", cat="stage") as sp:
            fill_ghost_layers(
                self.sim.forest,
                self.sim.fields,
                fields=("pdf",),
                levels=active,
                plan_cache=self._halo_plans,
                cache_token=token,
            )
        self.sim.data_stats["halo"].add(StageStats(seconds=sp.seconds))

    # -- stepping --------------------------------------------------------------
    def advance(self, coarse_steps: int) -> None:
        """Host substep loop: per-level activity sets, ghost exchange, then
        stream+collide finest-first (device engines override wholesale)."""
        sim = self.sim
        levels = sim.forest.levels_in_use()
        lmax = max(levels)
        for _ in range(coarse_steps):
            for s in range(2**lmax):
                active = {l for l in levels if s % (2 ** (lmax - l)) == 0}
                self.exchange_ghosts(active)
                with _TR.stage("step", cat="stage") as sp:
                    for l in sorted(active, reverse=True):
                        self.step_level(l)
                sim.data_stats["step"].add(StageStats(seconds=sp.seconds))

    def step_level(self, level: int) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    # -- Lagrangian tracers ----------------------------------------------------
    def particle_batches(
        self, level: int
    ) -> list[tuple[np.ndarray, np.ndarray, dict[int, int], list["Block"]]]:
        """(pdf stack, mask stack, bid->slot, blocks) advection groups for one
        level (host views must be current — the driver materializes first)."""
        arena = self.arena
        pdf = arena.buffer(level, "pdf")
        if pdf is None or pdf.shape[0] == 0:
            return []
        blocks = [b for b in self.sim.forest.all_blocks() if b.level == level]
        return [(pdf, arena.buffer(level, "mask"), arena.slots(level), blocks)]


@_register
class RestackEngine(StepEngine):
    """The seed data plane: stack every block of a level into a fresh array
    each substep and copy the results back out — the benchmark baseline."""

    mode = "restack"
    stepper_factory = staticmethod(make_stream_collide)

    def __init__(self, sim: "AMRLBM") -> None:
        super().__init__(sim)
        self._halo_plans = None  # arrays rebind every substep: nothing to cache

    def step_level(self, level: int) -> None:
        blocks = [b for b in self.sim.forest.all_blocks() if b.level == level]
        if not blocks:
            return
        f = jnp.asarray(np.stack([b.data["pdf"] for b in blocks]))
        m = jnp.asarray(np.stack([b.data["mask"] for b in blocks]))
        f = self._stepper(level)(f, m)
        # repro: host-ok(restack-mode copy-out contract: results return to host block storage)
        out = np.array(f)
        for i, b in enumerate(blocks):
            b.data["pdf"] = out[i]

    def particle_batches(self, level: int):
        blocks = sorted(
            (b for b in self.sim.forest.all_blocks() if b.level == level),
            key=lambda b: b.bid,
        )
        if not blocks:
            return []
        pdf = np.stack([b.data["pdf"] for b in blocks])
        mask = np.stack([b.data["mask"] for b in blocks])
        return [(pdf, mask, {b.bid: i for i, b in enumerate(blocks)}, blocks)]


@_register
class ArenaEngine(StepEngine):
    """Persistent per-level SoA buffers stepped in place (host-resident)."""

    mode = "arena"

    def __init__(self, sim: "AMRLBM") -> None:
        super().__init__(sim)
        self.arena = LevelArena(sim.fields)

    def _level_mask(self, level: int) -> jax.Array:
        """Device-resident (B, X, Y, Z) mask stack, cached across substeps."""
        self.sync_caches()
        m = self._mask_dev.get(level)
        if m is None:
            m = jnp.asarray(self.arena.buffer(level, "mask"))
            self._mask_dev[level] = m
        return m

    def step_level(self, level: int) -> None:
        buf = self.arena.buffer(level, "pdf")
        if buf is None or buf.shape[0] == 0:
            return
        # in-place: reads and writes the persistent level buffer directly
        self._stepper(level)(buf, self._level_mask(level))


@_register
class FusedEngine(ArenaEngine):
    """Device-resident single-arena mode: the whole ``2^lmax`` substep cycle
    is one jitted program over the arena's :class:`DeviceResidency`."""

    mode = "fused"

    def __init__(self, sim: "AMRLBM") -> None:
        super().__init__(sim)
        # fused superstep program cache: (arena version, level tuple) -> fn
        self._fused_fn = None
        self._fused_key: tuple | None = None

    def masks_refreshed(self) -> None:
        super().masks_refreshed()
        # host-side write: device mask copies (and the fused program that
        # baked them in) are stale
        self.arena.device().drop(name="mask")
        self._fused_fn = None
        self._fused_key = None

    def pdfs_written(self) -> None:
        # the residency asserts if this would discard an un-flushed step
        self.arena.device().drop(name="pdf")

    def materialize_host(self) -> None:
        self.arena.device().flush()

    def _fused_program(self) -> tuple[Callable, tuple[int, ...]]:
        """Get-or-build the jitted superstep for the current forest: compiled
        ghost plans for every activity pattern + per-level steppers + device
        masks, cached until the next AMR event (arena version) or mask
        refresh."""
        forest = self.sim.forest
        levels = tuple(sorted(forest.levels_in_use()))
        key = (self.arena.version, levels)
        if self._fused_fn is not None and self._fused_key == key:
            return self._fused_fn, levels
        with _TR.span("build:fused_superstep", cat="compile",
                      version=self.arena.version):
            lmax = levels[-1]
            slots = {l: self.arena.slots(l) for l in levels}
            plans = {
                p: compile_ghost_plan(
                    forest,
                    self.sim.fields,
                    slots,
                    fields=("pdf",),
                    levels={l for l in levels if l >= lmax - p},
                )
                for p in range(lmax + 1)
            }
            res = self.arena.device()
            # repro: host-ok(mask copy at program build, once per arena version)
            masks_host = {l: np.array(self.arena.buffer(l, "mask")) for l in levels}
            self._fused_fn = make_fused_superstep(
                levels=levels,
                plans=plans,
                steppers={l: self._fused_stepper(l) for l in levels},
                masks={l: res.fetch(l, "mask") for l in levels},
                halo_stepper_factory=self._halo_stepper_factory(masks_host),
                spec=self.sim.spec,
            )
        self._fused_key = key
        self._program_fresh = True
        return self._fused_fn, levels

    def advance(self, coarse_steps: int) -> None:
        """Run whole coarse steps on device: one program call each, zero host
        transfers in steady state (uploads only after AMR events / mask
        refreshes; downloads only when diagnostics or the control plane
        materialize host views). The superstep donates its pdf tuple, so
        each call consumes the previous arrays (ping-pong in place) — the
        fresh outputs are stored back into the residency immediately."""
        fn, levels = self._fused_program()
        res = self.arena.device()
        pdfs = tuple(res.fetch(l, "pdf") for l in levels)
        nsub = 1 << levels[-1]
        with _TR.stage("fused", cat="stage", coarse_steps=coarse_steps) as sp:
            for _ in range(coarse_steps):
                with self._first_call():
                    pdfs = fn(pdfs)
            # repro: host-ok(timing fence: StageStats seconds must not hide queued device work)
            jax.block_until_ready(pdfs)
            for l, arr in zip(levels, pdfs):
                res.store(l, "pdf", arr)
        self.sim.data_stats["fused"].add(
            StageStats(seconds=sp.seconds, exchange_rounds=coarse_steps * nsub)
        )


@_register
class ShardedEngine(StepEngine):
    """The rank-partitioned host data plane: per-rank arenas, in-place
    intra-rank halo copies, cross-rank faces as batched p2p messages."""

    mode = "sharded"

    def __init__(self, sim: "AMRLBM") -> None:
        super().__init__(sim)
        self.arenas = RankArenas(sim.fields, sim.cfg.nranks)

    def _group_mask(self, level: int, ranks: tuple[int, ...]) -> jax.Array:
        """Device mask for a batched group of rank buffers."""
        self.sync_caches()
        key = (level, ranks)
        m = self._mask_dev.get(key)
        if m is None:
            parts = [self.arenas.buffer(r, level, "mask") for r in ranks]
            m = jnp.asarray(parts[0] if len(parts) == 1 else np.concatenate(parts))
            self._mask_dev[key] = m
        return m

    def exchange_ghosts(self, active: set[int] | None = None) -> None:
        self.sync_caches()
        token = self.storage_version()
        comm = self.sim.comm
        s0 = comm.stats.summary()
        with _TR.stage("halo", cat="stage") as sp:
            fill_ghost_layers_sharded(
                self.sim.forest,
                self.sim.fields,
                comm,
                fields=("pdf",),
                levels=active,
                plan_cache=self._halo_plans,
                cache_token=token,
            )
        self.sim.data_stats["halo"].add(
            StageStats.delta(s0, comm.stats.summary(), sp.seconds)
        )

    def step_level(self, level: int) -> None:
        """One kernel call per rank per level, batched where shapes agree:
        ranks whose level buffers hold the same block count share one call
        (their stacked shapes are identical, so one jit specialization and
        one device round-trip cover the whole group)."""
        per_rank = [
            (r, buf)
            for r in range(self.cfg.nranks)
            if (buf := self.arenas.buffer(r, level, "pdf")) is not None
            and buf.shape[0] > 0
        ]
        by_count: dict[int, list[tuple[int, np.ndarray]]] = {}
        for r, buf in per_rank:
            by_count.setdefault(buf.shape[0], []).append((r, buf))
        stepper = self._stepper(level)
        for nblocks, group in sorted(by_count.items()):
            ranks = tuple(r for r, _ in group)
            mask = self._group_mask(level, ranks)
            if len(group) == 1:
                stepper(group[0][1], mask)  # in-place on the rank's buffer
                continue
            cat = np.concatenate([buf for _, buf in group])
            stepper(cat, mask)
            for i, (_r, buf) in enumerate(group):
                np.copyto(buf, cat[i * nblocks : (i + 1) * nblocks])

    def particle_batches(self, level: int):
        """Per-rank batches over that rank's own buffers, so a rank's tracers
        read only the rank's own memory."""
        out = []
        for r in range(self.cfg.nranks):
            arena = self.arenas.per_rank[r]
            pdf = arena.buffer(level, "pdf")
            if pdf is None or pdf.shape[0] == 0:
                continue
            blocks = [
                b
                for b in self.sim.forest.local_blocks(r).values()
                if b.level == level
            ]
            out.append(
                (pdf, arena.buffer(level, "mask"), arena.slots(level), blocks)
            )
        return out


@dataclass
class _RankPrograms:
    """Compiled per-rank substep programs for one (storage version, level
    set): emit/absorb jitted closures per (activity pattern, rank) plus the
    message routing tables the advance loop feeds the ``Comm`` fabric from."""

    levels: tuple[int, ...]
    nsub: int
    pattern: list[int]
    ranks: tuple[int, ...]
    rank_levels: dict[int, tuple[int, ...]]
    emits: dict[int, dict[int, Callable]] = field(default_factory=dict)
    absorbs: dict[int, dict[int, Callable]] = field(default_factory=dict)
    # interior/boundary split pair (exclusive with absorbs[p][r]): interior
    # steps while the host routes payloads, boundary consumes the messages
    interiors: dict[int, dict[int, Callable]] = field(default_factory=dict)
    boundaries: dict[int, dict[int, Callable]] = field(default_factory=dict)
    sends: dict[int, dict[int, list]] = field(default_factory=dict)
    recvs: dict[int, dict[int, list]] = field(default_factory=dict)
    has_messages: dict[int, bool] = field(default_factory=dict)


@_register
class FusedShardedEngine(ShardedEngine):
    """Device-resident rank-sharded mode: each rank's substep runs as jitted
    programs over its own :class:`DeviceResidency`, and cross-rank halo
    patches travel as device-built per-rank-pair message buffers through
    ``Comm`` — one p2p message per neighboring pair per exchange, zero
    host<->device transfers per substep (host contact only at AMR events).
    """

    mode = "fused_sharded"

    def __init__(self, sim: "AMRLBM") -> None:
        super().__init__(sim)
        self._programs_cache: _RankPrograms | None = None
        self._programs_key: tuple | None = None

    def masks_refreshed(self) -> None:
        super().masks_refreshed()
        for arena in self.arenas.per_rank:
            if arena._residency is not None:
                arena.device().drop(name="mask")
        self._programs_cache = None
        self._programs_key = None

    def pdfs_written(self) -> None:
        for arena in self.arenas.per_rank:
            if arena._residency is not None:
                arena.device().drop(name="pdf")

    def materialize_host(self) -> None:
        for arena in self.arenas.per_rank:
            if arena._residency is not None:
                arena.device().flush()

    def _programs(self) -> _RankPrograms:
        forest = self.sim.forest
        levels = tuple(sorted(forest.levels_in_use()))
        key = (self.arenas.version, levels)
        if self._programs_cache is not None and self._programs_key == key:
            return self._programs_cache
        with _TR.span("build:rank_programs", cat="compile",
                      version=self.arenas.version):
            self._programs_cache = self._build_programs(forest, levels)
        self._programs_key = key
        return self._programs_cache

    def _build_programs(self, forest: "BlockForest",
                        levels: tuple[int, ...]) -> _RankPrograms:
        lmax = levels[-1]
        nsub = 1 << lmax
        per_rank = self.arenas.per_rank
        ranks = tuple(r for r in range(self.cfg.nranks) if per_rank[r].levels())
        rank_levels = {r: tuple(per_rank[r].levels()) for r in ranks}
        rank_slots = {
            r: {l: per_rank[r].slots(l) for l in rank_levels[r]} for r in ranks
        }
        # pattern of substep s = trailing zeros of s (s=0 activates everything)
        pattern = [
            lmax if s == 0 else min((s & -s).bit_length() - 1, lmax)
            for s in range(nsub)
        ]
        progs = _RankPrograms(
            levels=levels,
            nsub=nsub,
            pattern=pattern,
            ranks=ranks,
            rank_levels=rank_levels,
        )
        for p in range(lmax + 1):
            active = {l for l in levels if l >= lmax - p}
            plan = compile_rank_halo_plan(
                forest, self.sim.fields, rank_slots, fields=("pdf",), levels=active
            )
            progs.has_messages[p] = bool(plan.messages)
            progs.emits[p] = {}
            progs.absorbs[p] = {}
            progs.interiors[p] = {}
            progs.boundaries[p] = {}
            progs.sends[p] = {}
            progs.recvs[p] = {}
            for r in ranks:
                idx = {l: i for i, l in enumerate(rank_levels[r])}
                res = per_rank[r].device()
                sends = [m for m in plan.messages if m.src_rank == r]
                recvs = [m for m in plan.messages if m.dst_rank == r]
                progs.sends[p][r] = sends
                progs.recvs[p][r] = recvs
                emit = make_rank_emit(sends, idx)
                if emit is not None:
                    progs.emits[p][r] = emit
                local = plan.local.get(r)
                rank_active = active & set(rank_levels[r])
                if not recvs and not rank_active and not (local and local.ops):
                    # the rank is idle in this pattern (e.g. it owns only
                    # coarse blocks and a fine-only substep is running):
                    # don't compile — and don't dispatch — an identity program
                    continue
                steppers = {l: self._fused_stepper(l) for l in rank_levels[r]}
                masks_dev = {l: res.fetch(l, "mask") for l in rank_levels[r]}
                masks_host = {
                    # repro: host-ok(mask copy at program build, once per arena version)
                    l: np.array(per_rank[r].buffer(l, "mask"))
                    for l in rank_levels[r]
                }
                bnd = boundary_slot_sets(
                    recvs, {l: masks_host[l] for l in rank_active}
                )
                n_interior = sum(
                    masks_host[l].shape[0] - len(bnd.get(l, ()))
                    for l in rank_active
                )
                if recvs and n_interior > 0:
                    # boundary blocks wait for inbound payloads; interior
                    # blocks don't — split so the host-side message routing
                    # overlaps the interior stepping dispatched before it
                    progs.interiors[p][r], progs.boundaries[p][r] = (
                        make_rank_absorb_split(
                            recvs,
                            local,
                            idx,
                            steppers=steppers,
                            masks=masks_dev,
                            active_levels=rank_active,
                        )
                    )
                else:
                    progs.absorbs[p][r] = make_rank_absorb(
                        recvs,
                        local,
                        idx,
                        steppers=steppers,
                        masks=masks_dev,
                        active_levels=rank_active,
                        halo_stepper_factory=self._halo_stepper_factory(masks_host),
                        spec=self.sim.spec,
                    )
        return progs

    def advance(self, coarse_steps: int) -> None:
        """Run whole coarse steps with per-rank device programs: the only
        per-substep host involvement is routing device-resident message
        buffers through ``Comm`` (the fabric sees exactly the same p2p shape
        as the host-sharded mode, with identical byte accounting).

        Dispatch order per substep implements the latency-hiding split:
        every rank's ``emit`` (payload build) and ``interior`` program is
        dispatched *before* the host touches the fabric, so the Python-side
        send/exchange/routing runs while the device is still chewing on
        payload gathers and interior stepping (JAX dispatch is async); only
        the ``boundary``/``absorb`` programs — which consume inbound
        payloads — wait for routing. Emits read the pre-step buffers the
        interior programs then consume by donation; the runtime sequences
        the donated write after the pending reads."""
        progs = self._programs()
        comm = self.sim.comm
        res = {r: self.arenas.per_rank[r].device() for r in progs.ranks}
        pdfs = {
            r: tuple(res[r].fetch(l, "pdf") for l in progs.rank_levels[r])
            for r in progs.ranks
        }
        s0 = comm.stats.summary()
        with _TR.stage("fused", cat="stage", coarse_steps=coarse_steps) as st:
            for _ in range(coarse_steps):
                for s in range(progs.nsub):
                    p = progs.pattern[s]
                    # the route span's `overlapped` flag marks whether this
                    # pattern dispatched interior programs before routing —
                    # the quantity trace_report's overlap efficiency reads
                    overlapped = bool(progs.interiors[p])
                    payloads = []
                    for r in progs.ranks:
                        emit = progs.emits[p].get(r)
                        if emit is not None:
                            with _TR.span("emit", cat="substep", rank=r,
                                          substep=s, pattern=p):
                                payloads.append((r, emit(pdfs[r])))
                    for r in progs.ranks:
                        interior = progs.interiors[p].get(r)
                        if interior is not None:
                            with _TR.span("interior", cat="substep", rank=r,
                                          substep=s, pattern=p):
                                pdfs[r] = interior(pdfs[r])
                    with _TR.span("route", cat="substep", substep=s,
                                  pattern=p, overlapped=overlapped) as rt:
                        nbytes = 0
                        for r, arrs in payloads:
                            for m, arr in zip(progs.sends[p][r], arrs):
                                comm.send(
                                    m.src_rank, m.dst_rank, "halo",
                                    (m.key, arr), nbytes=m.nbytes,
                                )
                                nbytes += m.nbytes
                        by_key = {}
                        if progs.has_messages[p]:
                            for _dst, msgs in comm.exchange().items():
                                for _tag, (mkey, arr) in msgs:
                                    by_key[mkey] = arr
                        rt.set(bytes=nbytes)
                    for r in progs.ranks:
                        boundary = progs.boundaries[p].get(r)
                        if boundary is not None:
                            with _TR.span("absorb", cat="substep", rank=r,
                                          substep=s, pattern=p, split=True):
                                msgs = tuple(
                                    by_key[m.key] for m in progs.recvs[p][r]
                                )
                                pdfs[r] = boundary(pdfs[r], msgs)
                            continue
                        absorb = progs.absorbs[p].get(r)
                        if absorb is None:  # rank is idle in this pattern
                            continue
                        with _TR.span("absorb", cat="substep", rank=r,
                                      substep=s, pattern=p, split=False):
                            msgs = tuple(by_key[m.key] for m in progs.recvs[p][r])
                            pdfs[r] = absorb(pdfs[r], msgs)
            # repro: host-ok(timing fence: StageStats seconds must not hide queued device work)
            jax.block_until_ready([pdfs[r] for r in progs.ranks])
            for r in progs.ranks:
                for l, arr in zip(progs.rank_levels[r], pdfs[r]):
                    res[r].store(l, "pdf", arr)
        stage = StageStats.delta(s0, comm.stats.summary(), st.seconds)
        # report in-program exchange rounds with the same meaning as the
        # fused engine (one logical ghost-exchange round per substep) rather
        # than the Comm superstep count the delta carries — the latter is 0
        # at one rank even though every substep exchanged intra-rank ghosts
        stage.exchange_rounds = coarse_steps * progs.nsub
        self.sim.data_stats["fused"].add(stage)


@dataclass
class _DevicePrograms:
    """One compiled SPMD superstep for a (storage version, level set): the
    shard_map'ed program plus the per-pattern message tables the advance loop
    feeds :meth:`~repro.core.comm.DeviceComm.ppermute` accounting from."""

    levels: tuple[int, ...]
    counts: dict[int, int]
    nsub: int
    pattern: list[int]
    fn: Callable
    messages: dict[int, tuple]
    rounds: dict[int, int]
    pad_bytes: dict[int, int]


@_register
class DeviceShardedEngine(ShardedEngine):
    """Real multi-device rank sharding: one XLA device per rank.

    Where ``fused_sharded`` *simulates* the distributed data plane (per-rank
    programs on one device, payloads routed through the host ``Comm``), this
    mode places each rank's block stacks on its own device via ``shard_map``
    over a 1-D mesh and moves halo payloads with ``jax.lax.ppermute`` inside
    the compiled program — no host involvement per substep at all, not even
    routing. Host devices are provisioned with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (see
    ``launch/env_preset.sh``); on a real TPU/GPU pod the same program maps
    onto the physical interconnect unchanged.

    Equal-blocks-per-rank padding makes the program SPMD: every level's stack
    is padded to the max per-rank block count with all-WALL masks and
    weight-vector PDFs — an exact fixed point of the stream+collide kernel
    (all-WALL streaming bounces the symmetric weights onto themselves and the
    final fluid blend returns the input), so padded slots are provably dead:
    never read by any halo plan (``verify_padded_plan``), unchanged by every
    step. The ``Comm`` fabric must be a :class:`~repro.core.comm.DeviceComm`
    (the driver wires this) so the in-program ppermute traffic lands in the
    same Table-1 counters as every other mode. The program does the same
    arithmetic as the host-fabric engines (:func:`make_device_superstep`);
    how it rounds is the backend's, so it agrees with them to float32
    round-off, not bit for bit by contract. Each rank fills its ghost layers
    as the fused engine does: same-level slabs held on the rank are copied
    block by block, and cross-level and inbound values are placed into the
    slabs by gather and select, with no per-cell scatter (the build records
    ``halo.slab_cells`` / ``halo.scatter_cells`` per level and rank).

    Pad slots are stepped like real ones: :meth:`slot_layout` gives, per
    level, the real blocks on each rank, the slots every step runs
    (``Bmax × nranks``) and the pad's share of them; each program build
    records them as telemetry (``device.real_blocks`` per level and rank,
    ``device.stepped_slots`` per level, ``device.pad_share`` per level).
    """

    mode = "device_sharded"

    def __init__(self, sim: "AMRLBM") -> None:
        super().__init__(sim)
        n = self.cfg.nranks
        ndev = jax.device_count()
        if ndev < n:
            raise RuntimeError(
                f"device_sharded needs one XLA device per rank: nranks={n} but "
                f"jax.device_count()={ndev}. Provision host devices with "
                "XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n} before the first jax import (launch/env_preset.sh does "
                "this), or lower cfg.nranks."
            )
        if not hasattr(sim.comm, "ppermute"):
            raise TypeError(
                "device_sharded requires a DeviceComm fabric so in-program "
                f"ppermute traffic is accounted; got {type(sim.comm).__name__}"
            )
        self.mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:n]),  # repro: host-ok(device handles, not array data)
            ("ranks",),
        )
        self._dev_programs: _DevicePrograms | None = None
        self._dev_programs_key: tuple | None = None
        self._dev_levels: tuple[int, ...] | None = None
        self._dev_pdfs: tuple | None = None
        self._dev_masks: tuple | None = None
        self._dev_version = -1
        self._host_stale = False  # device pdfs newer than the host arenas

    # -- storage / invalidation ------------------------------------------------
    def adopt(self, forest: "BlockForest") -> None:
        assert not self._host_stale, (
            "materialize_host() before adopt: device-resident steps would be "
            "lost rebinding the arenas"
        )
        super().adopt(forest)

    def masks_refreshed(self) -> None:
        super().masks_refreshed()
        self._dev_masks = None

    def pdfs_written(self) -> None:
        assert not self._host_stale, (
            "materialize_host() before writing host pdfs: the device holds "
            "newer steps the write would be mixed with"
        )
        self._dev_pdfs = None  # the next _ensure_device re-uploads the stacks

    def materialize_host(self) -> None:
        if not self._host_stale:
            return
        assert self._dev_pdfs is not None and self._dev_levels is not None
        with _TR.span("device:materialize_host", cat="transfer"):
            for i, l in enumerate(self._dev_levels):
                # repro: host-ok(AMR-event download: device-newer pdfs flush to the arenas)
                host = np.asarray(self._dev_pdfs[i])  # (R, Bmax, ...)
                for r in range(self.cfg.nranks):
                    buf = self.arenas.buffer(r, l, "pdf")
                    if buf is not None and buf.shape[0]:
                        np.copyto(buf, host[r, : buf.shape[0]])
        self._host_stale = False

    def exchange_ghosts(self, active: set[int] | None = None) -> None:
        # host-visible ghost refresh (post-AMR, pre-advection): flush device
        # steps first, then run the host-fabric exchange. The device copy's
        # interiors stay current (the exchange only writes ghost cells) and
        # its ghosts are re-exchanged in-program at the next substep 0, so
        # the device state is deliberately NOT invalidated here — same
        # contract as fused_sharded's residency.
        self.materialize_host()
        super().exchange_ghosts(active)

    def slot_layout(self) -> dict[int, dict]:
        """Per level of the current forest: ``blocks`` (real blocks on each
        rank), ``slots`` (the padded stack every step runs, ``Bmax ×
        nranks``) and ``pad_share`` (the share of those slots that are pad)."""
        nranks = self.cfg.nranks
        rank_slots = {
            r: {l: arena.slots(l) for l in arena.levels()}
            for r, arena in enumerate(self.arenas.per_rank)
        }
        out = {}
        for l, bmax in sorted(padded_block_counts(rank_slots, nranks).items()):
            blocks = [len(rank_slots[r].get(l, ())) for r in range(nranks)]
            out[l] = {"blocks": blocks, "slots": bmax * nranks,
                      "pad_share": 1.0 - sum(blocks) / (bmax * nranks)}
        return out

    # -- compiled programs -----------------------------------------------------
    def _programs(self) -> _DevicePrograms:
        forest = self.sim.forest
        levels = tuple(sorted(forest.levels_in_use()))
        key = (self.arenas.version, levels)
        if self._dev_programs is not None and self._dev_programs_key == key:
            return self._dev_programs
        with _TR.span("build:device_programs", cat="compile",
                      version=self.arenas.version):
            self._dev_programs = self._build_programs(forest, levels)
        self._dev_programs_key = key
        self._program_fresh = True
        return self._dev_programs

    def _build_programs(self, forest: "BlockForest",
                        levels: tuple[int, ...]) -> _DevicePrograms:
        lmax = levels[-1]
        nsub = 1 << lmax
        nranks = self.cfg.nranks
        per_rank = self.arenas.per_rank
        rank_slots = {
            r: {l: per_rank[r].slots(l) for l in per_rank[r].levels()}
            for r in range(nranks)
        }
        counts = padded_block_counts(rank_slots, nranks)
        if _TR.enabled:
            for l, lay in self.slot_layout().items():
                for r, n in enumerate(lay["blocks"]):
                    _TR.metrics.counter("device.real_blocks").inc(n, level=l, rank=r)
                _TR.metrics.counter("device.stepped_slots").inc(lay["slots"], level=l)
                _TR.metrics.gauge("device.pad_share").set(lay["pad_share"], level=l)
        pattern = [
            lmax if s == 0 else min((s & -s).bit_length() - 1, lmax)
            for s in range(nsub)
        ]
        fs = self.sim.fields.fields["pdf"]
        lead = int(np.prod(fs.shape, dtype=np.int64)) if fs.shape else 1
        itemsize = np.dtype(fs.dtype).itemsize
        plans: dict[int, object] = {}
        schedules: dict[int, tuple] = {}
        messages: dict[int, tuple] = {}
        rounds_n: dict[int, int] = {}
        pad_bytes: dict[int, int] = {}
        for p in range(lmax + 1):
            active = {l for l in levels if l >= lmax - p}
            plan = compile_rank_halo_plan(
                forest, self.sim.fields, rank_slots, fields=("pdf",),
                levels=active,
            )
            bad = verify_padded_plan(plan, rank_slots)
            assert not bad, bad  # no plan index may ever touch a padded slot
            sched = schedule_ppermute_rounds(plan.messages)
            plans[p] = plan
            schedules[p] = sched
            messages[p] = plan.messages
            rounds_n[p] = len(sched)
            pad_bytes[p] = (
                sum(rnd.pad_cells() for rnd in sched) * lead * itemsize
            )
        fn = make_device_superstep(
            mesh=self.mesh,
            levels=levels,
            plans=plans,
            schedules=schedules,
            steppers={l: self._fused_stepper(l) for l in levels},
            spec=self.sim.spec,
            counts=counts,
        )
        return _DevicePrograms(
            levels=levels,
            counts=counts,
            nsub=nsub,
            pattern=pattern,
            fn=fn,
            messages=messages,
            rounds=rounds_n,
            pad_bytes=pad_bytes,
        )

    # -- device residency ------------------------------------------------------
    def _ensure_device(self, progs: _DevicePrograms) -> None:
        """Upload the padded global stacks (once per storage version)."""
        version = self.arenas.version
        if self._dev_version != version or self._dev_levels != progs.levels:
            assert not self._host_stale  # adopt() already enforces the flush
            self._dev_pdfs = None
            self._dev_masks = None
        if self._dev_pdfs is not None and self._dev_masks is not None:
            return
        nranks = self.cfg.nranks
        sharding = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec("ranks")
        )
        lattice = self.sim.spec.lattice
        with _TR.span("device:upload", cat="transfer", version=version):
            if self._dev_pdfs is None:
                stacks = []
                for l in progs.levels:
                    bufs = [self.arenas.buffer(r, l, "pdf") for r in range(nranks)]
                    shape = next(b.shape[1:] for b in bufs if b is not None)
                    dtype = next(b.dtype for b in bufs if b is not None)
                    g = np.empty((nranks, progs.counts[l]) + shape, dtype)
                    # pad slots hold the weight vector — the all-WALL fixed
                    # point of the kernel (see the class docstring)
                    g[:] = np.asarray(  # repro: host-ok(lattice weights are a host constant)
                        lattice.w, dtype=dtype
                    ).reshape((lattice.Q,) + (1,) * 3)
                    for r, b in enumerate(bufs):
                        if b is not None and b.shape[0]:
                            g[r, : b.shape[0]] = b
                    stacks.append(jax.device_put(g, sharding))
                self._dev_pdfs = tuple(stacks)
            if self._dev_masks is None:
                stacks = []
                for l in progs.levels:
                    bufs = [self.arenas.buffer(r, l, "mask") for r in range(nranks)]
                    shape = next(b.shape[1:] for b in bufs if b is not None)
                    dtype = next(b.dtype for b in bufs if b is not None)
                    g = np.full(
                        (nranks, progs.counts[l]) + shape, CellType.WALL, dtype
                    )
                    for r, b in enumerate(bufs):
                        if b is not None and b.shape[0]:
                            g[r, : b.shape[0]] = b
                    stacks.append(jax.device_put(g, sharding))
                self._dev_masks = tuple(stacks)
        self._dev_version = version
        self._dev_levels = progs.levels

    def device_held_bytes_per_rank(self) -> int:
        """Per-device bytes of padded stepping state (equal on every rank by
        construction — the Table-1 boundedness quantity for this fabric)."""
        progs = self._programs()
        self._ensure_device(progs)
        n = self.cfg.nranks
        return sum(int(a.nbytes) // n for a in self._dev_pdfs + self._dev_masks)

    # -- stepping --------------------------------------------------------------
    def advance(self, coarse_steps: int) -> None:
        """Run whole coarse steps as one SPMD program per step: upload once
        per storage version, then every substep's emit/permute/absorb/step
        happens on-device; the host only attributes the known (compile-time)
        ppermute traffic into the ``DeviceComm`` counters."""
        progs = self._programs()
        self._ensure_device(progs)
        comm = self.sim.comm
        s0 = comm.stats.summary()
        with _TR.stage("fused", cat="stage", coarse_steps=coarse_steps) as st:
            pdfs = self._dev_pdfs
            for _ in range(coarse_steps):
                with _TR.span("device_superstep", cat="substep",
                              nsub=progs.nsub), self._first_call():
                    pdfs = progs.fn(pdfs, self._dev_masks)
                for s in range(progs.nsub):
                    p = progs.pattern[s]
                    if progs.messages[p]:
                        # repro: collective-ok(accounting mirror of the in-program ppermute rounds — p2p bytes, not a collective)
                        comm.ppermute(
                            progs.messages[p],
                            rounds=progs.rounds[p],
                            pad_bytes=progs.pad_bytes[p],
                        )
            # repro: host-ok(timing fence: StageStats seconds must not hide queued device work)
            jax.block_until_ready(pdfs)
            self._dev_pdfs = pdfs
        self._host_stale = True
        stage = StageStats.delta(s0, comm.stats.summary(), st.seconds)
        # same convention as the other fused engines: one logical ghost
        # exchange per substep, even where the fabric saw no cross-rank bytes
        stage.exchange_rounds = coarse_steps * progs.nsub
        self.sim.data_stats["fused"].add(stage)
