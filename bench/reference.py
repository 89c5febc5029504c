"""Plain reference for the lid-driven cavity cells.

It imports nothing of the program under test. What it knows of the method
comes from the configuration file: the velocity set in the program's pdf
order, the collision model and its rates, the cavity's walls and lid, the
block size and the forest's root grid. What it knows of a run's state comes
from block ids and interior pdfs alone; the ghost layers, the cell types,
the ghost-fill tables and the level rates are worked out here.

* :func:`step_blocks` advances a block forest by whole coarse steps: per
  substep ``s`` of ``2^lmax`` the levels ``l`` with ``s % 2^(lmax-l) == 0``
  are active; every active level's ghost cells are filled from the state
  before the substep (same level: copy; finer neighbour: mean of the 2x2x2
  fine cells; coarser neighbour: the covering coarse cell), then every
  active level takes one pull-stream, bounce-back, TRT-collide step with its
  acoustically scaled rate. It runs in jax.numpy at a chosen dtype (the
  control runs it in bfloat16).
* :class:`MigrationReference` follows the pdf field through AMR cycles on a
  dense grid at the finest level's resolution: refinement replicates a cell
  into its 2x2x2 children, coarsening replaces them by their mean. For the
  two-level stress cycle it also predicts each cycle's topology.
"""

from __future__ import annotations

import functools

import ml_dtypes  # noqa: F401  (registers bfloat16 as a numpy dtype, for the control)
import numpy as np

FLUID, WALL, LID = 0, 1, 2


# -- the forest, from block ids ---------------------------------------------------


def root_bits(root_grid) -> int:
    n = int(np.prod(root_grid))
    return max(1, (max(n - 1, 0)).bit_length())


def decode_bid(bid: int, root_grid) -> tuple[int, tuple[int, int, int]]:
    """(level, block coordinates in level-``level`` block units).

    A root id is ``1 << root_bits | root_index`` with ``root_index = x + RX*(y
    + RY*z)``; each level appends an octant ``x | y<<1 | z<<2``."""
    rb = root_bits(root_grid)
    level = (int(bid).bit_length() - 1 - rb) // 3
    root = (int(bid) >> (3 * level)) & ((1 << rb) - 1)
    rx, ry, _ = root_grid
    x, y, z = root % rx, (root // rx) % ry, root // (rx * ry)
    for k in range(level - 1, -1, -1):
        o = (int(bid) >> (3 * k)) & 7
        x, y, z = 2 * x + (o & 1), 2 * y + ((o >> 1) & 1), 2 * z + ((o >> 2) & 1)
    return level, (x, y, z)


def child_bids(bid: int) -> list[int]:
    return [(int(bid) << 3) | o for o in range(8)]


class Forest:
    """Blocks of one topology: per level, ascending bids and their origins in
    that level's cells; an owner map at the finest level's resolution."""

    def __init__(self, bids, *, root_grid, cells: int, max_level: int):
        self.root_grid = tuple(int(r) for r in root_grid)
        self.n = int(cells)
        self.L = int(max_level)
        self.levels: dict[int, list[int]] = {}
        self.origin: dict[int, np.ndarray] = {}  # level -> (B, 3) in level cells
        for bid in sorted(int(b) for b in bids):
            lvl, _ = decode_bid(bid, self.root_grid)
            if lvl > self.L:
                raise ValueError(f"block {bid:#x} at level {lvl} > max_level {self.L}")
            self.levels.setdefault(lvl, []).append(bid)
        for lvl, bl in self.levels.items():
            self.origin[lvl] = np.array(
                [decode_bid(b, self.root_grid)[1] for b in bl], dtype=np.int64
            ) * self.n
        fine = np.array(self.root_grid) * self.n << self.L
        self.extent_fine = tuple(int(v) for v in fine)
        # owner: (level, index in level) of the block covering each finest cell
        self.owner_level = np.full(self.extent_fine, -1, dtype=np.int8)
        self.owner_index = np.full(self.extent_fine, -1, dtype=np.int32)
        for lvl, org in self.origin.items():
            s = 1 << (self.L - lvl)
            side = self.n * s
            for i, o in enumerate(org * s):
                sl = tuple(slice(int(o[d]), int(o[d]) + side) for d in range(3))
                if (self.owner_level[sl] != -1).any():
                    raise ValueError("blocks overlap")
                self.owner_level[sl] = lvl
                self.owner_index[sl] = i
        if (self.owner_level == -1).any():
            raise ValueError("blocks do not cover the domain")

    def bids(self) -> list[int]:
        return [b for l in sorted(self.levels) for b in self.levels[l]]

    def extent(self, level: int) -> np.ndarray:
        return np.array(self.root_grid, dtype=np.int64) * self.n << level


# -- cell types from the cavity's geometry ---------------------------------------


def cell_types(forest: Forest, level: int) -> np.ndarray:
    """(B, n+2, n+2, n+2) cell types of a level's blocks, ghosts included:
    cells whose centre lies outside the cavity are walls, cells above its top
    face are the moving lid."""
    n = forest.n
    ext = forest.extent(level)
    org = forest.origin[level]
    ax = np.arange(-1, n + 1)
    out = np.zeros((len(org), n + 2, n + 2, n + 2), dtype=np.int8)
    for i, o in enumerate(org):
        x = (o[0] + ax)[:, None, None]
        y = (o[1] + ax)[None, :, None]
        z = (o[2] + ax)[None, None, :]
        outside = (x < 0) | (x >= ext[0]) | (y < 0) | (y >= ext[1]) | (z < 0)
        t = np.where(outside, WALL, FLUID)
        out[i] = np.where(np.broadcast_to(z >= ext[2], t.shape), LID, t)
    return out


# -- ghost-fill tables -------------------------------------------------------------


def _ghost_template(n: int) -> np.ndarray:
    """(G, 3) local coordinates of a block's ghost cells, ghosts at 0 and n+1."""
    g = np.stack(np.meshgrid(*[np.arange(n + 2)] * 3, indexing="ij"), -1).reshape(-1, 3)
    return g[((g == 0) | (g == n + 1)).any(axis=1)]


def _flat(local: np.ndarray, n: int) -> np.ndarray:
    m = n + 2
    return (local[..., 0] * m + local[..., 1]) * m + local[..., 2]


def fill_tables(forest: Forest, level: int) -> dict[str, tuple]:
    """Where every in-domain ghost cell of a level's blocks takes its value.

    Returns ``kind -> (dst_block, dst_cell, src_block, src_cell)`` for kinds
    ``same`` and ``coarse`` (src arrays ``(N,)``) and ``fine`` (``(N, 8)``);
    cells are flat indices into a block's ``(n+2)^3`` grid."""
    n, L = forest.n, forest.L
    tmpl = _ghost_template(n)
    org = forest.origin[level]
    gl = org[:, None, :] + tmpl[None, :, :] - 1  # (B, G, 3) level cells
    inside = ((gl >= 0) & (gl < forest.extent(level))).all(-1)
    blk = np.broadcast_to(np.arange(len(org))[:, None], inside.shape)[inside]
    loc = np.broadcast_to(tmpl[None], gl.shape)[inside]
    gl = gl[inside]
    s = 1 << (L - level)
    fine = gl * s
    own_l = forest.owner_level[fine[:, 0], fine[:, 1], fine[:, 2]].astype(np.int64)
    own_i = forest.owner_index[fine[:, 0], fine[:, 1], fine[:, 2]].astype(np.int64)
    out = {}
    for kind, m in (("same", level), ("coarse", level - 1), ("fine", level + 1)):
        sel = own_l == m
        if not sel.any():
            continue
        src_org = forest.origin[m][own_i[sel]]
        if kind == "same":
            src = _flat(gl[sel] - src_org + 1, n)
        elif kind == "coarse":
            src = _flat(gl[sel] // 2 - src_org + 1, n)
        else:
            octs = np.array([[(o >> 0) & 1, (o >> 1) & 1, (o >> 2) & 1] for o in range(8)])
            fc = 2 * gl[sel][:, None, :] + octs[None]  # (N, 8, 3)
            src = _flat(fc - src_org[:, None, :] + 1, n)
        src_blk = own_i[sel]
        if kind == "fine":
            src_blk = np.broadcast_to(src_blk[:, None], src.shape)
        out[kind] = (
            blk[sel].astype(np.int32), _flat(loc[sel], n).astype(np.int32),
            np.ascontiguousarray(src_blk).astype(np.int32), src.astype(np.int32),
        )
    if ((own_l < level - 1) | (own_l > level + 1)).any():
        raise ValueError("neighbouring blocks differ by more than one level")
    return out


# -- one level's stream-collide step ---------------------------------------------------


def level_omega(omega0: float, level: int) -> float:
    """Acoustic scaling: tau_l - 1/2 = 2^l (tau_0 - 1/2)."""
    return 1.0 / (0.5 + 2.0**level * (1.0 / omega0 - 0.5))


def _stream_collide(f, types, *, c, w, opp, omega, magic, u_lid, dt):
    """One pull-stream, bounce-back, TRT step of a (B, Q, X, Y, Z) stack."""
    import jax.numpy as jnp

    Q = len(w)
    lid = [dt.type(6.0 * w[q] * float(c[q] @ np.asarray(u_lid))) for q in range(Q)]
    om_p = omega
    om_m = 1.0 / (magic / (1.0 / omega - 0.5) + 0.5)
    f_in = []
    for q in range(Q):
        sh = tuple(int(v) for v in c[q])
        src_t = jnp.roll(types, sh, axis=(1, 2, 3))
        pulled = jnp.roll(f[:, q], sh, axis=(1, 2, 3))
        bounced = f[:, opp[q]] + jnp.where(src_t == LID, lid[q], dt.type(0))
        f_in.append(jnp.where(src_t == FLUID, pulled, bounced))
    rho = functools.reduce(lambda a, b: a + b, f_in)
    mom = []
    for d in range(3):
        acc = jnp.zeros_like(rho)
        for q in range(Q):
            if c[q, d] > 0:
                acc = acc + f_in[q]
            elif c[q, d] < 0:
                acc = acc - f_in[q]
        mom.append(acc / rho)
    usq = mom[0] * mom[0] + mom[1] * mom[1] + mom[2] * mom[2]
    feq = []
    for q in range(Q):
        cu = sum((mom[d] * dt.type(c[q, d]) for d in range(3) if c[q, d]),
                 jnp.zeros_like(rho))
        feq.append(dt.type(w[q]) * rho * (dt.type(1) + dt.type(3) * cu
                   + dt.type(4.5) * cu * cu - dt.type(1.5) * usq))
    out = []
    half = dt.type(0.5)
    for q in range(Q):
        o = opp[q]
        sym = half * (f_in[q] + f_in[o]) - half * (feq[q] + feq[o])
        anti = half * (f_in[q] - f_in[o]) - half * (feq[q] - feq[o])
        new = f_in[q] - dt.type(om_p) * sym - dt.type(om_m) * anti
        out.append(jnp.where(types == FLUID, new, f[:, q]))
    return jnp.stack(out, axis=1)


def _fill(bufs, level: int, tables, dt):
    """Ghost values of ``level`` gathered from the pre-substep buffers."""
    import jax.numpy as jnp

    dst_b, dst_c, vals = [], [], []
    for kind, (db, dc, sb, sc) in sorted(tables.items()):
        src = bufs[{"same": level, "coarse": level - 1, "fine": level + 1}[kind]]
        v = src.reshape(src.shape[0], src.shape[1], -1)[sb, :, sc]  # (N, Q) | (N, 8, Q)
        if kind == "fine":
            v = v.sum(axis=1) * dt.type(0.125)
        dst_b.append(db)
        dst_c.append(dc)
        vals.append(v)
    return jnp.concatenate(dst_b), jnp.concatenate(dst_c), jnp.concatenate(vals)


@functools.lru_cache(maxsize=None)
def _coarse_step(levels: tuple, lat_key: tuple, omega0: float, magic: float,
                 u_lid: tuple, dtype_name: str):
    """A jitted ``(bufs, types, tables) -> bufs`` of one coarse step; the
    fill tables are arguments, so the program holds no topology."""
    import jax
    import jax.numpy as jnp

    c = np.array(lat_key[0], dtype=np.int64)
    w = np.array(lat_key[1], dtype=np.float64)
    opp = [int(np.flatnonzero((c == -c[q]).all(1))[0]) for q in range(len(w))]
    dt = jnp.dtype(dtype_name)
    lmax = levels[-1]

    def coarse(bufs, types, tables):
        bufs = dict(zip(levels, bufs))
        types = dict(zip(levels, types))
        tables = dict(zip(levels, tables))
        for s in range(1 << lmax):
            active = [l for l in levels if s % (1 << (lmax - l)) == 0]
            fills = {l: _fill(bufs, l, tables[l], dt) for l in active if tables[l]}
            for l in active:
                f = bufs[l]
                if l in fills:
                    db, dc, v = fills[l]
                    shp = f.shape
                    f = f.reshape(shp[0], shp[1], -1).at[db, :, dc].set(v).reshape(shp)
                bufs[l] = _stream_collide(
                    f, types[l], c=c, w=w, opp=opp, omega=level_omega(omega0, l),
                    magic=magic, u_lid=u_lid, dt=dt)
        return tuple(bufs[l] for l in levels)

    return jax.jit(coarse)


def step_blocks(interiors: dict[int, np.ndarray], bids, cfg: dict, coarse_steps: int,
                *, dtype: str = "float32") -> dict[int, np.ndarray]:
    """Advance ``bid -> (Q, n, n, n)`` interior pdfs by whole coarse steps.

    ``cfg`` is the configuration file's mapping. Returns the new interiors
    as float32 numpy arrays, keyed like the input."""
    import jax
    import jax.numpy as jnp

    n = int(cfg["cells_per_block"][0])
    forest = Forest(bids, root_grid=cfg["root_grid"], cells=n, max_level=cfg["max_level"])
    lat = cfg["lattice"]
    levels = tuple(sorted(forest.levels))
    bufs, types, tables = [], [], []
    for l in levels:
        stack = np.zeros((len(forest.levels[l]), len(lat["w"]), n + 2, n + 2, n + 2),
                         dtype=np.float32)
        for i, b in enumerate(forest.levels[l]):
            stack[i, :, 1:-1, 1:-1, 1:-1] = interiors[b]
        bufs.append(jnp.asarray(stack).astype(dtype))
        types.append(jnp.asarray(cell_types(forest, l)))
        tables.append({k: tuple(jnp.asarray(a) for a in v)
                       for k, v in fill_tables(forest, l).items()})
    step = _coarse_step(levels, (tuple(tuple(v) for v in lat["c"]), tuple(lat["w"])),
                        float(cfg["omega"]), float(cfg["magic"]),
                        tuple(float(v) for v in cfg["u_lid"]), dtype)
    bufs = tuple(bufs)
    for _ in range(coarse_steps):
        bufs = step(bufs, tuple(types), tuple(tables))
    out = {}
    for l, buf in zip(levels, bufs):
        host = np.asarray(jax.device_get(buf.astype(jnp.float32)))
        for i, b in enumerate(forest.levels[l]):
            out[b] = host[i, :, 1:-1, 1:-1, 1:-1]
    return out


# -- the pdf field through AMR cycles --------------------------------------------------


class MigrationReference:
    """Follows the pdf field through AMR cycles of a two-level forest.

    ``dense`` holds the field at the finest level's resolution; a cell of a
    coarser block is stored replicated over the finest cells it covers."""

    def __init__(self, interiors: dict[int, np.ndarray], bids, cfg: dict,
                 *, dtype: str = "float32"):
        self.cfg = cfg
        self.dtype = np.dtype(dtype)
        self.n = int(cfg["cells_per_block"][0])
        self.forest = self._forest(bids)
        Q = len(cfg["lattice"]["w"])
        self.dense = np.empty((Q, *self.forest.extent_fine), dtype=self.dtype)
        for b in self.forest.bids():
            self._put(b, np.asarray(interiors[b]).astype(self.dtype))

    def _forest(self, bids) -> Forest:
        return Forest(bids, root_grid=self.cfg["root_grid"], cells=self.n,
                      max_level=self.cfg["max_level"])

    def _region(self, bid: int):
        lvl, xyz = decode_bid(bid, self.forest.root_grid)
        s = 1 << (self.forest.L - lvl)
        side = self.n * s
        return lvl, s, tuple(slice(v * side, (v + 1) * side) for v in xyz)

    def _put(self, bid: int, interior: np.ndarray) -> None:
        _lvl, s, sl = self._region(bid)
        for ax in (1, 2, 3):
            interior = np.repeat(interior, s, axis=ax)
        self.dense[(slice(None), *sl)] = interior

    def block(self, bid: int) -> np.ndarray:
        """(Q, n, n, n) float32 interior of a block of the current topology."""
        _lvl, s, sl = self._region(bid)
        return self.dense[(slice(None), *sl)][:, ::s, ::s, ::s].astype(np.float32)

    def stress_topology(self) -> list[int]:
        """The next topology under the two-level stress trigger: every fine
        octet merges into its parent, every coarse block splits in eight."""
        out = set()
        for lvl, bl in self.forest.levels.items():
            for b in bl:
                if lvl == 0:
                    out.update(child_bids(b))
                else:
                    out.add(b >> 3)
        return sorted(out)

    def cycle(self, new_bids) -> None:
        """Apply one AMR cycle that produced topology ``new_bids``: regions
        that became coarser take the mean of their finer cells."""
        old = self.forest
        new = self._forest(new_bids)
        for lvl, bl in new.levels.items():
            for b in bl:
                _l, s, sl = self._region(b)
                if s == 1:
                    continue
                o = [v.start for v in sl]
                if old.owner_level[o[0], o[1], o[2]] <= lvl:
                    continue  # was as coarse or coarser: already replicated
                reg = self.dense[(slice(None), *sl)]
                q, x, y, z = reg.shape
                mean = reg.astype(np.float32).reshape(
                    q, x // s, s, y // s, s, z // s, s).mean(axis=(2, 4, 6))
                for ax in (1, 2, 3):
                    mean = np.repeat(mean, s, axis=ax)
                self.dense[(slice(None), *sl)] = mean.astype(self.dtype)
        self.forest = new
