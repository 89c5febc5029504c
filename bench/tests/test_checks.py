"""The check decides ``correct``: the control (the plain reference in
bfloat16, put in the program's place) and faults planted under the timed
path must each come out not correct. CPU, tiny size."""

from __future__ import annotations

import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from conftest import CELLS


def _run(root, workload, **kw):
    cell = harness.load_cell(root, workload)
    return harness.run_cell(cell, seed=2**31 + 5, seconds=0.5, trace=False,
                            t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_is_not_correct(tiny_root, workload):
    out = _run(tiny_root, workload, control=True)
    assert not out["correct"], out["check"]


# -- faults under the fused superstep (steady stepping) ---------------------------------


def _patch_superstep(sim, change):
    orig = sim.engine._fused_program

    def patched():
        fn, levels = orig()
        return (lambda pdfs: change(pdfs, fn(pdfs))), levels

    sim.engine._fused_program = patched


def _unchanged(sim):
    sim.engine.advance = lambda coarse_steps: None


def _half_batch(sim):
    def change(old, new):
        return tuple(jnp.concatenate([n[: n.shape[0] // 2], o[n.shape[0] // 2:]])
                     for o, n in zip(old, new))

    _patch_superstep(sim, change)


def _altered(sim):
    _patch_superstep(sim, lambda old, new: (new[0].at[0, 1, 4, 4, 4].add(0.01), *new[1:]))


# -- faults under the AMR cycle (stress cycles) --------------------------------------------


def _amr_unchanged(sim):
    sim.adapt = lambda force_rebalance=False: types.SimpleNamespace(executed=True, stages={})


def _wrap_adapt(sim, after):
    orig = sim.adapt

    def adapt(force_rebalance=False):
        report = orig(force_rebalance)
        after(sorted(sim.forest.all_blocks(), key=lambda b: b.bid))
        return report

    sim.adapt = adapt


def _amr_half_batch(sim):
    def drop(blocks):
        for b in blocks[::2]:
            sim.spec.interior(b.data["pdf"])[...] = 0.0

    _wrap_adapt(sim, drop)


def _amr_altered(sim):
    def alter(blocks):
        sim.spec.interior(blocks[0].data["pdf"])[1, 3, 3, 3] += np.float32(0.01)

    _wrap_adapt(sim, alter)


FAULTS = {
    "tiny.steady": [_unchanged, _half_batch, _altered],
    "tiny.amr-stress": [_amr_unchanged, _amr_half_batch, _amr_altered],
}


@pytest.mark.parametrize("workload,fault", [(w, f) for w, fs in FAULTS.items() for f in fs],
                         ids=lambda v: getattr(v, "__name__", v))
def test_planted_fault_is_not_correct(tiny_root, workload, fault):
    out = _run(tiny_root, workload, faults=fault)
    assert not out["correct"], out["check"]
