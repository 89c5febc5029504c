"""Sum every pdf stack held on the device, once: it closes the traced window
of traffic whose own device work is transfers alone (the profiler records
no op for a host-to-device copy), so the trace shows that the uploaded
state reached the chip. Runs after the window's timing ends."""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _stack_sum():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda stacks: sum(jnp.sum(x, dtype=jnp.float32) for x in stacks))


def run(sim) -> dict:
    res = sim.arena.device()
    levels = sorted({b.level for b in sim.forest.all_blocks()})
    _stack_sum()(tuple(res.fetch(level, "pdf") for level in levels)).block_until_ready()
    return {}
