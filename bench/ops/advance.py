"""One coarse step through ``AMRLBM.advance``; the engine fences on
``block_until_ready``."""


def run(sim) -> dict:
    sim.advance(1)
    return {"coarse_steps": 1}
