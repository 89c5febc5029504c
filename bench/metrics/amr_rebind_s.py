"""Seconds per AMR cycle outside the pipeline's stages: the flush before it,
the engine's rebind after it (adopt, mask refresh, host ghost exchange) and
the upload of the new topology's stacks."""


def read(rec):
    if not rec.cycles:
        return None
    return sum(c["wall_s"] - sum(c["stages"].values()) for c in rec.cycles) / len(rec.cycles)
