"""Seconds per AMR cycle in the pipeline's four stages (refine, proxy,
balance, migrate), as the program's ``CycleReport.stages`` time them."""


def read(rec):
    if not rec.cycles:
        return None
    return sum(sum(c["stages"].values()) for c in rec.cycles) / len(rec.cycles)
