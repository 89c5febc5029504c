"""One AMR cycle under the configuration's refinement criterion."""


def run(sim) -> dict:
    report = sim.adapt()
    return {"stages": {name: s.seconds for name, s in report.stages.items()}}
